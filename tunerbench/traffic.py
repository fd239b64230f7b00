"""The load generator: one general driver per loop kind, read from a mix.

``mixes/<name>.json`` holds the parameters:

* ``loop``: ``"closed"`` — every in-flight job pushes its next
  ``push_samples`` as soon as its previous push was scored, and a job
  whose whole trace is scored is finished (one ``finish_many`` per loop
  iteration, at most ``max_finish_batch`` jobs) and replaced at once from
  the pool; or ``"open"`` — every job pushes each sample when it is taken,
  at ``sample_hz`` per job, whatever the service does, and a job whose
  trace ends is finished and replaced.
* ``jobs_in_flight``: jobs submitted at once.
* ``start``: ``"random_phase"`` pre-advances each job, in set-up and in
  ``warm_push``-sample pushes, to a uniformly drawn point of its trace,
  so the in-flight set looks like a cluster in steady state;
  ``"fresh"`` starts every job at its first sample (the first
  ``warm_push`` samples are pushed and scored in set-up).
* ``pool``: jobs generated at set-up (reused in order if the window
  needs more).

The driver calls the service's public API only: ``submit``, ``push``,
``tick`` and ``finish_many``.  It records what the comparison needs: a
snapshot of one scored job's in-flight scores at every tick, every early
decision and every verdict.
"""

from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
from typing import Dict, List

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Flight:
    job: object                 # deploy.Job
    job_id: str
    pos: int = 0                # samples pushed
    scored: int = 0             # samples a tick has consumed
    offered_at: float = 0.0     # when the last sample was offered


@dataclasses.dataclass
class Record:
    """What a window produced, for the metrics and the comparison."""
    samples: int = 0
    pushes: int = 0
    ticks: int = 0
    window_s: float = 0.0
    score_latency: List[float] = dataclasses.field(default_factory=list)
    verdict_latency: List[float] = dataclasses.field(default_factory=list)
    late: List[float] = dataclasses.field(default_factory=list)
    tick_s: List[float] = dataclasses.field(default_factory=list)
    snapshots: List[tuple] = dataclasses.field(default_factory=list)
    early: List[tuple] = dataclasses.field(default_factory=list)
    verdicts: List[tuple] = dataclasses.field(default_factory=list)
    tick_work: List[tuple] = dataclasses.field(default_factory=list)
    verdict_work: List[list] = dataclasses.field(default_factory=list)


class Driver:
    def __init__(self, svc, pool, mix: Dict, seed: int, span=None):
        self.svc = svc
        self.pool = pool
        self.mix = mix
        self.rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
        self.span = span or (lambda name: contextlib.nullcontext())
        self.next_job = 0
        self.flights: Dict[str, Flight] = {}
        self.rec = Record()
        self.tracing = False

    # -- jobs -----------------------------------------------------------------
    def _new_flight(self) -> Flight:
        i = self.next_job
        self.next_job += 1
        job = self.pool[i % len(self.pool)]
        jid = job.job_id if i < len(self.pool) else \
            f"{job.job_id}.{i // len(self.pool)}"
        self.svc.submit(jid, expected_len=len(job))
        fl = Flight(job, jid)
        self.flights[jid] = fl
        return fl

    def _push(self, fl: Flight, n: int) -> int:
        x = fl.job.x[fl.pos: fl.pos + n]
        if not x.size:
            return 0
        v = None if fl.job.v is None else fl.job.v[fl.pos: fl.pos + n]
        with self.span("bench.push"):
            self.svc.push(fl.job_id, x, variance=v)
        fl.pos += x.size
        return int(x.size)

    def _tick(self, pushed: List[Flight]):
        svc = self.svc
        if self.tracing:
            self.rec.tick_work.append(tuple(
                (fl.scored, fl.pos - fl.scored, fl.job.x.shape[0])
                for fl in pushed))
        for fl in pushed:
            fl.scored = fl.pos
        t0 = clock()
        with self.span("bench.tick"):
            out = svc.tick()
        t = clock()
        self.rec.ticks += 1
        self.rec.tick_s.append(t - t0)
        for jid, d in out.items():
            if d is not None and jid in self.flights:
                self.rec.early.append((self.flights[jid].job,
                                       self.flights[jid].pos, d))
        if pushed:
            fl = pushed[int(self.rng.integers(len(pushed)))]
            job = svc._jobs[fl.job_id]
            probs = None if job.last_probs is None \
                else job.last_probs.copy()
            self.rec.snapshots.append((fl.job, fl.pos, job.last_sims.copy(),
                                       probs))
        return t

    def _finish(self, done: List[Flight]):
        if not done:
            return clock()
        ids = [fl.job_id for fl in done]
        if self.tracing:
            self.rec.verdict_work.append([fl.job.x.shape[0] for fl in done])
        with self.span("bench.finish_many"):
            res = self.svc.finish_many(ids)
        t = clock()
        for fl in done:
            self.rec.verdicts.append((fl.job, res[fl.job_id]))
            del self.flights[fl.job_id]
        return t

    # -- set-up ------------------------------------------------------------------
    def start(self) -> None:
        """Submit the in-flight set and pre-advance it."""
        for _ in range(self.mix["jobs_in_flight"]):
            self._new_flight()
        step = self.mix["warm_push"]
        target = {}
        for jid, fl in self.flights.items():
            n = fl.job.x.shape[0]
            target[jid] = int(self.rng.integers(0, n)) \
                if self.mix["start"] == "random_phase" else min(step, n)
        while True:
            moved = []
            for jid, fl in self.flights.items():
                k = min(step, target[jid] - fl.pos)
                if k > 0 and self._push(fl, k):
                    moved.append(fl)
            if not moved:
                break
            self.svc.tick()
            for fl in moved:
                fl.scored = fl.pos

    def verdict_shapes(self):
        """(jobs, padded query length) of every verdict the window can
        reach: the power-of-two job buckets up to the largest batch, times
        the power-of-two length buckets of the pool's traces."""
        jbs = [1 << b for b in range(
            _pow2(self.mix["max_finish_batch"], 1).bit_length())]
        lens = sorted({_pow2(j.x.shape[0], 8) for j in self.pool})
        return [(jb, n) for jb in jbs for n in lens]

    def finished(self) -> List[Flight]:
        """Jobs whose whole trace was pushed and scored, oldest first, at
        most ``max_finish_batch`` of them."""
        return [fl for fl in self.flights.values()
                if fl.pos == fl.job.x.shape[0]][: self.mix["max_finish_batch"]]


def _pow2(n: int, lo: int) -> int:
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


class ClosedLoop(Driver):
    """Backlog: every job keeps one push in flight."""

    def window(self, seconds: float) -> None:
        rec = self.rec
        step = self.mix["push_samples"]
        t0 = clock()
        while clock() - t0 < seconds:
            pushed = []
            with self.span("bench.generate"):
                for fl in list(self.flights.values()):
                    if fl.pos < fl.job.x.shape[0]:
                        k = self._push(fl, step)
                        rec.samples += k
                        rec.pushes += 1
                        pushed.append(fl)
                        if fl.pos == fl.job.x.shape[0]:
                            fl.offered_at = clock()
            self._tick(pushed)
            done = self.finished()
            t = self._finish(done)
            rec.verdict_latency += [t - fl.offered_at for fl in done]
            with self.span("bench.generate"):
                for _ in done:
                    self._new_flight()
        rec.window_s = clock() - t0


class OpenLoop(Driver):
    """Live: each job pushes every sample when it is taken."""

    def window(self, seconds: float) -> None:
        rec = self.rec
        hz = float(self.mix["sample_hz"])
        t0 = clock()
        end = t0 + seconds
        heap = []

        def schedule(fl: Flight, first: float):
            heapq.heappush(heap, (first, fl.job_id))

        for fl in self.flights.values():
            schedule(fl, t0 + float(self.rng.random()) / hz)
        pending: List[tuple] = []
        while True:
            now = clock()
            pushed = {}
            with self.span("bench.generate"):
                while heap and heap[0][0] <= now and heap[0][0] < end:
                    due, jid = heapq.heappop(heap)
                    fl = self.flights[jid]
                    rec.late.append(clock() - due)
                    self._push(fl, 1)
                    rec.samples += 1
                    rec.pushes += 1
                    pending.append(due)
                    pushed[jid] = fl
                    if fl.pos < fl.job.x.shape[0]:
                        heapq.heappush(heap, (due + 1.0 / hz, jid))
                    else:
                        fl.offered_at = due
            if not pending:
                if not heap or heap[0][0] >= end:
                    break
                time.sleep(max(0.0, min(heap[0][0], end) - clock()))
                continue
            t = self._tick(list(pushed.values()))
            rec.score_latency += [t - d for d in pending]
            pending = []
            done = self.finished()
            t = self._finish(done)
            rec.verdict_latency += [t - fl.offered_at for fl in done]
            with self.span("bench.generate"):
                for _ in done:
                    nf = self._new_flight()
                    schedule(nf, t + float(self.rng.random()) / hz)
        rec.window_s = clock() - t0


LOOPS = {"closed": ClosedLoop, "open": OpenLoop}


def driver(svc, pool, mix, seed, span=None) -> Driver:
    return LOOPS[mix["loop"]](svc, pool, mix, seed, span)
