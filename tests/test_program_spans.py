"""The service's profiler spans (``tuner.*``): structure and arguments.

A small ``TuningService`` runs a few ticks and one ``finish_many`` under
``jax.profiler.trace``; the captured ``.xplane.pb`` is read back with
``ProfileData`` and the ``tuner.*`` events are nested by their intervals
(one host thread), which is how a trace viewer shows them.
"""
import glob
import os
import warnings

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.database import pack_series
from repro.serve.tuning import TuningService

K = 16
JOBS = 4
PUSH = 8
TICKS = 3

TICK_CHILDREN = ["tuner.drain", "tuner.repack", "tuner.chunks",
                 "tuner.dispatch", "tuner.pull", "tuner.decide"]
VERDICT_CHILDREN = ["tuner.retire", "tuner.verdict.pack",
                    "tuner.verdict.dispatch", "tuner.verdict.pull",
                    "tuner.verdict.render"]

MODES = {
    "scored": dict(),
    "distance": dict(score_in_flight=False),
    "prob": dict(min_probability=0.5),
}


class Span:
    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.args = name, start, end, stats
        self.children = []

    def names(self):
        return [c.name for c in self.children]

    def find(self, name):
        return [c for c in self.children if c.name == name]


def _bank():
    rng = np.random.default_rng(7)
    series, labels = [], []
    for k in range(K):
        n = 40 + 4 * (k % 5)
        t = np.linspace(0.0, 1.0, n)
        base = 0.5 + 0.4 * np.sin(2 * np.pi * (k % 4 + 1) * t)
        series.append(np.clip(base + 0.05 * rng.standard_normal(n), 0, 1))
        labels.append(f"app{k % 4}")
    return pack_series(series, labels=labels)


@pytest.fixture(scope="module")
def bank():
    return _bank()


def _queries():
    rng = np.random.default_rng(11)
    return {f"job{j}": np.clip(0.5 + 0.4 * np.sin(np.linspace(
                0, 2 * np.pi * (j + 1), 40)) + 0.05 * rng.standard_normal(40),
                0, 1).astype(np.float32)
            for j in range(JOBS)}


def _drive(svc, queries):
    """TICKS ticks of PUSH samples a job, then three more samples for the
    first two jobs and one ``finish_many`` of them (its drain tick
    scores those three)."""
    for jid, q in queries.items():
        svc.submit(jid, expected_len=len(q))
    for t in range(TICKS):
        for jid, q in queries.items():
            svc.push(jid, q[t * PUSH: (t + 1) * PUSH])
        svc.tick()
    done = list(queries)[:2]
    for jid in done:
        svc.push(jid, queries[jid][TICKS * PUSH: TICKS * PUSH + 3])
    return svc.finish_many(done)


def _capture(tmp_path, fn):
    """Run ``fn`` under the profiler -> (its result, the top-level
    ``tuner.*`` spans with their children nested)."""
    with jax.profiler.trace(str(tmp_path)):
        result = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    flat = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("tuner."):
                        flat.append(Span(ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         dict(ev.stats)))
    flat.sort(key=lambda s: (s.start, -s.end))
    roots, stack = [], []
    for sp in flat:
        while stack and sp.start >= stack[-1].end:
            stack.pop()
        (stack[-1].children if stack else roots).append(sp)
        stack.append(sp)
    return result, roots


def _service(bank, **kw):
    return TuningService(bank, band=8, slots=JOBS, denoise=True, **kw)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tick_and_finish_spans(tmp_path, bank, mode):
    # compile every program first, so the traced run holds the service
    # alone
    _drive(_service(bank, **MODES[mode]), _queries())
    svc = _service(bank, **MODES[mode])
    _, roots = _capture(tmp_path, lambda: _drive(svc, _queries()))

    assert [r.name for r in roots] == ["tuner.tick"] * TICKS + \
        ["tuner.finish_many"]
    want = [n for n in TICK_CHILDREN
            if not (mode == "distance" and n == "tuner.pull")]
    for i, tick in enumerate(roots[:TICKS]):
        assert tick.args == {"tick": i + 1, "internal": 0}
        assert tick.names() == want
        drain, = tick.find("tuner.drain")
        assert drain.args == {"jobs": JOBS, "samples": JOBS * PUSH,
                              "filtered": 1}
        filt, = drain.find("tuner.filter")
        assert filt.args == {"jobs": JOBS, "samples": JOBS * PUSH}
        repack, = tick.find("tuner.repack")
        assert set(repack.args) == {"slot_repacks", "k_repacks"}
        chunks, = tick.find("tuner.chunks")
        assert chunks.args == {"chunk": PUSH, "slots": svc.slot_capacity}
        dispatch, = tick.find("tuner.dispatch")
        assert dispatch.args == {"mode": mode, "k_live": K, "shards": 1}
        decide, = tick.find("tuner.decide")
        assert decide.args["jobs"] == JOBS
        assert 0 <= decide.args["decisions"] <= JOBS
        # no early decision can come before the third tick, so each of
        # these ticks ranks every scored job in its one batched call
        scored = mode != "distance"
        assert decide.args["ranked"] == (JOBS if scored else 0)

    fin = roots[-1]
    assert fin.args == {"jobs": 2}
    assert fin.names() == ["tuner.tick"] + VERDICT_CHILDREN
    inner, = fin.find("tuner.tick")
    assert inner.args == {"tick": TICKS + 1, "internal": 1}
    assert inner.names() == want
    inner_decide, = inner.find("tuner.decide")
    assert 0 <= inner_decide.args["ranked"] <= inner_decide.args["jobs"]
    inner_drain, = inner.find("tuner.drain")
    assert inner_drain.args == {"jobs": 2, "samples": 6, "filtered": 1}
    assert [f.args for f in inner_drain.find("tuner.filter")] == [
        {"jobs": 2, "samples": 6}]
    assert fin.find("tuner.retire")[0].args == {"jobs": 2}
    assert fin.find("tuner.verdict.pack")[0].args == {
        "jobs": 2, "padded": 2, "npad": 32}
    assert fin.find("tuner.verdict.dispatch")[0].args == {"shards": 1}
    assert fin.find("tuner.verdict.render")[0].args == {"jobs": 2,
                                                         "ranked": 2}
    assert svc.ticks == TICKS + 1


def test_no_filter_spans_without_denoise(tmp_path, bank):
    svc = TuningService(bank, band=8, slots=JOBS)
    _, roots = _capture(tmp_path, lambda: _drive(svc, _queries()))
    ticks = [r for r in roots if r.name == "tuner.tick"]
    assert len(ticks) == TICKS
    for tick in ticks:
        drain, = tick.find("tuner.drain")
        assert drain.args["filtered"] == 0
        assert drain.find("tuner.filter") == []
    assert svc._front.filter_count == 0


def test_prefilter_span_closes_the_tick(tmp_path, bank):
    svc = _service(bank, prefilter_top=4, prefilter_min_fraction=0.0)
    _, roots = _capture(tmp_path, lambda: _drive(svc, _queries()))
    for tick in roots[:TICKS]:
        assert tick.names() == TICK_CHILDREN + ["tuner.prefilter"]
        assert tick.find("tuner.prefilter")[0].args == {}


def test_drain_finishes_spans(tmp_path, bank):
    svc = _service(bank, finish_batch=8)

    def run():
        queries = _queries()
        for jid, q in queries.items():
            svc.submit(jid, expected_len=len(q))
            svc.push(jid, q[:PUSH])
        svc.tick()
        for jid in list(queries)[:3]:
            svc.finish_later(jid)
        return svc.drain_finishes()

    out, roots = _capture(tmp_path, run)
    assert len(out) == 3
    assert [r.name for r in roots] == ["tuner.tick"] + ["tuner.retire"] * 3 \
        + ["tuner.finish_many"]
    fin = roots[-1]
    assert fin.args == {"jobs": 3}
    assert fin.names() == VERDICT_CHILDREN[1:]


def test_traced_outputs_equal_untraced(tmp_path, bank):
    """The spans observe; scores and decisions come out bit-identical
    with the profiler on and off."""
    plain = _service(bank)
    want = _drive(plain, _queries())
    traced = _service(bank)
    got, _ = _capture(tmp_path, lambda: _drive(traced, _queries()))
    assert set(got) == set(want)
    for jid in want:
        assert got[jid].matched == want[jid].matched
        assert got[jid].corr == want[jid].corr
        assert got[jid].scores == want[jid].scores
    for jid in plain._jobs:
        np.testing.assert_array_equal(traced._jobs[jid].last_sims,
                                      plain._jobs[jid].last_sims)
