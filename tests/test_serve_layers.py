"""Layered serving front: ingest, scheduler, and the churn invariant.

The refactor's hard pin: a job's decisions — early (matched, corr,
decided_at_fraction) and final — are bit-for-bit independent of slot
packing, admission order, S-bucket capacity history, tick-rate cohorts
and verdict batching.  Randomized submit/evict/finish interleavings must
therefore reproduce a fixed-slot reference run exactly, including runs
that cross S-bucket boundaries and runs with the wavelet prefilter
pruning the bank underneath the slots.

Plus unit coverage of the new layers: bounded ingest queues with both
backpressure policies, trace-log rotation/replay, slot-bucket math,
cohort due-clocks, and multi-tenant routing.
"""
import numpy as np
import pytest

from repro import mrsim
from repro.core.database import SeriesBank, pack_series
from repro.serve.ingest import (BackpressureError, BoundedBuffer, IngestFront,
                                TraceLog)
from repro.serve.scheduler import (MIN_SLOT_BUCKET, SlotScheduler,
                                   TickCohorts, slot_bucket)
from repro.serve.tuning import MultiTenantTuningService, TuningService


@pytest.fixture(scope="module")
def paper_bank():
    from repro.core.filters import preprocess_bank

    psets = mrsim.paper_param_sets()
    series, labels = [], []
    for app in ("wordcount", "terasort"):
        for p in psets:
            series.append(mrsim.simulate_cpu_series(app, p, dt=0.25))
            labels.append(app)
    bank = pack_series(series, labels=labels)
    return SeriesBank(preprocess_bank(bank.series, bank.lengths),
                      bank.lengths, bank.labels, bank.entries)


# ---------------------------------------------------------------------------
# ingest: bounded queues
# ---------------------------------------------------------------------------

def test_bounded_buffer_reject_is_atomic():
    buf = BoundedBuffer(limit=8, policy="reject")
    buf.append(np.arange(6, dtype=np.float32))
    with pytest.raises(BackpressureError, match="buffer full"):
        buf.append(np.arange(3, dtype=np.float32))
    # nothing partially enqueued: the same chunk fits after a drain
    assert len(buf) == 6 and buf.dropped == 0
    got = buf.drain()
    np.testing.assert_array_equal(got, np.arange(6, dtype=np.float32))
    buf.append(np.arange(3, dtype=np.float32))
    assert len(buf) == 3


def test_bounded_buffer_drop_oldest_sheds_from_front():
    buf = BoundedBuffer(limit=8, policy="drop_oldest")
    buf.append(np.arange(6, dtype=np.float32))
    buf.append(10 + np.arange(4, dtype=np.float32))   # sheds 2 oldest
    assert buf.dropped == 2 and len(buf) == 8
    np.testing.assert_array_equal(
        buf.drain(), np.concatenate([np.arange(2, 6),
                                     10 + np.arange(4)]).astype(np.float32))
    # a single chunk larger than the whole queue keeps only its tail
    buf.append(np.arange(20, dtype=np.float32))
    assert len(buf) == 8 and buf.dropped == 2 + 12
    np.testing.assert_array_equal(
        buf.drain(), np.arange(12, 20, dtype=np.float32))


def test_service_backpressure_policies(paper_bank):
    svc = TuningService(paper_bank, queue_limit=16, queue_policy="reject")
    svc.submit("j", expected_len=64)
    svc.push("j", np.zeros(16, np.float32))
    with pytest.raises(BackpressureError):
        svc.push("j", np.zeros(1, np.float32))
    svc.tick()                                        # drains the queue
    svc.push("j", np.zeros(16, np.float32))           # accepted again


# ---------------------------------------------------------------------------
# ingest: batched drain
# ---------------------------------------------------------------------------

def _feed(fronts, rng, jobs, rounds, variance):
    """The same ragged pushes into every front: some jobs skip a round,
    half the variance pushes carry explicit values (the rest default to
    the filter residual at drain time)."""
    for _ in range(rounds):
        for jid in jobs:
            n = int(rng.integers(0, 6))
            if not n:
                continue
            x = rng.uniform(0, 1, size=n).astype(np.float32)
            v = None
            if variance and rng.random() < 0.5:
                v = rng.uniform(0, 0.01, size=n).astype(np.float32)
            for front in fronts:
                front.push(jid, x, variance=v)


@pytest.mark.parametrize("variance", [False, True])
def test_ingest_drain_many_equals_sequential_drains(variance):
    jobs = [f"j{i}" for i in range(6)]
    kw = dict(denoise=True, track_variance=variance)
    batched, single = IngestFront(**kw), IngestFront(**kw)
    for front in (batched, single):
        for jid in jobs:
            front.register(jid)
    rng = np.random.default_rng(5)
    for _ in range(4):
        _feed((batched, single), rng, jobs, 2, variance)
        got = batched.drain_many(jobs, with_variance=variance)
        want = [single.drain(jid, with_variance=variance) for jid in jobs]
        for g, w in zip(got, want):
            g, w = (g, w) if variance else ((g, None), (w, None))
            for a, b in zip(g, w):
                if b is None:
                    assert a is None
                else:
                    np.testing.assert_array_equal(a, b)
        for jid in jobs:
            np.testing.assert_array_equal(batched._jobs[jid].filt._z,
                                          single._jobs[jid].filt._z)
    assert batched.filter_count == 4
    assert all(not batched.has_data(jid) for jid in jobs)


def test_ingest_filter_count_one_per_batched_drain():
    for denoise, per_drain in ((True, 1), (False, 0)):
        front = IngestFront(denoise=denoise)
        for jid in ("a", "b", "c"):
            front.register(jid)
        front.drain_many(["a", "b", "c"])           # nothing to filter
        assert front.filter_count == 0
        for k in range(1, 4):
            front.push("a", np.ones(3, np.float32))
            front.push("c", np.ones(1, np.float32))
            out = front.drain_many(["a", "b", "c"])
            assert out[1] is None
            assert [o.shape for o in (out[0], out[2])] == [(3,), (1,)]
            assert front.filter_count == k * per_drain
    with pytest.raises(ValueError, match="track_variance"):
        front.drain_many(["a"], with_variance=True)


def test_denoised_service_snapshot_mid_stream_continues_exactly(paper_bank):
    """A snapshot taken between ticks, with samples still queued, carries
    each job's host filter state: the restored service filters the rest
    of the stream bit-identically to one that never stopped."""
    from repro.serve.recovery import restore_service, snapshot_service

    rng = np.random.default_rng(2)
    qs = {f"j{i}": rng.uniform(0, 1, size=48).astype(np.float32)
          for i in range(3)}
    svc = TuningService(paper_bank, band=8, denoise=True)
    for jid, q in qs.items():
        svc.submit(jid, expected_len=len(q))
    for t in range(3):
        for jid, q in qs.items():
            svc.push(jid, q[8 * t: 8 * t + 8])
        svc.tick()
    for jid, q in qs.items():                       # queued, not drained
        svc.push(jid, q[24:29])
    twin = restore_service(snapshot_service(svc), paper_bank)
    for jid in qs:
        z = twin._front._jobs[jid].filt._z
        assert isinstance(z, np.ndarray) and z.dtype == np.float32
        np.testing.assert_array_equal(z, svc._front._jobs[jid].filt._z)
    for s in (svc, twin):
        s.tick()
        for jid, q in qs.items():
            s.push(jid, q[29:])
        s.tick()
    for jid in qs:
        np.testing.assert_array_equal(twin._jobs[jid].x.view(),
                                      svc._jobs[jid].x.view())
        np.testing.assert_array_equal(twin._jobs[jid].last_sims,
                                      svc._jobs[jid].last_sims)


# ---------------------------------------------------------------------------
# ingest: trace log
# ---------------------------------------------------------------------------

def test_trace_log_rotation_and_replay(tmp_path):
    log = TraceLog(str(tmp_path), max_segment_bytes=4 * 8, max_segments=2)
    rng = np.random.default_rng(0)
    a_parts, b_parts = [], []
    for i in range(6):
        ca = rng.normal(size=4).astype(np.float32)
        cb = rng.normal(size=4).astype(np.float32)
        log.append("a", ca)
        log.append("b", cb)
        a_parts.append(ca)
        b_parts.append(cb)
    log.flush()
    # rotation kept only the newest max_segments files
    assert len(log.segments()) == 2
    import os
    on_disk = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert on_disk == sorted(log.segments())
    # replay returns the RETAINED window, in ingest order, per job
    got_a = log.read_job("a")
    want_a = np.concatenate(a_parts)
    assert got_a.shape[0] < want_a.shape[0]           # oldest rotated out
    np.testing.assert_array_equal(got_a, want_a[-got_a.shape[0]:])
    assert log.read_job("nope").shape == (0,)


def test_service_traces_accepted_pushes(tmp_path, paper_bank):
    log = TraceLog(str(tmp_path))
    svc = TuningService(paper_bank, trace_log=log)
    q = np.linspace(0, 1, 32, dtype=np.float32)
    svc.submit("j", expected_len=32)
    for lo in range(0, 32, 8):
        svc.push("j", q[lo: lo + 8])
        svc.tick()
    svc.finish("j")
    log.flush()
    np.testing.assert_array_equal(log.read_job("j"), q)


# ---------------------------------------------------------------------------
# scheduler: buckets and cohorts
# ---------------------------------------------------------------------------

def test_slot_bucket_math():
    assert slot_bucket(0, 64) == MIN_SLOT_BUCKET
    assert slot_bucket(4, 64) == 4
    assert slot_bucket(5, 64) == 8
    assert slot_bucket(9, 64) == 16
    assert slot_bucket(100, 64) == 64                 # clamped
    assert slot_bucket(3, 2) == 2                     # max below floor


def test_scheduler_grow_release_shrink():
    sched = SlotScheduler(64)
    assert sched.capacity == MIN_SLOT_BUCKET
    for i in range(4):
        slot, grow = sched.admit(f"j{i}")
        assert grow is None and slot == i
    slot, grow = sched.admit("j4")                    # crosses 4 -> 8
    assert sched.capacity == 8 and slot == 4
    np.testing.assert_array_equal(grow, [0, 1, 2, 3, -1, -1, -1, -1])
    # release three, leaving j1 and j4: shrink compacts them (stable)
    for jid in ("j0", "j2", "j3"):
        sched.release(jid)
    src, moves = sched.shrink_plan()
    assert sched.capacity == 4
    np.testing.assert_array_equal(src, [1, 4, -1, -1])
    assert moves == {"j1": 0, "j4": 1}
    assert sched.slot_of("j4") == 1
    assert sched.shrink_plan() is None                # already minimal


def test_scheduler_max_slots_pin():
    sched = SlotScheduler(2)
    sched.admit("a")
    sched.admit("b")
    with pytest.raises(RuntimeError, match="slots busy"):
        sched.admit("c")
    with pytest.raises(ValueError, match="already scheduled"):
        sched.admit("a")


def test_tick_cohorts_due_clocks():
    c = TickCohorts()
    c.assign("fast", 100.0)
    c.assign("slow", 4.0)
    c.assign("always", None)
    assert c.n_cohorts == 3
    # first beat: everyone due (clocks start at -inf)
    assert c.due_jobs(0.0) == {"fast", "slow", "always"}
    # 10 ms later only the 100 Hz cohort (and the unrated job) is due
    assert c.due_jobs(0.01) == {"fast", "always"}
    # the 4 Hz cohort re-arms at 0.25 s
    assert "slow" not in c.due_jobs(0.2)
    assert "slow" in c.due_jobs(0.26)
    # clock-less query = legacy drain-everything
    assert c.due_jobs(None) == {"fast", "slow", "always"}


def test_service_cohorts_meter_drains(paper_bank):
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("exim", p, dt=0.25)
    svc = TuningService(paper_bank, band=16, denoise=True)
    svc.submit("fast", expected_len=len(q), tick_hz=100.0)
    svc.submit("slow", expected_len=len(q), tick_hz=4.0)
    lo = 0
    for t in range(20):                               # 100 Hz wall clock
        svc.push("fast", q[lo: lo + 4])
        svc.push("slow", q[lo: lo + 4])
        lo += 4
        svc.tick(now=t / 100.0)
    fast, slow = svc._jobs["fast"], svc._jobs["slow"]
    assert fast.n == 80                               # drained every beat
    # the slow cohort was touched only on its own period: 0.20 s of wall
    # clock at 4 Hz = the t=0 beat, nothing else due before 0.25 s; the
    # other 76 samples just sit in the ingest queue (none lost).
    assert slow.n == 4 and slow.x.view().shape[0] == 4
    assert len(svc._front._jobs["slow"].buffer) == 76
    assert svc.dispatch_count <= svc.ticks
    d = svc.finish_many(["fast", "slow"])
    assert d["fast"].corr == d["slow"].corr           # same data, same verdict


# ---------------------------------------------------------------------------
# churn invariance: the refactor's hard pin
# ---------------------------------------------------------------------------

def _job_chunks(q, rng):
    """Fixed per-job chunk schedule (identical in every run)."""
    chunks, lo = [], 0
    while lo < len(q):
        c = int(rng.integers(4, 24))
        chunks.append(q[lo: lo + c])
        lo += c
    return chunks


def _decision_key(d):
    return None if d is None else (d.matched, d.corr, d.decided_at_fraction,
                                   tuple(sorted(d.scores.items())))


def _reference_run(bank, jobs, **kw):
    """Fixed-slot, fixed-order baseline: all jobs submitted up front,
    chunk i consumed at tick i, sequential finishes."""
    svc = TuningService(bank, elastic_slots=False, **kw)
    for jid, chunks in jobs.items():
        svc.submit(jid, expected_len=sum(len(c) for c in chunks))
    early = {}
    for t in range(max(len(c) for c in jobs.values())):
        for jid, chunks in jobs.items():
            if t < len(chunks):
                svc.push(jid, chunks[t])
        for jid, d in svc.tick().items():
            if d is not None:
                early.setdefault(jid, d)
    finals = {jid: svc.finish(jid) for jid in jobs}
    return early, finals


def _churned_run(bank, jobs, seed, **kw):
    """Elastic slots, randomized admission order + staggered starts,
    decoy jobs evicted mid-run (forcing compaction + slot moves), and
    grouped/deferred finishes.  Job j still consumes chunk i at its i-th
    data tick, so the information schedule matches the reference."""
    rng = np.random.default_rng(seed)
    svc = TuningService(bank, **kw)
    order = list(jobs)
    rng.shuffle(order)
    start = {jid: int(rng.integers(0, 4)) for jid in order}
    decoys = {}
    early, finals, t = {}, {}, 0
    live = set()
    while len(finals) < len(jobs):
        for jid in order:                   # staggered admissions
            if start[jid] == t:
                svc.submit(jid, expected_len=sum(
                    len(c) for c in jobs[jid]))
                live.add(jid)
        if t == 1:                          # decoys force bucket growth
            for i in range(3):
                d = f"decoy{i}"
                svc.submit(d, expected_len=64)
                decoys[d] = 0
        for jid in sorted(live):
            k = t - start[jid]
            if k < len(jobs[jid]):
                svc.push(jid, jobs[jid][k])
        for d in list(decoys):
            svc.push(d, np.full(8, 0.5, np.float32))
            decoys[d] += 1
        for jid, d in svc.tick().items():
            if d is not None and jid in jobs:
                early.setdefault(jid, d)
        if t == 4:                          # evict decoys mid-run
            for d in list(decoys):
                svc.evict(d)
                del decoys[d]
        done = [jid for jid in sorted(live)
                if t - start[jid] + 1 >= len(jobs[jid])]
        if done:
            if rng.integers(2):             # grouped batch finish
                finals.update(svc.finish_many(done))
            else:                           # deferred drain queue
                for jid in done:
                    svc.finish_later(jid)
                finals.update(svc.drain_finishes())
            live.difference_update(done)
        t += 1
    assert svc.slot_repack_count > 0        # buckets actually crossed
    assert svc.evicted_count == 3
    return early, finals


@pytest.mark.parametrize("seed", [0, 1])
def test_churn_invariance(paper_bank, seed):
    rng = np.random.default_rng(100 + seed)
    psets = mrsim.paper_param_sets()
    jobs = {}
    for i, (app, run) in enumerate((("wordcount", 1), ("exim", 2),
                                    ("terasort", 1), ("exim", 3))):
        q = mrsim.simulate_cpu_series(app, psets[i % len(psets)], run=run,
                                      dt=0.25)
        jobs[f"{app}{i}"] = _job_chunks(q, rng)

    kw = dict(band=16, threshold=0.85, margin=0.02, stable_ticks=2,
              min_fraction=0.15, denoise=True, slots=16)
    early_ref, fin_ref = _reference_run(paper_bank, jobs, **kw)
    early_chn, fin_chn = _churned_run(paper_bank, jobs, seed, **kw)

    # bit-for-bit: same early decisions (matched, corr,
    # decided_at_fraction, full score dict) and same final verdicts,
    # regardless of slot packing, admission order or capacity history.
    assert early_ref.keys() == early_chn.keys()
    for jid in early_ref:
        assert _decision_key(early_ref[jid]) == _decision_key(early_chn[jid])
    for jid in jobs:
        assert _decision_key(fin_ref[jid]) == _decision_key(fin_chn[jid])


def test_churn_invariance_with_prefilter(paper_bank):
    """S-axis churn composes with K-axis pruning: the prefiltered churned
    run still reproduces the prefiltered fixed-slot run bitwise."""
    rng = np.random.default_rng(7)
    psets = mrsim.paper_param_sets()
    jobs = {}
    for i, app in enumerate(("wordcount", "exim", "terasort")):
        q = mrsim.simulate_cpu_series(app, psets[i], run=1, dt=0.25)
        jobs[f"{app}{i}"] = _job_chunks(q, rng)

    kw = dict(band=16, threshold=0.85, margin=0.02, stable_ticks=2,
              min_fraction=0.15, denoise=True, slots=16,
              prefilter_top=2, prefilter_margin=0.02)
    early_ref, fin_ref = _reference_run(paper_bank, jobs, **kw)
    early_chn, fin_chn = _churned_run(paper_bank, jobs, 7, **kw)

    assert early_ref.keys() == early_chn.keys()
    for jid in early_ref:
        assert _decision_key(early_ref[jid]) == _decision_key(early_chn[jid])
    for jid in jobs:
        assert _decision_key(fin_ref[jid]) == _decision_key(fin_chn[jid])


# ---------------------------------------------------------------------------
# multi-tenant front
# ---------------------------------------------------------------------------

def test_multi_tenant_routing_and_isolation(paper_bank):
    # tenant B sees only the first half of the bank (its own references)
    half = len(paper_bank) // 2
    sub = SeriesBank(paper_bank.series[:half], paper_bank.lengths[:half],
                     paper_bank.labels[:half], paper_bank.entries[:half])
    front = MultiTenantTuningService({"A": paper_bank, "B": sub},
                                     band=16, denoise=True)
    assert front.tenants == ("A", "B")

    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("wordcount", p, dt=0.25)
    front.submit("ja", expected_len=len(q), tenant="A")
    front.submit("jb", expected_len=len(q), tenant="B")
    with pytest.raises(ValueError, match="already in flight"):
        front.submit("ja", expected_len=8, tenant="B")
    with pytest.raises(KeyError, match="unknown tenant"):
        front.submit("jc", expected_len=8, tenant="C")

    ticks = 0
    for lo in range(0, len(q), 16):
        front.push("ja", q[lo: lo + 16])
        front.push("jb", q[lo: lo + 16])
        front.tick()
        ticks += 1
    # per-engine dispatch bound: data-ticks x tenants
    assert front.dispatch_count <= ticks * 2
    assert front.n_active == 2

    d = front.finish_many(["ja", "jb"])
    # same query, but each verdict is scored against the TENANT's bank:
    # B's score dict only covers the sub-bank's workloads
    assert set(d["ja"].scores) == set(paper_bank.labels)
    assert set(d["jb"].scores) == set(sub.labels)
    assert front.n_active == 0

    # isolation: a single-tenant service over the same sub-bank renders
    # the identical verdict for B's job
    solo = TuningService(sub, band=16, denoise=True)
    solo.submit("jb", expected_len=len(q))
    for lo in range(0, len(q), 16):
        solo.push("jb", q[lo: lo + 16])
        solo.tick()
    want = solo.finish("jb")
    assert d["jb"].matched == want.matched
    assert d["jb"].corr == want.corr
