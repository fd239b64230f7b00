"""Crash-safe serving: snapshot/restore + WAL replay == never crashed.

The recovery twin of the churn-invariance suite: a service snapshotted
and rehydrated at ANY point of a command schedule — or killed and
rebuilt from snapshot + journal tail — must continue the schedule with
decisions, scores and counters bit-identical to a service that ran it
uninterrupted.  Covers the exact and probabilistic decision rules, the
wavelet prefilter, denoised ingest, mid-repack snapshots (pending
fresh-slot resets), zero-job snapshots, torn journal tails and torn
snapshot steps, plus hypothesis-driven random interleavings of
push/tick/snapshot/crash/restore/evict/finish.
"""
import json
import os
import warnings

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.database import pack_series
from repro.runtime.chaos import truncate_file
from repro.serve.ingest import TraceLog
from repro.serve.recovery import (RecoverableTuningService, restore_service,
                                  snapshot_service)
from repro.serve.tuning import TuningService


def _bank(k=5, seed=0, base=90):
    rng = np.random.default_rng(seed)
    series = [np.abs(np.cumsum(rng.normal(size=base + 7 * i)))
              .astype(np.float32) for i in range(k)]
    return pack_series(series, labels=[f"w{i}" for i in range(k)])


def _streams(n=3, seed=42, length=80):
    r = np.random.default_rng(seed)
    return {f"j{i}": np.abs(np.cumsum(r.normal(size=length)))
            .astype(np.float32) for i in range(n)}


def _schedule(streams, chunks=10, chunk=8, variance=False, evict=None,
              finish_later=None):
    """Deterministic command list: submits, interleaved pushes + ticks,
    optional evict / deferred finish, then a batched finish."""
    cmds = [("submit", jid, chunks * chunk) for jid in streams]
    vr = np.random.default_rng(99)
    for t in range(chunks):
        for jid, s in streams.items():
            x = s[t * chunk: (t + 1) * chunk]
            v = (0.01 * np.abs(vr.normal(size=x.shape[0]))
                 .astype(np.float32)) if variance else None
            cmds.append(("push", jid, x, v))
        cmds.append(("tick",))
        if evict is not None and t == chunks // 2:
            cmds.append(("evict", evict))
        if finish_later is not None and t == chunks - 2:
            cmds.append(("finish_later", finish_later))
    live = [j for j in streams if j not in (evict, finish_later)]
    cmds.append(("finish", live))
    if finish_later is not None:
        cmds.append(("drain",))
    return cmds


def _run(svc, cmds, lo=0, hi=None):
    """Execute cmds[lo:hi]; returns the emitted decision trajectory with
    full-precision scores (float hex) so equality means bitwise."""
    outs = []
    hi = len(cmds) if hi is None else min(hi, len(cmds))
    gone = set()
    for i in range(lo, hi):
        c = cmds[i]
        if c[0] == "submit":
            svc.submit(c[1], c[2])
        elif c[0] == "push":
            if c[1] in gone:
                continue
            svc.push(c[1], c[2], variance=c[3], now=float(i))
        elif c[0] == "tick":
            outs.append((i, _keyd(svc.tick(now=float(i)))))
        elif c[0] == "evict":
            svc.evict(c[1])
            gone.add(c[1])
        elif c[0] == "finish_later":
            svc.finish_later(c[1])
            gone.add(c[1])
        elif c[0] == "finish":
            outs.append((i, _keyd(svc.finish_many(c[1]))))
        elif c[0] == "drain":
            outs.append((i, _keyd(svc.drain_finishes())))
    return outs


def _keyd(decisions):
    out = []
    for j, d in sorted(decisions.items()):
        if d is None:
            out.append((j, None))
        else:
            out.append((j, d.matched, float(d.corr).hex(), d.final,
                        d.fraction_seen,
                        None if d.probability is None
                        else float(d.probability).hex(),
                        tuple((k, float(v).hex())
                              for k, v in sorted(d.scores.items()))))
    return out


# ---------------------------------------------------------------------------
# snapshot/restore: bitwise continuation at every kind of cut point
# ---------------------------------------------------------------------------

def test_snapshot_restore_bitwise_exact_mode():
    bank = _bank()
    streams = _streams()
    cmds = _schedule(streams)
    gold = _run(TuningService(bank, slots=8), cmds)
    for cut in (0, 3, 9, 17, len(cmds) - 2):
        svc = TuningService(bank, slots=8)
        _run(svc, cmds, 0, cut)
        twin = restore_service(snapshot_service(svc), bank)
        a = _run(svc, cmds, cut)
        b = _run(twin, cmds, cut)
        assert a == b, f"restored service diverged (cut={cut})"
        assert a == gold[-len(a):], f"continuation != golden (cut={cut})"
        assert twin.ticks == svc.ticks
        assert twin.dispatch_count == svc.dispatch_count


def test_snapshot_restore_prob_prefilter_denoise():
    """All the stateful features at once: probabilistic rule (6-channel
    moments + vstats + variance queues), wavelet prefilter (haar state,
    allowed masks, packed-K state), causal denoise filter state, queues,
    heartbeats, eviction and the deferred-finish queue."""
    bank = _bank(k=6, seed=1)
    streams = _streams(n=4, seed=7, length=64)
    kw = dict(slots=8, min_probability=0.5, threshold=0.5, denoise=True,
              prefilter_top=3, prefilter_min_fraction=0.05,
              heartbeat_timeout=50.0, queue_limit=512,
              queue_policy="drop_oldest")
    cmds = _schedule(streams, chunks=8, variance=True, evict="j0",
                     finish_later="j1")
    gold = _run(TuningService(bank, **kw), cmds)
    for cut in (2, 11, 23, len(cmds) - 3):
        svc = TuningService(bank, **kw)
        _run(svc, cmds, 0, cut)
        twin = restore_service(snapshot_service(svc), bank)
        a = _run(svc, cmds, cut)
        b = _run(twin, cmds, cut)
        assert a == b, f"restored service diverged (cut={cut})"
        assert a == gold[-len(a):], f"continuation != golden (cut={cut})"


def test_snapshot_restore_approx_prob_mode():
    """Approx probability mode rides snapshots: the 4-channel moment
    slab and the ``prob_mode`` flag are persisted, the restored twin
    rebuilds an approx-mode service (same channel count, same config)
    and continues the schedule bitwise."""
    bank = _bank(k=6, seed=1)
    streams = _streams(n=4, seed=7, length=64)
    kw = dict(slots=8, min_probability=0.5, prob_mode="approx",
              threshold=0.5, denoise=True, queue_limit=512)
    cmds = _schedule(streams, chunks=8, variance=True, evict="j0",
                     finish_later="j1")
    gold = _run(TuningService(bank, **kw), cmds)
    for cut in (2, 11, 23, len(cmds) - 3):
        svc = TuningService(bank, **kw)
        _run(svc, cmds, 0, cut)
        twin = restore_service(snapshot_service(svc), bank)
        assert twin.prob_mode == "approx"
        assert twin._config["prob_mode"] == "approx"
        assert twin._moms.shape[0] == 4
        a = _run(svc, cmds, cut)
        b = _run(twin, cmds, cut)
        assert a == b, f"restored service diverged (cut={cut})"
        assert a == gold[-len(a):], f"continuation != golden (cut={cut})"


def test_snapshot_mid_repack_dirty_slots():
    """Snapshot taken AFTER a submit but BEFORE its lazy slot reset ran
    (the `_dirty` list is non-empty) must carry the pending reset."""
    bank = _bank()
    streams = _streams(n=2)
    svc = TuningService(bank, slots=8)
    svc.submit("j0", 80)
    svc.push("j0", streams["j0"][:8])
    svc.tick()
    svc.submit("j1", 80)            # slot dirty, no tick yet
    assert svc._dirty, "test setup: expected a pending lazy reset"
    twin = restore_service(snapshot_service(svc), bank)
    assert twin._dirty == svc._dirty
    for s in (svc, twin):
        s.push("j0", streams["j0"][8:16])
        s.push("j1", streams["j1"][:8])
    a, b = svc.tick(), twin.tick()
    assert _keyd(a) == _keyd(b)
    np.testing.assert_array_equal(svc._jobs["j1"].last_sims,
                                  twin._jobs["j1"].last_sims)


def test_restore_rejects_wrong_bank():
    svc = TuningService(_bank(), slots=4)
    tree = snapshot_service(svc)
    with pytest.raises(ValueError, match="different reference bank"):
        restore_service(tree, _bank(seed=123))


# ---------------------------------------------------------------------------
# the WAL wrapper: checkpoint + journal tail replay
# ---------------------------------------------------------------------------

def test_recover_snapshot_plus_journal_tail(tmp_path):
    bank = _bank()
    cmds = _schedule(_streams())
    gold = _run(TuningService(bank, slots=8), cmds)

    r1 = RecoverableTuningService(bank, root=str(tmp_path), slots=8)
    _run(r1, cmds, 0, 9)
    r1.checkpoint()
    _run(r1, cmds, 9, 21)           # journaled past the snapshot
    del r1                          # "crash": nothing carried over

    r2 = RecoverableTuningService.recover(bank, root=str(tmp_path))
    assert r2.replayed > 0, "tail records should have replayed"
    a = _run(r2, cmds, 21)
    assert a == gold[-len(a):]
    assert r2.ticks == 10


def test_recover_journal_only_cold_start(tmp_path):
    """No checkpoint was ever taken: the whole journal replays against a
    fresh service built from the recover() kwargs."""
    bank = _bank()
    cmds = _schedule(_streams())
    gold = _run(TuningService(bank, slots=8), cmds)
    r1 = RecoverableTuningService(bank, root=str(tmp_path), slots=8)
    _run(r1, cmds, 0, 15)
    del r1
    r2 = RecoverableTuningService.recover(bank, root=str(tmp_path),
                                          slots=8)
    assert r2.replayed == 15
    a = _run(r2, cmds, 15)
    assert a == gold[-len(a):]


def test_checkpoint_prunes_journal(tmp_path):
    bank = _bank()
    cmds = _schedule(_streams())
    r1 = RecoverableTuningService(bank, root=str(tmp_path), slots=8,
                                  keep=1)
    _run(r1, cmds, 0, 20)
    n_before = len(r1.wal.segments())
    r1.checkpoint()
    assert len(r1.wal.segments()) < n_before or n_before == 0
    # pruning must not break recovery
    del r1
    gold = _run(TuningService(bank, slots=8), cmds)
    r2 = RecoverableTuningService.recover(bank, root=str(tmp_path))
    a = _run(r2, cmds, 20)
    assert a == gold[-len(a):]


def test_recover_replays_quarantine_not_poison(tmp_path):
    """A poisoned push quarantines its job and is journaled as an
    explicit quarantine EVENT (the poison never enters the WAL); replay
    re-evicts and survivors continue bit-identically."""
    from repro.serve.ingest import PoisonedSampleError

    bank = _bank()
    streams = _streams()
    r1 = RecoverableTuningService(bank, root=str(tmp_path), slots=8)
    for j in streams:
        r1.submit(j, 80)
    for t in range(3):
        for j, s in streams.items():
            r1.push(j, s[t * 8: (t + 1) * 8], now=float(t))
        r1.tick(now=float(t))
    bad = streams["j1"][24:32].copy()
    bad[2] = np.inf
    with pytest.raises(PoisonedSampleError):
        r1.push("j1", bad, now=3.0)
    assert r1.quarantined == {"j1": "non-finite sample (NaN/Inf)"}
    survivors_before = {j: svc_job.last_sims.copy()
                        for j, svc_job in r1.svc._jobs.items()}
    del r1

    r2 = RecoverableTuningService.recover(bank, root=str(tmp_path))
    assert r2.quarantined == {"j1": "non-finite sample (NaN/Inf)"}
    assert "j1" not in r2.svc._jobs
    for j, sims in survivors_before.items():
        if j == "j1":
            continue
        np.testing.assert_array_equal(r2.svc._jobs[j].last_sims, sims)
    # a sick agent still pushing is dropped, not resurrected
    r2.push("j1", streams["j1"][24:32], now=4.0)
    assert r2.quarantine_dropped == 1 and "j1" not in r2.svc._jobs


def test_quarantine_sticks_across_checkpoint_and_recover(tmp_path):
    """Quarantine must survive the SNAPSHOT path too, not just WAL
    replay: a job quarantined before ``checkpoint()`` stays quarantined
    after ``recover()``, its sick agent's post-recovery pushes are
    swallowed and counted (``quarantine_dropped``, including swallows
    journaled before the crash), and the survivors finish with
    bitwise-identical verdicts to an uninterrupted run."""
    from repro.serve.ingest import PoisonedSampleError

    bank = _bank()
    streams = _streams()

    def drive(svc, poisoned):
        for j in streams:
            svc.submit(j, 80)
        for t in range(3):
            for j, s in streams.items():
                if poisoned and j == "j1" and t >= 1:
                    continue
                svc.push(j, s[t * 8: (t + 1) * 8], now=float(t))
            svc.tick(now=float(t))

    gold = TuningService(bank, slots=8)
    drive(gold, poisoned=True)
    gold_fin = _run(gold, [("finish", ["j0", "j2"])])

    r1 = RecoverableTuningService(bank, root=str(tmp_path), slots=8)
    for j in streams:
        r1.submit(j, 80)
    for j, s in streams.items():
        r1.push(j, s[:8], now=0.0)
    r1.tick(now=0.0)
    bad = streams["j1"][8:16].copy()
    bad[4] = np.nan
    with pytest.raises(PoisonedSampleError):
        r1.push("j1", bad, now=1.0)
    r1.push("j1", streams["j1"][8:16], now=1.0)   # swallowed pre-crash
    assert r1.quarantine_dropped == 1
    for t in range(1, 3):
        for j, s in streams.items():
            if j == "j1":
                continue
            r1.push(j, s[t * 8: (t + 1) * 8], now=float(t))
        r1.tick(now=float(t))
    r1.checkpoint()
    del r1

    r2 = RecoverableTuningService.recover(bank, root=str(tmp_path))
    assert r2.replayed == 0                       # snapshot was current
    assert r2.quarantined == {"j1": "non-finite sample (NaN/Inf)"}
    assert r2.quarantine_dropped == 1
    assert "j1" not in r2.svc._jobs
    # still-sick agent keeps pushing: swallowed + counted, never revived
    r2.push("j1", streams["j1"][16:24], now=3.0)
    assert r2.quarantine_dropped == 2 and "j1" not in r2.svc._jobs
    assert _run(r2, [("finish", ["j0", "j2"])]) == gold_fin


# ---------------------------------------------------------------------------
# torn files: truncated journal tails and incomplete snapshot steps
# ---------------------------------------------------------------------------

def test_tracelog_truncated_tail_is_skipped(tmp_path):
    """Chop bytes off a real flushed segment: the reopened log warns,
    counts it in ``corrupt_segments``, and replays everything before."""
    log = TraceLog(str(tmp_path), max_segment_bytes=1 << 14)
    rng = np.random.default_rng(0)
    for i in range(4):
        log.append("job0", rng.normal(size=32).astype(np.float32))
        log.flush()                 # one segment per record
    segs = log.segments()
    assert len(segs) == 4
    victim = os.path.join(str(tmp_path), segs[-1])
    truncate_file(victim, drop_bytes=max(1, os.path.getsize(victim) // 2))

    reopened = TraceLog(str(tmp_path), max_segment_bytes=1 << 14)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        recs = reopened.records()
    assert reopened.corrupt_segments == 1
    assert any("truncated or corrupt" in str(x.message) for x in w)
    assert [seq for seq, _, _ in recs] == [0, 1, 2]  # tail record lost
    assert reopened.read_job("job0").shape[0] == 3 * 32


def test_tracelog_reopen_resumes_sequence(tmp_path):
    log = TraceLog(str(tmp_path))
    log.append("a", np.ones(4, np.float32))
    log.append_event("tick", {"now": 1.0})
    log.flush()
    assert log.next_seq == 2
    reopened = TraceLog(str(tmp_path))
    assert reopened.next_seq == 2
    assert reopened.segments() == log.segments()
    seq = reopened.append_event("tick", {"now": 2.0})
    assert seq == 2                 # no clobbering of the old journal


def test_recover_with_torn_snapshot_falls_back(tmp_path):
    """A crash mid-save leaves a manifest-less step dir; recovery must
    restore the newest COMPLETE snapshot and replay a longer tail."""
    bank = _bank()
    cmds = _schedule(_streams())
    gold = _run(TuningService(bank, slots=8), cmds)
    r1 = RecoverableTuningService(bank, root=str(tmp_path), slots=8)
    _run(r1, cmds, 0, 9)
    r1.checkpoint(prune=False)
    _run(r1, cmds, 9, 15)
    # fake a crash mid-checkpoint: a step dir with arrays but no manifest
    torn = os.path.join(str(tmp_path), "ckpt", "step_000099")
    os.makedirs(torn)
    np.savez(os.path.join(torn, "arrays.npz"), junk=np.zeros(3))
    del r1
    r2 = RecoverableTuningService.recover(bank, root=str(tmp_path))
    a = _run(r2, cmds, 15)
    assert a == gold[-len(a):]


# ---------------------------------------------------------------------------
# hypothesis: random interleavings of push/tick/snapshot/crash/restore
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_random_interleaving_recovery_invariance(seed):
    """Random command tapes (uneven pushes, empty ticks, evictions,
    deferred finishes, zero-job stretches) crashed at a random point and
    recovered from snapshot+journal continue exactly like the
    uninterrupted run."""
    rng = np.random.default_rng(seed)
    bank = _bank(k=4, seed=3)
    n_jobs = int(rng.integers(1, 5))
    streams = _streams(n=n_jobs, seed=int(rng.integers(1 << 30)),
                       length=48)
    # random tape
    cmds = [("submit", j, 48) for j in streams]
    pos = {j: 0 for j in streams}
    for t in range(int(rng.integers(4, 12))):
        for j in streams:
            step = int(rng.integers(0, 9))
            if step and pos[j] < 48:
                cmds.append(("push", j, streams[j][pos[j]:pos[j] + step],
                             None))
                pos[j] = min(48, pos[j] + step)
        cmds.append(("tick",))
    if n_jobs > 1 and rng.random() < 0.5:
        cmds.append(("evict", f"j{n_jobs - 1}"))
        live = [j for j in streams if j != f"j{n_jobs - 1}"]
    else:
        live = list(streams)
    cmds.append(("finish", live))

    gold = _run(TuningService(bank, slots=8), cmds)

    import tempfile
    with tempfile.TemporaryDirectory() as root:
        r1 = RecoverableTuningService(bank, root=root, slots=8)
        cut = int(rng.integers(0, len(cmds)))
        ckpt_at = int(rng.integers(0, cut + 1))
        _run(r1, cmds, 0, ckpt_at)
        r1.checkpoint()
        _run(r1, cmds, ckpt_at, cut)
        del r1
        r2 = RecoverableTuningService.recover(bank, root=root, slots=8)
        a = _run(r2, cmds, cut)
        tail = gold[len(gold) - len(a):]
        assert a == tail, f"seed={seed} cut={cut} ckpt={ckpt_at}"
