"""Overload-safe serving: admission, degradation ladder, breaker.

The robustness contract of PR "overload-safe serving":

* the :class:`OverloadController` ladder escalates/de-escalates on the
  EWMA'd window-p99 tick latency with patience/cooldown hysteresis, and
  its whole trajectory is recorded in ``rung_history``;
* admission thresholds are QoS-ordered — gold is NEVER shed at a
  pressure that admits bronze — and a shed submit leaves no state;
* every ladder rung may delay decisions but never change them: under a
  seeded 10x submission spike with slow-dispatch chaos the service walks
  the ladder, sheds bronze spike jobs, and still emits decisions
  bitwise identical to the unloaded golden run (the golden overload
  test);
* the :class:`CircuitBreaker` generalises the one-shot kernel fallback:
  a persistent fault burst trips it OPEN (fallback served directly),
  and after the burst ends a seeded half-open probe re-promotes the
  kernel path — ``degraded`` clears, decisions bitwise unchanged;
* ``call_with_retry(max_elapsed=...)`` abandons remaining retries at a
  wall-clock deadline without perturbing the seeded jitter stream;
* a degraded journal (``TraceLog.journal_degraded``) never loses
  accepted commands and ``checkpoint()`` refuses to advance the
  watermark past them;
* the whole control plane snapshots/restores mid-ladder bit-identically.

The fast CI chaos job runs this module over a fixed seed matrix via the
``CHAOS_SEEDS`` env var (comma-separated ints).
"""
import os

import numpy as np
import pytest

from repro.core.database import pack_series
from repro.runtime.chaos import FaultPlan
from repro.runtime.fault import ElasticController
from repro.runtime.retry import CircuitBreaker, RetryPolicy, call_with_retry
from repro.serve.ingest import BackpressureError, TraceLog
from repro.serve.overload import (RUNGS, AdmissionController,
                                  AdmissionPolicy, AdmissionShedError,
                                  OverloadConfig, OverloadController)
from repro.serve.recovery import (RecoverableTuningService,
                                  restore_service, snapshot_service)
from repro.serve.tuning import TuningService

SEEDS = [int(s) for s in os.environ.get("CHAOS_SEEDS", "5,17").split(",")]


def _bank(k=4, seed=2):
    rng = np.random.default_rng(seed)
    series = [np.abs(np.cumsum(rng.normal(size=100)))
              .astype(np.float32) for _ in range(k)]
    return pack_series(series, labels=[f"w{i}" for i in range(k)])


def _streams(n=3, seed=3, length=48):
    r = np.random.default_rng(seed)
    return {f"j{i}": np.abs(np.cumsum(r.normal(size=length)))
            .astype(np.float32) for i in range(n)}


def _keyd(decisions):
    return sorted((j, None if d is None else
                   (d.matched, float(d.corr).hex(), d.final,
                    tuple((k, float(v).hex())
                          for k, v in sorted(d.scores.items()))))
                  for j, d in decisions.items())


def _policy(**kw):
    kw.setdefault("base_delay", 0.0)
    kw.setdefault("sleep", lambda s: None)
    return RetryPolicy(**kw)


# ---------------------------------------------------------------------------
# the ladder controller
# ---------------------------------------------------------------------------

class TestOverloadController:
    def test_escalates_after_patience_and_records_history(self):
        c = OverloadController(OverloadConfig(target_p99=0.1, patience=2,
                                              cooldown=3, window=8))
        assert c.observe(10.0) == 0          # hot once: patience not met
        assert c.observe(10.0) == 1          # hot twice: escalate
        assert c.observe(10.0) == 1
        assert c.observe(10.0) == 2
        assert c.rung_history == [(2, 0, 1), (4, 1, 2)]

    def test_deescalates_after_cooldown(self):
        c = OverloadController(OverloadConfig(target_p99=0.1, patience=1,
                                              cooldown=2, window=2))
        c.observe(10.0)
        assert c.rung == 1
        # window=2 pushes the spike out after two calm ticks; EWMA decays
        for _ in range(40):
            c.observe(0.0)
        assert c.rung == 0
        assert c.rung_history[-1][2] == 0

    def test_max_rung_caps_escalation(self):
        c = OverloadController(OverloadConfig(target_p99=0.01, patience=1,
                                              max_rung=2))
        for _ in range(10):
            c.observe(5.0)
        assert c.rung == 2

    def test_derived_knobs_by_rung(self):
        c = OverloadController(OverloadConfig(cohort_scale=4.0))
        caps = {}
        for r in range(len(RUNGS)):
            c.rung = r
            caps[r] = (c.tick_mode_cap, c.prefilter_divisor, c.cohort_scale)
        assert caps[0] == ("prob", 1, 1.0)
        assert caps[1] == ("approx_prob", 1, 1.0)
        assert caps[2] == ("scored", 1, 1.0)
        assert caps[3] == ("distance", 1, 1.0)
        assert caps[4] == ("distance", 2, 1.0)
        assert caps[5] == ("distance", 2, 4.0)
        assert caps[6] == ("distance", 2, 4.0)

    def test_state_roundtrip_resumes_identically(self):
        import json
        cfg = OverloadConfig(target_p99=0.1, patience=2, cooldown=2,
                             window=4)
        a = OverloadController(cfg)
        lat = [10.0, 10.0, 0.0, 10.0, 10.0]
        for v in lat:
            a.observe(v)
        st = json.loads(json.dumps(a.state_dict()))   # JSON-able
        b = OverloadController(cfg)
        b.load_state(st)
        tail = [10.0, 0.0, 0.0, 0.0, 10.0, 10.0]
        assert [a.observe(v) for v in tail] == [b.observe(v) for v in tail]
        assert a.rung_history == b.rung_history

    def test_config_validation(self):
        with pytest.raises(ValueError):
            OverloadConfig(target_p99=0.0)
        with pytest.raises(ValueError):
            OverloadConfig(patience=0)
        with pytest.raises(ValueError):
            OverloadConfig(ewma_alpha=0.0)
        with pytest.raises(ValueError):
            OverloadConfig(max_rung=len(RUNGS))
        with pytest.raises(ValueError):
            OverloadConfig(cohort_scale=0.5)


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_threshold_ordering_enforced(self):
        with pytest.raises(ValueError):
            AdmissionPolicy(bronze=0.9, silver=0.5)
        with pytest.raises(ValueError):
            AdmissionPolicy(silver=0.99, gold=0.98)
        with pytest.raises(ValueError):
            AdmissionPolicy(bronze=0.0)

    def test_unknown_qos_is_error_not_shed(self):
        a = AdmissionController()
        with pytest.raises(ValueError, match="unknown QoS"):
            a.admit("j", qos="platinum", cost_fill=0.0, queue_fill=0.0,
                    rung_frac=0.0)

    def test_gold_never_shed_before_bronze(self):
        a = AdmissionController(AdmissionPolicy())

        def shed(qos, p):
            try:
                a.admit("j", qos=qos, cost_fill=p, queue_fill=0.0,
                        rung_frac=0.0)
                return False
            except AdmissionShedError:
                return True

        for p in np.linspace(0.0, 1.0, 101):
            assert not (shed("gold", p) and not shed("bronze", p))
            assert not (shed("silver", p) and not shed("bronze", p))

    def test_shed_error_carries_context_and_is_backpressure(self):
        a = AdmissionController(AdmissionPolicy(bronze=0.5))
        with pytest.raises(AdmissionShedError) as ei:
            a.admit("jb", qos="bronze", cost_fill=0.2, queue_fill=0.9,
                    rung_frac=0.0)
        e = ei.value
        assert isinstance(e, BackpressureError)
        assert (e.job_id, e.qos) == ("jb", "bronze")
        assert e.pressure == pytest.approx(0.9)
        assert e.threshold == pytest.approx(0.5)

    def test_pressure_is_worst_signal_clipped(self):
        a = AdmissionController()
        assert a.pressure(cost_fill=0.2, queue_fill=0.7,
                          rung_frac=0.4) == pytest.approx(0.7)
        assert a.pressure(cost_fill=3.0, queue_fill=0.0,
                          rung_frac=0.0) == 1.0

    def test_shed_submit_leaves_no_state(self):
        svc = TuningService(_bank(), overload=OverloadConfig(),
                            admission=AdmissionPolicy(bronze=0.1,
                                                      silver=0.1,
                                                      gold=0.1,
                                                      cost_scale=0.01))
        with pytest.raises(AdmissionShedError):
            svc.submit("big", 400, qos="bronze")
        assert svc.n_active == 0
        assert "big" not in svc._jobs
        assert svc.shed_count == 1 and svc.shed_by_class == {"bronze": 1}
        # relaxing the gate admits the same id cleanly
        svc._admission_suppressed = True
        svc.submit("big", 400, qos="bronze")
        assert svc.n_active == 1


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_state_machine_walk(self):
        br = CircuitBreaker(fail_threshold=2, cooldown=3,
                            probe_interval=1, seed=0)
        assert br.before_dispatch() == "primary"
        br.record_failure()
        assert br.state == br.CLOSED
        br.record_failure()                        # trips
        assert br.state == br.OPEN and br.opened_count == 1
        routes = [br.before_dispatch() for _ in range(3)]
        assert routes == ["fallback"] * 3          # cooldown served
        assert br.state == br.HALF_OPEN
        assert br.before_dispatch() == "probe"     # probe_interval=1
        br.record_success()                        # probe re-promotes
        assert br.state == br.CLOSED and br.reclosed_count == 1
        assert not br.engaged

    def test_failed_probe_reopens(self):
        br = CircuitBreaker(fail_threshold=1, cooldown=1,
                            probe_interval=1, seed=0)
        br.record_failure()
        assert br.state == br.OPEN
        br.before_dispatch()                       # -> half-open
        assert br.before_dispatch() == "probe"
        br.record_failure()
        assert br.state == br.OPEN and br.opened_count == 2

    @pytest.mark.parametrize("seed", SEEDS)
    def test_state_roundtrip_preserves_probe_schedule(self, seed):
        import json

        def routes(br, n):
            out = []
            for _ in range(n):
                r = br.before_dispatch()
                out.append(r)
                if r == "probe":
                    br.record_failure()            # keep it cycling
            return out

        a = CircuitBreaker(fail_threshold=1, cooldown=2,
                           probe_interval=5, seed=seed)
        a.record_failure()
        routes(a, 7)
        st = json.loads(json.dumps(a.state_dict()))
        b = CircuitBreaker(fail_threshold=1, cooldown=2,
                           probe_interval=5, seed=seed + 999)
        b.load_state(st)
        assert routes(a, 30) == routes(b, 30)

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(fail_threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker(cooldown=0)


# ---------------------------------------------------------------------------
# retry deadline (satellite a)
# ---------------------------------------------------------------------------

class TestRetryDeadline:
    def test_deadline_abandons_retries_to_fallback(self):
        t = [0.0]
        calls = []

        def clock():
            return t[0]

        def sleeper(d):
            t[0] += d

        def fn():
            calls.append("primary")
            raise OSError("transient")

        pol = _policy(max_retries=10, base_delay=1.0, jitter=0.0,
                      sleep=sleeper)
        out, rep = call_with_retry(fn, policy=pol, transient=(OSError,),
                                   fallback=lambda: "fb",
                                   max_elapsed=2.5, clock=clock)
        assert out == "fb" and rep["degraded"]
        # attempts at t=0 (sleep 1), t=1 (sleep 2 -> 3 > 2.5: abandon)
        assert calls == ["primary", "primary"]

    def test_jitter_stream_unchanged_when_deadline_not_hit(self):
        def run(max_elapsed):
            slept = []
            pol = _policy(max_retries=3, base_delay=0.01, seed=7,
                          sleep=slept.append)
            fails = [0]

            def fn():
                if fails[0] < 3:
                    fails[0] += 1
                    raise OSError("transient")
                return "ok"

            out, rep = call_with_retry(fn, policy=pol,
                                       transient=(OSError,),
                                       max_elapsed=max_elapsed)
            return out, rep, slept

        a = run(None)
        b = run(1e9)
        assert a == b and a[0] == "ok" and a[1]["retries"] == 3

    def test_no_deadline_report_unchanged_on_exhaustion(self):
        pol = _policy(max_retries=2)

        def fn():
            raise OSError("transient")

        out, rep = call_with_retry(fn, policy=pol, transient=(OSError,),
                                   fallback=lambda: "fb",
                                   max_elapsed=1e9)
        assert out == "fb" and rep["retries"] == 3


# ---------------------------------------------------------------------------
# ladder downgrades: delayed, never different
# ---------------------------------------------------------------------------

def _drive_pair(kw_golden, kw_loaded, hot_ticks):
    """Run the same 3-job schedule through an unloaded and a loaded
    service; the loaded one is pre-heated to its ladder rung by
    ``hot_ticks`` latency-override observations before data flows."""
    streams = _streams()
    outs = {}
    for tag, kw in (("golden", kw_golden), ("loaded", kw_loaded)):
        svc = TuningService(_bank(), **kw)
        if tag == "loaded":
            for _ in range(hot_ticks):
                svc.tick(latency=10.0)
        for j in streams:
            svc.submit(j, 48)
        earlies = []
        for t in range(6):
            for j, s in streams.items():
                svc.push(j, s[t * 8: (t + 1) * 8])
            for j, d in svc.tick().items():
                if d is not None:
                    earlies.append((j, d.matched))
        finals = _keyd(svc.finish_many(list(streams)))
        outs[tag] = (earlies, finals, svc)
    return outs


def test_approx_prob_downgrade_never_changes_a_decision():
    """Rung 1 caps an exact prob-mode service to the approximate
    4-channel probability tick: probabilities keep flowing (precision
    shed, not probabilities), but ticked jobs carry
    ``degraded_level=1`` so any early that still fires used exact
    channels — no early may disagree with the golden verdict — and
    finals are bitwise unchanged (exact offline recompute)."""
    base = dict(min_probability=0.5, margin=0.01, stable_ticks=1,
                min_fraction=0.1)
    out = _drive_pair(
        base,
        dict(base, overload=OverloadConfig(target_p99=0.01, patience=1,
                                           cooldown=1000, window=64,
                                           max_rung=1)),
        hot_ticks=3)
    (ge, gf, gsvc) = out["golden"]
    (le, lf, lsvc) = out["loaded"]
    assert lsvc.worst_rung == 1 and lsvc.overload_ticks > 0
    assert lf == gf                                  # finals bitwise
    assert set(le) <= set(ge)                        # no WRONG earlies
    assert all(j.degraded_level == 0 for j in gsvc._jobs.values())
    assert all(j.degraded_level <= 1 for j in lsvc._jobs.values())


def test_approx_prob_rung_is_noop_for_approx_configured_service():
    """A ``prob_mode="approx"`` service already runs the approximate
    tick as its base mode, so the ``approx_prob`` rung neither degrades
    its jobs nor changes anything: earlies AND finals are bitwise equal
    to its own unloaded run."""
    base = dict(min_probability=0.5, prob_mode="approx", margin=0.01,
                stable_ticks=1, min_fraction=0.1)
    out = _drive_pair(
        base,
        dict(base, overload=OverloadConfig(target_p99=0.01, patience=1,
                                           cooldown=1000, window=64,
                                           max_rung=1)),
        hot_ticks=3)
    (ge, gf, _) = out["golden"]
    (le, lf, lsvc) = out["loaded"]
    assert lsvc.worst_rung == 1
    assert le == ge                                  # earlies unchanged
    assert lf == gf                                  # finals bitwise
    assert all(j.degraded_level == 0 for j in lsvc._jobs.values())


def test_exact_score_downgrade_bitwise_finals_and_no_wrong_earlies():
    """Rung 2 caps a prob-mode service to exact scored ticks: early
    decisions that still fire use the EXACT score channels, finals are
    bitwise unchanged, and ticked jobs carry ``degraded_level=1``."""
    base = dict(min_probability=0.5, margin=0.01, stable_ticks=1,
                min_fraction=0.1)
    out = _drive_pair(
        base,
        dict(base, overload=OverloadConfig(target_p99=0.01, patience=1,
                                           cooldown=1000, window=64,
                                           max_rung=2)),
        hot_ticks=5)
    (ge, gf, gsvc) = out["golden"]
    (le, lf, lsvc) = out["loaded"]
    assert lsvc.worst_rung == 2 and lsvc.overload_ticks > 0
    assert lf == gf                                  # finals bitwise
    assert set(le) <= set(ge)                        # no WRONG earlies
    assert all(j.degraded_level == 0 for j in gsvc._jobs.values())


def test_distance_downgrade_suppresses_earlies_finals_bitwise():
    """Rung 3 caps everything to distance-only ticks: no early decisions
    at all for jobs ticked there (``degraded_level=2``), finals still
    bitwise equal (recomputed offline from the full query)."""
    out = _drive_pair(
        {},
        dict(overload=OverloadConfig(target_p99=0.01, patience=1,
                                     cooldown=1000, window=64,
                                     max_rung=3)),
        hot_ticks=5)
    (ge, gf, _) = out["golden"]
    (le, lf, lsvc) = out["loaded"]
    assert lsvc.worst_rung == 3
    assert le == []                                  # zero early decisions
    assert lf == gf


# -- capped ticks: the channel splice against the per-mode branches ---------
_STATE = ("rows", "moms", "ns", "sx", "sxx", "vstats")


def _branch_tick(pre, base, mode, chunks, vchunks, nvalid, qlens, band,
                 threshold):
    """The tick as it was dispatched before one entry point took every
    channel set: one branch a mode, each building its own arguments, the
    approx and scored branches ticking the first 4 or 3 channels of a
    wider slab and concatenating the untouched ones back, the distance
    branch touching rows and ``ns`` only.  Kept as the oracle of the one
    dispatch branch in ``TuningService._tick_impl``."""
    import jax.numpy as jnp
    from repro.core.dtw import bank_extend_tick
    kw = dict(band=band, threshold=threshold, use_kernel=False)
    rows, moms, ns, sx, sxx, vst = (pre[a] for a in _STATE)
    bank_t, lengths = pre["bank_t"], pre["lengths"]
    if mode == "prob":
        rows, moms, ns, sx, sxx, _, vst, _ = bank_extend_tick(
            rows, moms, ns, sx, sxx, vst, bank_t, lengths, chunks, vchunks,
            nvalid, qlens, **kw)
    elif mode == "approx_prob":
        moms_in = moms[:4] if base == "prob" else moms
        rows, out, ns, sx, sxx, _, vst, _ = bank_extend_tick(
            rows, moms_in, ns, sx, sxx, vst, bank_t, lengths, chunks,
            vchunks, nvalid, qlens, **kw)
        moms = jnp.concatenate([out, moms[4:]], axis=0) \
            if base == "prob" else out
    elif mode == "scored":
        moms_in = moms[:3] if base in ("prob", "approx_prob") else moms
        rows, out, ns, sx, sxx, _ = bank_extend_tick(
            rows, moms_in, ns, sx, sxx, None, bank_t, lengths, chunks, None,
            nvalid, qlens, **kw)
        moms = jnp.concatenate([out, moms[3:]], axis=0) \
            if base in ("prob", "approx_prob") else out
    else:
        rows, _, ns, *_ = bank_extend_tick(
            rows, None, ns, None, None, None, bank_t, lengths, chunks, None,
            nvalid, qlens, **kw)
    return dict(rows=rows, moms=moms, ns=ns, sx=sx, sxx=sxx, vstats=vst)


@pytest.mark.parametrize("prob_mode", ["exact", "approx"])
def test_capped_ticks_splice_channels_as_the_mode_branches(prob_mode):
    """A prob-configured (six channels) and an approx-configured (four)
    service ticked through the ladder's caps — ``approx_prob``,
    ``scored``, ``distance`` and back — leave the DP rows, every slab
    channel (the untouched upper ones included), ``ns``, the query folds
    and the variance folds bitwise where the per-mode branches
    (:func:`_branch_tick`) leave them, from the same state and chunks."""
    svc = TuningService(_bank(), slots=4, min_probability=0.5,
                        prob_mode=prob_mode,
                        overload=OverloadConfig(target_p99=1e9, max_rung=3))
    base = svc._base_mode()
    order = ("prob", "approx_prob", "scored", "distance")   # the caps
    calls = []

    def record(mode):
        fn, fallback = svc._tick_fn_for(mode)

        def tick(*args):
            pre = {a: getattr(svc, "_" + a) for a in
                   _STATE + ("bank_t", "lengths")}
            var = len(args) == 12
            calls.append((mode, pre, args[-4] if var else args[-3],
                          args[-3] if var else None, args[-2], args[-1]))
            return fn(*args)
        svc._tick_fns[mode] = (tick, fallback)

    for mode in {base, "approx_prob", "scored", "distance"}:
        record(mode)
    streams = _streams(seed=11, length=64)
    noise = np.random.default_rng(12)
    for j in streams:
        svc.submit(j, 64)
    rungs = [1, 2, 3, 0, 2, 1, 3, 0]
    for t, rung in enumerate(rungs):
        for j, x in streams.items():
            svc.push(j, x[t * 8:(t + 1) * 8],
                     variance=noise.uniform(0.0, 0.05, 8).astype(np.float32))
        svc._overload.rung = rung
        svc.tick()
        mode, pre, chunks, vchunks, nvalid, qlens = calls[-1]
        assert mode == order[max(rung, order.index(base))]
        want = _branch_tick(pre, base, mode, chunks, vchunks, nvalid, qlens,
                            svc.band, float(svc.threshold))
        for a in _STATE:
            np.testing.assert_array_equal(np.asarray(getattr(svc, "_" + a)),
                                          np.asarray(want[a]), err_msg=a)
        nch = {"prob": 6, "approx_prob": 4, "scored": 3}.get(mode, 0)
        np.testing.assert_array_equal(np.asarray(svc._moms)[nch:],
                                      np.asarray(pre["moms"])[nch:])
    assert {c[0] for c in calls} == {base, "approx_prob", "scored",
                                     "distance"}


def test_deep_prune_rung_halves_prefilter_budget():
    svc = TuningService(_bank(k=8), prefilter_top=6,
                        overload=OverloadConfig(target_p99=0.01,
                                                patience=1, cooldown=1000,
                                                max_rung=4))
    for _ in range(8):
        svc.tick(latency=10.0)
    assert svc.rung == 4
    assert svc._overload.prefilter_divisor == 2


def test_slow_cohorts_rung_stretches_tick_rates():
    svc = TuningService(_bank(), overload=OverloadConfig(
        target_p99=0.01, patience=1, cooldown=1000, max_rung=5,
        cohort_scale=8.0))
    svc.submit("a", 48, tick_hz=10.0)
    for _ in range(10):
        svc.tick(now=0.0, latency=10.0)
    assert svc.rung == 5
    svc.tick(now=0.1, latency=10.0)         # due: re-arms 8/10 s ahead
    assert svc._sched.cohorts._next_due[10.0] == pytest.approx(0.9)


# ---------------------------------------------------------------------------
# the golden overload test
# ---------------------------------------------------------------------------

def _golden_run(streams):
    svc = TuningService(_bank(), queue_limit=64)
    for j in streams:
        svc.submit(j, 48)
    earlies = []
    for t in range(6):
        for j, s in streams.items():
            svc.push(j, s[t * 8: (t + 1) * 8])
        for j, d in svc.tick().items():
            if d is not None:
                earlies.append((j, d.matched))
    return earlies, _keyd(svc.finish_many(list(streams)))


@pytest.mark.parametrize("seed", SEEDS)
def test_golden_overload_spike(seed):
    """Seeded 10x submission spike + slow-dispatch chaos: the service
    walks the ladder (non-trivial rung history), sheds bronze spike
    jobs, never blows queue limits — and every decision it emits is a
    decision the unloaded golden run also made (delayed allowed,
    wrong/extra forbidden).  After the burst the ladder de-escalates
    and ``degraded`` clears."""
    streams = _streams()
    g_earlies, g_finals = _golden_run(streams)

    plan = FaultPlan(seed=seed, slow_rate=1.0, slow_extra=10.0,
                     spike_rate=0.5, spike_factor=10.0, spike_len=2)
    svc = TuningService(
        _bank(), queue_limit=64, slots=64,
        overload=OverloadConfig(target_p99=0.2, patience=1, cooldown=2,
                                window=4),
        admission=AdmissionPolicy(), chaos=plan)
    for j in streams:
        svc.submit(j, 48, qos="gold")

    spike_rng = np.random.default_rng((seed, 8))
    n_spike = 0
    earlies = []
    for t in range(6):
        # the spike: a burst beat multiplies offered submissions 10x
        mult = plan.spike_multiplier()
        for i in range(int(mult) - 1):
            try:
                svc.submit(f"spike{t}_{i}", 48, qos="bronze")
                n_spike += 1
            except (AdmissionShedError, RuntimeError):
                pass
        for j, s in streams.items():
            # queue_policy=reject: a push past queue_limit would raise
            # BackpressureError, so completing silently IS the
            # never-exceeds-queue-limits assertion.
            svc.push(j, s[t * 8: (t + 1) * 8])
        for jid in list(svc._jobs):
            if jid.startswith("spike"):
                svc.push(jid, np.abs(spike_rng.normal(size=4))
                         .astype(np.float32))
        for j, d in svc.tick().items():
            if d is not None and not j.startswith("spike"):
                earlies.append((j, d.matched))

    assert svc.worst_rung >= 1 and len(svc.rung_history) >= 1
    assert plan.spiked_beats >= 1 and plan.slowed_dispatches >= 1
    # under-load decisions: delayed allowed, wrong/extra forbidden —
    # numerics are chaos-independent, so any early the loaded run emits
    # must be one the unloaded golden run emitted too.
    assert set(earlies) <= set(g_earlies)
    finals = _keyd(svc.finish_many(list(streams)))
    assert finals == g_finals

    # burst ends: chaos off, ladder walks back down, degraded clears
    svc.chaos = None
    for _ in range(40):
        svc.tick(latency=0.0)
        if svc.rung == 0:
            break
    assert svc.rung == 0
    assert not svc.degraded
    assert svc.rung_history[-1][2] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_breaker_rides_fault_burst_then_recloses(seed):
    """A persistent dispatch-fault burst trips the breaker OPEN (the
    fallback serves; no more retry ladders); when the burst ends the
    seeded probe re-promotes the kernel path and ``degraded`` clears.
    Decisions stay bitwise equal to the fault-free run throughout."""
    streams = _streams()
    g_earlies, g_finals = _golden_run(streams)

    br = CircuitBreaker(fail_threshold=2, cooldown=2, probe_interval=2,
                        seed=seed)
    svc = TuningService(_bank(), queue_limit=64,
                        retry_policy=_policy(max_retries=1, seed=seed),
                        chaos=FaultPlan(seed=seed, dispatch_fail_rate=1.0),
                        breaker=br)
    for j in streams:
        svc.submit(j, 48)
    for t in range(3):                       # burst: every dispatch fails
        for j, s in streams.items():
            svc.push(j, s[t * 8: (t + 1) * 8])
        svc.tick()
    assert br.state == br.OPEN
    assert svc.degraded and svc.degraded_dispatch_count >= 2

    svc.chaos = None                         # burst over
    for t in range(3, 6):
        for j, s in streams.items():
            svc.push(j, s[t * 8: (t + 1) * 8])
        svc.tick()
    assert br.state == br.CLOSED and br.reclosed_count >= 1
    assert not svc.degraded
    assert _keyd(svc.finish_many(list(streams))) == g_finals


def test_overload_pressure_feeds_rescale_ahead():
    svc = TuningService(_bank(), overload=OverloadConfig(target_p99=0.01,
                                                         patience=1,
                                                         cooldown=1000))
    ec = ElasticController(model_parallel=1)
    calm = ec.decide_ahead(2, range(8),
                           overload_pressure=svc.overload_pressure())
    assert calm.new_data_parallel <= 2       # nothing to grow for
    for _ in range(10):
        svc.tick(latency=10.0)
    hot = ec.decide_ahead(2, range(8),
                          overload_pressure=svc.overload_pressure())
    assert hot.should_rescale and hot.new_data_parallel == 4
    assert "grow-ahead" in hot.reason


class TestDecideAhead:
    def test_grow_capped_at_usable_pow2(self):
        ec = ElasticController(model_parallel=1)
        d = ec.decide_ahead(4, range(6), overload_pressure=1.0)
        assert (d.should_rescale, d.new_data_parallel) == (False, 4)

    def test_shrink_on_idle(self):
        ec = ElasticController(model_parallel=1, min_data_parallel=2)
        d = ec.decide_ahead(8, range(8), overload_pressure=0.0)
        assert (d.should_rescale, d.new_data_parallel) == (True, 4)
        assert "shrink-ahead" in d.reason
        d2 = ec.decide_ahead(2, range(8), overload_pressure=0.0)
        assert not d2.should_rescale         # floor reached

    def test_mid_pressure_defers_to_reactive_decide(self):
        ec = ElasticController(model_parallel=1)
        d = ec.decide_ahead(4, range(3), (), overload_pressure=0.5)
        assert d == ec.decide(4, range(3))

    def test_threshold_validation(self):
        ec = ElasticController(model_parallel=1)
        with pytest.raises(ValueError):
            ec.decide_ahead(1, range(2), overload_pressure=0.5,
                            grow_threshold=0.2, shrink_threshold=0.4)


# ---------------------------------------------------------------------------
# journal degradation (satellite b) + checkpoint refusal
# ---------------------------------------------------------------------------

class TestJournalDegradation:
    def _fail_writes(self, monkeypatch):
        import repro.serve.ingest as ingest

        real = ingest.atomic_write_npz

        def boom(*a, **kw):
            raise OSError("disk full")

        monkeypatch.setattr(ingest, "atomic_write_npz", boom)
        return lambda: monkeypatch.setattr(ingest, "atomic_write_npz",
                                           real)

    def test_push_survives_write_failure(self, tmp_path, monkeypatch):
        log = TraceLog(str(tmp_path))
        log.append("j", np.ones(4, np.float32))
        log.flush()
        heal = self._fail_writes(monkeypatch)
        log.append("j", 2 * np.ones(4, np.float32))
        with pytest.warns(RuntimeWarning, match="journal"):
            log.flush()                       # degrades, does NOT raise
        assert log.journal_degraded and log.journal_write_errors == 1
        assert log.durable_seq == 1 < log.next_seq
        # pending records still replayable from memory
        assert len(log.records()) == 2
        heal()
        log.flush()                           # retries the same segment
        assert not log.journal_degraded
        assert log.durable_seq == log.next_seq == 2
        # a FRESH reader sees both records on disk
        assert len(TraceLog(str(tmp_path)).records()) == 2

    def test_checkpoint_refuses_degraded_journal(self, tmp_path,
                                                 monkeypatch):
        rsvc = RecoverableTuningService(_bank(), root=str(tmp_path))
        rsvc.submit("a", 48)
        heal = self._fail_writes(monkeypatch)
        with pytest.warns(RuntimeWarning):
            rsvc.push("a", np.ones(8, np.float32))   # accepted, in-memory
        with pytest.raises(RuntimeError, match="journal degraded"):
            rsvc.checkpoint()
        heal()
        rsvc.checkpoint()                     # heals, then succeeds
        rec = RecoverableTuningService.recover(_bank(),
                                               root=str(tmp_path))
        assert rec.svc._front._jobs["a"].pushed == 8


# ---------------------------------------------------------------------------
# control plane snapshots (mid-ladder recovery)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_recover_mid_ladder_bitwise(tmp_path, seed):
    """Kill an overloaded service mid-burst; the recovered twin resumes
    at the same rung with the same history, same QoS/degraded markers,
    and finishes with bitwise-identical verdicts."""
    streams = _streams(seed=seed)
    kw = dict(overload=OverloadConfig(target_p99=0.01, patience=1,
                                      cooldown=1000, window=64),
              admission=AdmissionPolicy(),
              chaos=FaultPlan(seed=seed, slow_rate=1.0, slow_extra=10.0))
    rsvc = RecoverableTuningService(_bank(), root=str(tmp_path), **kw)
    for j in streams:
        rsvc.submit(j, 48, qos="gold")
    for t in range(3):
        for j, s in streams.items():
            rsvc.push(j, s[t * 8: (t + 1) * 8])
        rsvc.tick()
    assert rsvc.rung >= 1
    rsvc.checkpoint()
    for t in range(3, 5):                     # journal tail past snapshot
        for j, s in streams.items():
            rsvc.push(j, s[t * 8: (t + 1) * 8])
        rsvc.tick()

    rec = RecoverableTuningService.recover(_bank(), root=str(tmp_path))
    assert rec.replayed > 0
    assert rec.rung == rsvc.rung
    assert rec.rung_history == rsvc.rung_history
    for j in streams:
        assert rec.svc._jobs[j].qos == "gold"
        assert (rec.svc._jobs[j].degraded_level
                == rsvc.svc._jobs[j].degraded_level)
    for t in range(5, 6):
        for j, s in streams.items():
            rsvc.push(j, s[t * 8: (t + 1) * 8])
            rec.push(j, s[t * 8: (t + 1) * 8])
        rsvc.tick()
        rec.tick()
    assert (_keyd(rec.finish_many(list(streams)))
            == _keyd(rsvc.finish_many(list(streams))))


@pytest.mark.parametrize("seed", SEEDS)
def test_recover_mid_approx_prob_rung_replays_same_rungs(tmp_path, seed):
    """Kill an exact prob-mode service while the ladder sits ON the new
    ``approx_prob`` rung; the recovered twin resumes at the same rung
    with the same history (so it re-walks the same rungs), carries the
    same degraded markers, and finishes bitwise identical."""
    streams = _streams(seed=seed)
    kw = dict(min_probability=0.5,
              overload=OverloadConfig(target_p99=0.01, patience=1,
                                      cooldown=1000, window=64,
                                      max_rung=1),
              chaos=FaultPlan(seed=seed, slow_rate=1.0, slow_extra=10.0))
    rsvc = RecoverableTuningService(_bank(), root=str(tmp_path), **kw)
    for j in streams:
        rsvc.submit(j, 48)
    for t in range(3):
        for j, s in streams.items():
            rsvc.push(j, s[t * 8: (t + 1) * 8])
        rsvc.tick()
    assert rsvc.rung == 1 and RUNGS[rsvc.rung] == "approx_prob"
    rsvc.checkpoint()
    for t in range(3, 5):                     # journal tail past snapshot
        for j, s in streams.items():
            rsvc.push(j, s[t * 8: (t + 1) * 8])
        rsvc.tick()

    rec = RecoverableTuningService.recover(_bank(), root=str(tmp_path))
    assert rec.replayed > 0
    assert rec.rung == rsvc.rung == 1
    assert rec.rung_history == rsvc.rung_history
    for j in streams:
        assert (rec.svc._jobs[j].degraded_level
                == rsvc.svc._jobs[j].degraded_level == 1)
    for t in range(5, 6):
        for j, s in streams.items():
            rsvc.push(j, s[t * 8: (t + 1) * 8])
            rec.push(j, s[t * 8: (t + 1) * 8])
        rsvc.tick()
        rec.tick()
    assert (_keyd(rec.finish_many(list(streams)))
            == _keyd(rsvc.finish_many(list(streams))))


def test_snapshot_restores_breaker_and_shed_counters():
    br = CircuitBreaker(fail_threshold=1, cooldown=3, probe_interval=4,
                        seed=9)
    svc = TuningService(_bank(), overload=OverloadConfig(),
                        admission=AdmissionPolicy(bronze=0.1, silver=0.1,
                                                  gold=0.1,
                                                  cost_scale=0.01),
                        breaker=br)
    with pytest.raises(AdmissionShedError):
        svc.submit("big", 400, qos="silver")
    br.record_failure()                       # tripped at snapshot time
    tree = snapshot_service(svc)
    br2 = CircuitBreaker(fail_threshold=1, cooldown=3, probe_interval=4,
                         seed=0)
    svc2 = restore_service(tree, _bank(), breaker=br2)
    assert svc2.shed_count == 1 and svc2.shed_by_class == {"silver": 1}
    assert br2.state == br2.OPEN and br2.opened_count == 1
    assert [br.before_dispatch() for _ in range(8)] \
        == [br2.before_dispatch() for _ in range(8)]


# ---------------------------------------------------------------------------
# overload fault classes (chaos plan)
# ---------------------------------------------------------------------------

class TestOverloadFaults:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_streams_deterministic_and_independent(self, seed):
        def trace(plan, n=40):
            return ([plan.spike_multiplier() for _ in range(n)],
                    [plan.slow_dispatch() for _ in range(n)],
                    [plan.queue_burst() for _ in range(n)])

        kw = dict(spike_rate=0.3, spike_factor=10.0, spike_len=2,
                  slow_rate=0.3, slow_extra=0.5, queue_burst_rate=0.3)
        a = trace(FaultPlan(seed=seed, **kw))
        b = trace(FaultPlan(seed=seed, **kw))
        assert a == b
        # enabling dispatch faults must not shift the overload streams
        c = trace(FaultPlan(seed=seed, dispatch_fail_rate=0.9, **kw))
        assert a == c

    def test_spike_windows_and_counters(self):
        plan = FaultPlan(seed=1, spike_rate=1.0, spike_factor=7.0,
                         spike_len=3)
        mults = [plan.spike_multiplier() for _ in range(6)]
        assert mults == [7.0] * 6             # rate 1: wall-to-wall burst
        assert plan.spiked_beats == 6
        calm = FaultPlan(seed=1)
        assert [calm.spike_multiplier() for _ in range(4)] == [1.0] * 4

    def test_slow_dispatch_returns_latency_never_sleeps(self):
        import time
        plan = FaultPlan(seed=2, slow_rate=1.0, slow_extra=123.0)
        t0 = time.perf_counter()
        extras = [plan.slow_dispatch() for _ in range(10)]
        assert time.perf_counter() - t0 < 1.0  # injected, not slept
        assert extras == [123.0] * 10
        assert plan.slowed_dispatches == 10

    def test_queue_burst_windows(self):
        plan = FaultPlan(seed=3, queue_burst_rate=1.0, queue_burst_len=2)
        assert all(plan.queue_burst() for _ in range(6))
        assert plan.queue_bursts >= 1
        assert not FaultPlan(seed=3).queue_burst()
