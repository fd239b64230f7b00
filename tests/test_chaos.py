"""Chaos injection: seeded faults must move counters, never decisions.

The fault plan injects dispatch failures, sample corruption and clock
skew into a serving run; the invariants pinned here are the robustness
contract of PR "crash-safe serving":

* injected transient dispatch failures are retried and the run's
  decisions are BITWISE equal to the fault-free run (with retry
  counters surfaced);
* failure bursts that exhaust the retry budget fall back to the jnp
  wavefront twin — ``degraded`` flagged, decisions still bitwise equal;
* with no fallback available the dispatch raises ``DispatchFailure``;
* corrupted (NaN/Inf) samples quarantine the poisoned JOB while every
  survivor's scores and decisions stay bitwise identical;
* skewed clocks never mass-evict healthy jobs (heartbeat monotonicity);
* the plan itself is deterministic per seed, with independent streams
  per fault class.

The fast CI job runs this module over a fixed seed matrix via the
``CHAOS_SEEDS`` env var (comma-separated ints).
"""
import os

import numpy as np
import pytest

from repro.core.database import pack_series
from repro.runtime.chaos import FaultPlan, InjectedDispatchError
from repro.runtime.retry import DispatchFailure, RetryPolicy, call_with_retry
from repro.serve.ingest import PoisonedSampleError
from repro.serve.tuning import TuningService

SEEDS = [int(s) for s in os.environ.get("CHAOS_SEEDS", "5,17").split(",")]


def _bank(k=4, seed=2):
    rng = np.random.default_rng(seed)
    series = [np.abs(np.cumsum(rng.normal(size=100)))
              .astype(np.float32) for _ in range(k)]
    return pack_series(series, labels=[f"w{i}" for i in range(k)])


def _drive(svc, poison=None):
    """Fixed schedule; poisons one chunk of j1 when ``poison`` is set.
    Returns the full decision trajectory with float-hex scores."""
    outs = []
    r = np.random.default_rng(3)
    streams = {f"j{i}": np.abs(np.cumsum(r.normal(size=48)))
               .astype(np.float32) for i in range(3)}
    for j in streams:
        svc.submit(j, 48)
    for t in range(6):
        for j, s in streams.items():
            if j in svc.quarantined:
                continue
            x = s[t * 8: (t + 1) * 8]
            if poison == (j, t):
                x = x.copy()
                x[3] = np.nan
                with pytest.raises(PoisonedSampleError):
                    svc.push(j, x)
                continue
            svc.push(j, x)
        outs.append(_keyd(svc.tick()))
    outs.append(_keyd(svc.finish_many(
        [j for j in streams if j not in svc.quarantined])))
    return outs


def _keyd(decisions):
    return sorted((j, None if d is None else
                   (d.matched, float(d.corr).hex(), d.final,
                    tuple((k, float(v).hex())
                          for k, v in sorted(d.scores.items()))))
                  for j, d in decisions.items())


def _policy(**kw):
    kw.setdefault("base_delay", 0.0)
    kw.setdefault("sleep", lambda s: None)   # no real sleeping in tests
    return RetryPolicy(**kw)


# ---------------------------------------------------------------------------
# retry / fallback wrapper
# ---------------------------------------------------------------------------

def test_retry_succeeds_after_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise InjectedDispatchError("boom")
        return 42

    out, report = call_with_retry(flaky, policy=_policy(max_retries=3),
                                  transient=(InjectedDispatchError,))
    assert out == 42
    assert report == {"retries": 2, "degraded": False}


def test_retry_exhaustion_uses_fallback_once():
    def always_fails():
        raise InjectedDispatchError("boom")

    out, report = call_with_retry(always_fails,
                                  policy=_policy(max_retries=2),
                                  transient=(InjectedDispatchError,),
                                  fallback=lambda: "degraded-result")
    assert out == "degraded-result"
    assert report == {"retries": 3, "degraded": True}


def test_retry_exhaustion_without_fallback_raises():
    def always_fails():
        raise InjectedDispatchError("boom")

    with pytest.raises(DispatchFailure):
        call_with_retry(always_fails, policy=_policy(max_retries=1),
                        transient=(InjectedDispatchError,))


def test_non_transient_errors_propagate_immediately():
    calls = {"n": 0}

    def typo():
        calls["n"] += 1
        raise TypeError("not a device fault")

    with pytest.raises(TypeError):
        call_with_retry(typo, policy=_policy(max_retries=5),
                        transient=(InjectedDispatchError,))
    assert calls["n"] == 1


def test_backoff_delays_grow_and_cap():
    p = RetryPolicy(max_retries=8, base_delay=0.1, max_delay=1.0,
                    jitter=0.0, sleep=lambda s: None)
    delays = [p.delay(a) for a in range(8)]
    assert delays[0] == pytest.approx(0.1)
    assert delays == sorted(delays)
    assert max(delays) <= 1.0


# ---------------------------------------------------------------------------
# fault plan determinism
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic_per_seed():
    a = FaultPlan(seed=9, dispatch_fail_rate=0.3)
    b = FaultPlan(seed=9, dispatch_fail_rate=0.3)
    sched_a, sched_b = [], []
    for plan, sched in ((a, sched_a), (b, sched_b)):
        for _ in range(50):
            try:
                plan.on_dispatch()
                sched.append(0)
            except InjectedDispatchError:
                sched.append(1)
    assert sched_a == sched_b
    assert a.injected_failures == b.injected_failures > 0


def test_fault_plan_streams_are_independent():
    """Enabling corruption must not shift the dispatch-failure
    schedule: each fault class draws from its own seeded stream."""
    def dispatch_schedule(plan, n=40):
        out = []
        for _ in range(n):
            try:
                plan.on_dispatch()
                out.append(0)
            except InjectedDispatchError:
                out.append(1)
        return out

    a = FaultPlan(seed=9, dispatch_fail_rate=0.3)
    b = FaultPlan(seed=9, dispatch_fail_rate=0.3, corrupt_rate=1.0,
                  skew_rate=1.0)
    rng_noise = np.random.default_rng(0)
    sched_b = []
    for _ in range(40):
        b.corrupt(rng_noise.normal(size=4).astype(np.float32))
        b.skew(1.0)
        try:
            b.on_dispatch()
            sched_b.append(0)
        except InjectedDispatchError:
            sched_b.append(1)
    assert dispatch_schedule(a) == sched_b


def test_corrupt_injects_nonfinite_and_counts():
    plan = FaultPlan(seed=4, corrupt_rate=1.0)
    x = np.zeros(16, np.float32)
    y = plan.corrupt(x)
    assert np.all(np.isfinite(x)), "corrupt must not mutate its input"
    assert not np.all(np.isfinite(y))
    assert plan.corrupted_pushes == 1


def test_should_kill_fires_on_schedule():
    plan = FaultPlan(seed=0, kill_every=5)
    kills = [i for i in range(20) if plan.should_kill(i)]
    assert kills == [4, 9, 14, 19]
    assert not any(FaultPlan(seed=0).should_kill(i) for i in range(20))


# ---------------------------------------------------------------------------
# service-level invariants, over the CI seed matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_injected_failures_never_change_decisions(seed):
    bank = _bank()
    gold = _drive(TuningService(bank, slots=4))

    chaos = FaultPlan(seed=seed, dispatch_fail_rate=0.5)
    svc = TuningService(bank, slots=4, chaos=chaos,
                        retry_policy=_policy(max_retries=3))
    assert _drive(svc) == gold, "retried faults changed decisions"
    assert svc.retry_count == chaos.injected_failures > 0
    assert svc.degraded_dispatch_count == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_burst_exhausts_retries_falls_back_degraded(seed):
    bank = _bank()
    gold = _drive(TuningService(bank, slots=4))

    chaos = FaultPlan(seed=seed, dispatch_fail_rate=0.9,
                      dispatch_fail_burst=10)
    svc = TuningService(bank, slots=4, chaos=chaos,
                        retry_policy=_policy(max_retries=2))
    assert _drive(svc) == gold, "degraded fallback changed decisions"
    assert svc.degraded_dispatch_count > 0
    assert svc.last_tick_degraded in (True, False)  # surfaced per tick
    assert svc.retry_count >= 3 * svc.degraded_dispatch_count


@pytest.mark.parametrize("seed", SEEDS)
def test_quarantine_leaves_survivors_bit_identical(seed):
    bank = _bank()
    poison = ("j1", 2 + seed % 3)
    gold = _drive(TuningService(bank, slots=4), poison=poison)
    run2 = _drive(TuningService(bank, slots=4), poison=poison)
    assert gold == run2, "poisoned run must itself be deterministic"

    clean = _drive(TuningService(bank, slots=4))
    surv_clean = [[e for e in tick if e[0] != "j1"] for tick in clean]
    surv_poison = [[e for e in tick if e[0] != "j1"] for tick in gold]
    assert surv_clean == surv_poison, \
        "quarantining j1 perturbed the survivors"

    svc = TuningService(bank, slots=4)
    _drive(svc, poison=poison)
    assert svc.quarantined == {"j1": "non-finite sample (NaN/Inf)"}
    assert svc.quarantined_count == 1


def test_chaos_corruption_quarantines_via_push():
    """End-to-end: FaultPlan.corrupt wired through TuningService.push
    poisons a stream, the service quarantines instead of crashing."""
    bank = _bank()
    chaos = FaultPlan(seed=1, corrupt_rate=1.0)
    svc = TuningService(bank, slots=4, chaos=chaos)
    svc.submit("j0", 48)
    with pytest.raises(PoisonedSampleError):
        svc.push("j0", np.ones(8, np.float32))
    assert svc.quarantined == {"j0": "non-finite sample (NaN/Inf)"}
    # later pushes silently dropped
    svc.push("j0", np.ones(8, np.float32))
    assert svc.quarantine_dropped == 1


def test_backwards_clock_skew_never_mass_evicts():
    """A sweep clock that jumps BACKWARDS (NTP step, VM migration, the
    chaos plan's skew injection) must decide exactly what the honest
    sweep decided — the heartbeat high-water guard clamps it.  (A
    forward jump legitimately times jobs out, so only the backwards
    direction carries an invariant.)"""
    bank = _bank()
    svc = TuningService(bank, slots=4, heartbeat_timeout=10.0)
    svc.submit("j0", 48)
    svc.submit("j1", 48)
    rng = np.random.default_rng(0)
    for step in range(1, 21):
        t = float(step)
        for j in ("j0", "j1"):
            svc.push(j, np.abs(rng.normal(size=4)).astype(np.float32),
                     now=t)
        assert svc.sweep_stalled(t) == {}
        # chaos: the very next sweep arrives on a clock 100s in the past
        assert svc.sweep_stalled(t - 100.0) == {}, \
            "backwards sweep clock evicted heartbeating jobs"
    assert svc.n_active == 2


def test_backwards_beat_clock_cannot_rewind_liveness():
    """A push stamped with a backwards clock proves liveness; it must
    not rewind ``last_time`` so a later honest sweep times the job
    out on the strength of the skewed stamp."""
    bank = _bank()
    svc = TuningService(bank, slots=4, heartbeat_timeout=10.0)
    svc.submit("j0", 48)
    svc.push("j0", np.ones(4, np.float32), now=100.0)
    # skewed agent clock: stamps an ancient time on a fresh push
    svc.push("j0", np.ones(4, np.float32), now=3.0)
    assert svc.sweep_stalled(105.0) == {}, \
        "backwards beat rewound the heartbeat and got the job evicted"
    assert svc.n_active == 1


class _FailingTick:
    """Wraps a tick callable; raises a runtime error on the listed calls
    (1-based), the way a refused kernel or a lost device surfaces."""

    def __init__(self, fn, fail_on):
        self.fn, self.fail_on, self.calls = fn, set(fail_on), 0

    def __call__(self, *args):
        self.calls += 1
        if self.calls in self.fail_on:
            import jax
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: Mosaic failed to compile TPU kernel")
        return self.fn(*args)


def _armed_service(fail_on):
    from repro.runtime.retry import CircuitBreaker
    svc = TuningService(_bank(), slots=4,
                        retry_policy=_policy(max_retries=3),
                        breaker=CircuitBreaker())
    mode = svc._base_mode()
    fn, fb = svc._tick_fn_for(mode)
    svc._tick_fns[mode] = (_FailingTick(fn, fail_on), fb)
    svc.submit("j0", 48)
    return svc


def test_first_dispatch_runtime_error_raises_never_falls_back():
    """A runtime error on a signature's FIRST dispatch is a lowering or
    compile failure: it raises through an armed retry policy and
    breaker — never retried, never served by the jnp twin."""
    import jax
    svc = _armed_service(fail_on={1})
    svc.push("j0", np.linspace(0, 1, 8, dtype=np.float32))
    with pytest.raises(jax.errors.JaxRuntimeError):
        svc.tick()
    assert svc.retry_count == 0
    assert svc.degraded_dispatch_count == 0


def test_runtime_error_after_first_execution_is_retried():
    """Once a signature has executed, a runtime error is an execution
    fault: the retry re-runs it and the tick is served undegraded."""
    svc = _armed_service(fail_on={2})
    for t in range(3):
        svc.push("j0", np.linspace(0, 1, 8, dtype=np.float32) + t)
        svc.tick()
    assert svc.retry_count == 1
    assert svc.degraded_dispatch_count == 0
    assert svc.dispatch_count == 3
