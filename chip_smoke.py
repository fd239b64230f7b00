#!/usr/bin/env python3
"""Drive the streaming tuner end to end on one TPU chip, and check it.

Deployment: the tuner of a Hadoop cluster that samples each job's CPU at
1 Hz and keeps a reference DB of 1,024 profiled executions.  The bank is
the three ``mrsim`` applications under seeded Hadoop job configurations
(HDFS-block splits of 64 or 128 MB, 256 MB - 4 GB inputs), 16 recurring
configurations per application with repeated runs of each, traces of up
to M=512 samples (about 8.5 minutes), preprocessed as
``AutoTuner.profile`` does.  The in-flight jobs are new runs: half of
recurring configurations, half of fresh ones.

Phases, all in this one process, through ``TuningService``'s public API:

* scored: ``TuningService(db, band=8, denoise=True, slots=256)`` (the
  paper's Table-1 band), 256 jobs, 8-sample pushes for 32 ticks, then
  ``finish_many`` of 32 completed jobs.  State is 4 x 256 x 512 x 1024
  x 4 B, about 2.1 GB.
* approx: ``min_probability=0.5, prob_mode="approx"``, unbanded, 128
  jobs, 16 ticks, then ``finish_many`` of 16 completed jobs (the exact
  six-channel verdict kernel).

Each phase checks:

* one dispatch per data tick, and no dispatch served degraded;
* the Pallas kernel in the tick, compiled at the live shapes
  (``tpu_custom_call`` in the compiled text);
* the first 3 ticks of 4 jobs against the same service served by its
  jnp wavefront twin, on the chip: scores to 1e-5, same leaders;
* the finals of 4 jobs against ``similarity_bank(...,
  matrix_path=True)`` (``dtw_matrix`` + backtrack + ``correlation``):
  scores to 5e-3, same leader (and, on the point rule, the same match).

``--chips 4`` runs only the bank-sharded service over a 4-chip mesh
(64 jobs, 16 ticks, finals) against the unsharded one on one chip of the
same process: scores to 1e-6, identical decisions, one dispatch a tick.

Without a TPU it exits non-zero at once and names the platform it found.
The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

APPS = ("wordcount", "exim", "terasort")
BANK_SIZE = 1024
CONFIGS_PER_APP = 16
MAX_LEN = 512           # samples of the longest trace (1 Hz)
CHUNK = 8               # samples per push
TWIN_JOBS, TWIN_TICKS, TWIN_TOL = 4, 3, 1e-5
FINAL_JOBS, FINAL_TOL = 4, 5e-3
SHARD_TOL = 1e-6
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(chips: int):
    """The TPU devices, or exit non-zero naming what JAX found."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{d0.platform!r} ({d0.device_kind})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devices)}")
    return devices


class CompileCounter:
    """Counts XLA backend compiles (and their seconds) in this process."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


# -- the deployment ----------------------------------------------------------

def hadoop_config(rng, app: str):
    """One seeded Hadoop job configuration whose trace fits MAX_LEN (a
    trace's length is set by its configuration alone)."""
    from repro.mrsim import JobParams, simulate_cpu_series
    while True:
        p = JobParams(mappers=int(rng.integers(8, 65)),
                      reducers=int(rng.integers(4, 65)),
                      split_mb=int(rng.choice((64, 128))),
                      input_mb=int(rng.integers(256, 4097)))
        if len(simulate_cpu_series(app, p, noise=0.0)) <= MAX_LEN:
            return p


def build_db(rng, size: int = BANK_SIZE,
             configs_per_app: int = CONFIGS_PER_APP):
    """The reference DB: ``size`` profiled runs, round-robin over the
    recurring configurations -> (db, {app: [configs]})."""
    from repro.core.database import ReferenceDB
    from repro.core.tuner import AutoTuner
    from repro.mrsim import simulate_cpu_series
    configs = {app: [hadoop_config(rng, app) for _ in range(configs_per_app)]
               for app in APPS}
    pairs = [(app, p) for app in APPS for p in configs[app]]
    tuner = AutoTuner(ReferenceDB())
    run = 0
    while len(tuner.db) < size:
        for app, p in pairs[: size - len(tuner.db)]:
            tuner.profile(app, p.as_dict(),
                          simulate_cpu_series(app, p, run=run), run=run)
        run += 1
    return tuner.db, configs


def make_jobs(rng, configs, n: int):
    """``n`` in-flight jobs -> {job_id: 1 Hz trace}: new runs, half of a
    recurring configuration, half of a fresh one."""
    from repro.mrsim import simulate_cpu_series
    jobs = {}
    for i in range(n):
        app = APPS[i % len(APPS)]
        if rng.random() < 0.5:
            p = configs[app][int(rng.integers(len(configs[app])))]
        else:
            p = hadoop_config(rng, app)
        jobs[f"{app}-{i:04d}"] = simulate_cpu_series(app, p, run=1000 + i)
    return jobs


# -- service probes ----------------------------------------------------------

class TickRecorder:
    """Stands in for the service's tick callable and keeps the argument
    shapes of its last call, so the tick can be compiled again at the
    live shapes and inspected."""

    def __init__(self, fn):
        self.fn, self.specs = fn, None

    def __call__(self, *args):
        import jax
        self.specs = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding)
                      for a in args]
        return self.fn(*args)


def record_tick(svc) -> TickRecorder:
    mode = svc._base_mode()
    fn, fallback = svc._tick_fn_for(mode)
    rec = TickRecorder(fn)
    svc._tick_fns[mode] = (rec, fallback)
    return rec


def jnp_twin(db, slots: int, kw):
    """The same service with its tick served by the jnp wavefront twin
    (the ``use_kernel=False`` fallback it carries)."""
    from repro.serve.tuning import TuningService
    svc = TuningService(db, slots=slots, **kw)
    mode = svc._base_mode()
    _, twin = svc._tick_fn_for(mode)
    svc._tick_fns[mode] = (twin, None)
    return svc


def kernel_served(rec: TickRecorder) -> dict:
    """Compile the recorded tick at its live shapes; fail unless the
    Pallas kernel is in it -> memory analysis of the tick."""
    import jax
    compiled = jax.jit(rec.fn).lower(*rec.specs).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("the tick holds no Pallas kernel "
                             "(no tpu_custom_call in the compiled text)")
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}


def leader(svc, sims) -> str:
    """The service's own ranking of one job's [K] scores."""
    return svc._rank(svc._reduce(sims))[0]


def push_tick(svc, jobs, t: int) -> bool:
    """Push every job's t-th chunk (jobs whose trace ended push nothing)
    -> whether any sample was pushed."""
    pushed = False
    for jid, x in jobs.items():
        chunk = x[t * CHUNK:(t + 1) * CHUNK]
        if chunk.size:
            svc.push(jid, chunk)
            pushed = True
    return pushed


def completed(jobs, ticks: int, n: int):
    """The first ``n`` jobs whose whole trace was pushed in ``ticks``."""
    done = [jid for jid, x in jobs.items() if len(x) <= ticks * CHUNK][:n]
    if len(done) < n:
        raise AssertionError(f"only {len(done)} of {n} jobs completed")
    return done


# -- phases ------------------------------------------------------------------

def run_phase(name: str, db, jobs, *, slots: int, ticks: int,
              n_finish: int, kw, counter: CompileCounter) -> None:
    import jax
    from repro.core.similarity import similarity_bank
    from repro.serve.tuning import TuningService

    t_phase, c_phase = time.perf_counter(), (counter.count, counter.seconds)
    svc = TuningService(db, slots=slots, **kw)
    rec = record_tick(svc)
    for jid, x in jobs.items():
        svc.submit(jid, expected_len=len(x))
    watched = list(jobs)[:TWIN_JOBS]
    seen, early, data_ticks, late_compiles = [], 0, 0, 0
    for t in range(ticks):
        data_ticks += push_tick(svc, jobs, t)
        c0 = counter.count
        out = svc.tick()
        if t > 0:
            late_compiles += counter.count - c0
        early += sum(d is not None for d in out.values())
        if t < TWIN_TICKS:
            seen.append({j: svc._jobs[j].last_sims.copy() for j in watched})
    rows, moms = svc._rows, svc._moms
    state_bytes = rows.nbytes + (0 if moms is None else moms.nbytes)
    log(f"{name}: S={svc.slot_capacity} M={rows.shape[1]} "
        f"K={rows.shape[2]} moment channels="
        f"{0 if moms is None else moms.shape[0]} chunk={CHUNK} "
        f"state bytes={state_bytes}")
    log(f"{name}: tick memory analysis {kernel_served(rec)}")
    log(f"{name}: compiles during ticks after warm-up: {late_compiles}")

    done = completed(jobs, ticks, n_finish)
    checked = done[:FINAL_JOBS]
    queries = {j: svc._jobs[j].x.view().copy() for j in checked}
    finals = svc.finish_many(done)
    if not (svc.dispatch_count == data_ticks == svc.ticks):
        raise AssertionError(f"{name}: {svc.dispatch_count} dispatches for "
                             f"{data_ticks} data ticks ({svc.ticks} ticks)")
    if svc.degraded_dispatch_count:
        raise AssertionError(f"{name}: {svc.degraded_dispatch_count} "
                             "dispatches served degraded")
    log(f"{name}: dispatches={svc.dispatch_count} data ticks={data_ticks} "
        f"early decisions={early} verdict dispatches="
        f"{svc.offline_dispatch_count} degraded=0")

    # the kernel tick against the service's jnp twin, on the chip
    twin = jnp_twin(db, TWIN_JOBS, kw)
    for j in watched:
        twin.submit(j, expected_len=len(jobs[j]))
    worst = 0.0
    for t in range(TWIN_TICKS):
        push_tick(twin, {j: jobs[j] for j in watched}, t)
        twin.tick()
        for j in watched:
            a, b = seen[t][j], twin._jobs[j].last_sims
            worst = max(worst, float(np.max(np.abs(a - b))))
            if leader(svc, a) != leader(twin, b):
                raise AssertionError(f"{name}: tick {t} leader of {j}: "
                                     f"kernel {leader(svc, a)} vs jnp "
                                     f"{leader(twin, b)}")
    if not worst <= TWIN_TOL:
        raise AssertionError(f"{name}: kernel vs jnp twin score error "
                             f"{worst:.3g} > {TWIN_TOL}")
    log(f"{name}: kernel vs jnp twin, {TWIN_JOBS} jobs x {TWIN_TICKS} "
        f"ticks: max |score diff|={worst:.3g}, same leaders")

    # verdicts against the matrix path (dtw_matrix + backtrack + corr)
    bank, worst = db.bank(), 0.0
    for j in checked:
        ref = svc._reduce(similarity_bank(queries[j], bank, band=kw.get(
            "band"), matrix_path=True))
        d = finals[j]
        worst = max(worst, max(abs(d.scores[w] - s) for w, s in ref.items()))
        ref_leader, ref_score, _ = svc._rank(ref)
        if svc._rank(d.scores)[0] != ref_leader:
            raise AssertionError(f"{name}: final leader of {j}: "
                                 f"{svc._rank(d.scores)[0]} vs matrix "
                                 f"path {ref_leader}")
        if "min_probability" not in kw:
            ref_match = ref_leader if ref_score >= svc.threshold else None
            if d.matched != ref_match:
                raise AssertionError(f"{name}: final match of {j}: "
                                     f"{d.matched} vs {ref_match}")
    if not worst <= FINAL_TOL:
        raise AssertionError(f"{name}: verdict vs matrix path error "
                             f"{worst:.3g} > {FINAL_TOL}")
    log(f"{name}: {len(finals)} finals; {len(checked)} vs matrix path: "
        f"max |score diff|={worst:.3g}, same leaders")
    log(f"{name}: compiles={counter.count - c_phase[0]} compile seconds="
        f"{counter.seconds - c_phase[1]:.1f} phase seconds="
        f"{time.perf_counter() - t_phase:.1f}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"{name}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def run_sharded(db, jobs, *, ticks: int, n_finish: int, chips: int,
                counter: CompileCounter) -> None:
    """The bank-sharded service over a ``chips``-device mesh against the
    unsharded one on one device (both tick on the jnp wavefront: the
    sharded tick closes over it, the unsharded one is its twin)."""
    import jax
    from repro.serve.tuning import TuningService

    kw = dict(band=8, denoise=True)
    mesh = jax.make_mesh((chips,), ("bank",))
    shd = TuningService(db, slots=len(jobs), mesh=mesh, **kw)
    ref = jnp_twin(db, len(jobs), kw)
    for svc in (shd, ref):
        for jid, x in jobs.items():
            svc.submit(jid, expected_len=len(x))
    worst, c0, data_ticks = 0.0, counter.count, 0
    for t in range(ticks):
        outs = []
        for svc in (shd, ref):
            pushed = push_tick(svc, jobs, t)
            outs.append({j: (d.matched, d.corr)
                         for j, d in svc.tick().items() if d is not None})
        for jid in jobs:
            a, b = shd._jobs[jid].last_sims, ref._jobs[jid].last_sims
            if (a is None) != (b is None):
                raise AssertionError(f"sharded: tick {t} {jid} scored once")
            if a is not None:
                worst = max(worst, float(np.max(np.abs(a - b))))
        if outs[0].keys() != outs[1].keys() or any(
                outs[0][j][0] != outs[1][j][0]
                or abs(outs[0][j][1] - outs[1][j][1]) > SHARD_TOL
                for j in outs[0]):
            raise AssertionError(f"sharded: tick {t} decisions differ: "
                                 f"{outs[0]} vs {outs[1]}")
        data_ticks += pushed
    if not worst <= SHARD_TOL:
        raise AssertionError(f"sharded: score error {worst:.3g} > "
                             f"{SHARD_TOL}")
    done = completed(jobs, ticks, n_finish)
    fin_s, fin_r = shd.finish_many(done), ref.finish_many(done)
    for j in done:
        if fin_s[j].matched != fin_r[j].matched or \
                abs(fin_s[j].corr - fin_r[j].corr) > 1e-9:
            raise AssertionError(f"sharded: final of {j} differs")
    if shd.dispatch_count != data_ticks:
        raise AssertionError(f"sharded: {shd.dispatch_count} dispatches "
                             f"for {data_ticks} data ticks")
    log(f"sharded: mesh={dict(mesh.shape)} S={shd.slot_capacity} "
        f"K={len(db)} data ticks={data_ticks} "
        f"dispatches={shd.dispatch_count} "
        f"max |score diff| vs unsharded={worst:.3g}, decisions identical, "
        f"{len(done)} finals identical, compiles={counter.count - c0}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the job configurations and runs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the bank-sharded four-chip check")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    import jax

    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    counter = CompileCounter()
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    db, configs = build_db(rng)
    bank = db.bank()
    log(f"bank: K={len(bank)} M={bank.series.shape[1]} lengths "
        f"{int(bank.lengths.min())}-{int(bank.lengths.max())} "
        f"({len(set(bank.lengths.tolist()))} distinct); set-up seconds="
        f"{time.perf_counter() - t0:.1f}")

    if args.chips == 4:
        run_sharded(db, make_jobs(rng, configs, 64), ticks=16, n_finish=16,
                    chips=4, counter=counter)
    else:
        run_phase("scored", db, make_jobs(rng, configs, 256), slots=256,
                  ticks=32, n_finish=32, counter=counter,
                  kw=dict(band=8, denoise=True))
        gc.collect()
        run_phase("approx", db, make_jobs(rng, configs, 128), slots=128,
                  ticks=16, n_finish=16, counter=counter,
                  kw=dict(denoise=True, min_probability=0.5,
                          prob_mode="approx"))
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
