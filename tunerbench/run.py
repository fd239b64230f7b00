#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the TPU this process is started on.

    python3 tunerbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up builds the cell's deployment from the seed (the bank of profiled
runs, preprocessed in bulk by the paper's pipeline, and the pool of
in-flight jobs),
starts ``TuningService`` with the configuration's settings, warms every
program shape the window can reach (the tick at its slot bucket with
8-sample chunks, every verdict batch and length bucket) and pre-advances
the in-flight jobs as the mix says.  The window then drives the service
for ``--seconds`` through ``push``, ``tick`` and ``finish_many``.  With
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics read from the trace; otherwise the end-to-end
metrics, taken by the host clock.  After the window, a sample of what it
produced is compared with the plain reference (``check.py``).

Standard error carries the details (latency medians and counts, how late
the generator ran, compiles inside the window) and, as its last lines,
every compared number beside its limit.  The last line of standard
output is one JSON object.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from tunerbench import check, deploy, reference, spec, traffic  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".tunerbench", "trace")


def log(msg: str) -> None:
    print(f"[tunerbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA backend compiles in this process."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1


class GcWatch:
    """Pauses of Python's garbage collector, by generation, while it is
    registered."""

    def __init__(self):
        self.pauses = {0: [], 1: [], 2: []}
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t)
            self._t = None

    def line(self) -> str:
        full = self.pauses[2]
        every = sum(sum(p) for p in self.pauses.values())
        return (f"gc inside the window: {len(full)} full collections, "
                f"longest {1e3 * max(full, default=0.0):.3f} ms; all "
                f"collections {1e3 * every:.3f} ms")


def setup_jax() -> None:
    """The persistent compile cache at a fixed path inside the checkout,
    holding every program, however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    """The TPU devices, or exit non-zero naming what JAX found."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"tunerbench: needs a TPU; JAX found platform "
                 f"{d0.platform!r} ({d0.device_kind})")
    if len(devices) < chips:
        sys.exit(f"tunerbench: the cell needs {chips} TPU chips, JAX found "
                 f"{len(devices)}")
    return devices


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def end_to_end(name: str, rec, setup_s: float):
    """An end-to-end metric of the window -> (value, detail line)."""
    if name == "setup_s":
        return setup_s, None
    if name == "samples_per_s":
        v = rec.samples / rec.window_s
        return v, (f"samples_per_s: {rec.samples} samples scored in "
                   f"{rec.window_s:.3f} s, {rec.ticks} ticks")
    lat = {"score_latency_p95_ms": rec.score_latency,
           "verdict_latency_p95_ms": rec.verdict_latency}[name]
    if not lat:
        raise RuntimeError(f"{name}: the window produced no samples")
    ms = 1e3 * np.asarray(lat)
    return p95(ms), (f"{name}: median {float(np.median(ms)):.3f} ms, "
                     f"p95 {p95(ms):.3f} ms, max {float(ms.max()):.3f} ms, "
                     f"{len(ms)} samples")


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            man=None, cfg=None, mix=None, limits=None, chips=True,
            tamper=None, workers=None, sink=None):
    """One run of a cell -> the result object.  ``chips=False`` skips the
    look for a TPU (tests run the rest at small sizes on the CPU);
    ``tamper`` receives the service before the window (tests break the
    timed path with it); ``sink``, a dict, receives the compared items,
    the reference's answers and the reference bank."""
    man = man or spec.manifest()
    cell = spec.cell(man, cell_name)
    cfg = cfg or spec.config(man, cell["config"])
    mix = mix or spec.mix(cell["traffic"])
    limits = limits or spec.limits(cell_name)
    import jax
    setup_jax()
    devices = require_chips(cell["chips"]) if chips else jax.devices()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from tunerbench import system
    counter = CompileCounter()

    phases = []

    def phase(name):
        phases.append(f"{name} {time.perf_counter() - T_START:.1f} s "
                      f"({counter.count} compiles)")

    lay = deploy.layout(cfg)
    runs = deploy.profiled_runs(cfg, seed, lay)
    pool = deploy.job_pool(cfg, seed, mix["pool"], lay)
    phase("traces")
    bank_ref = reference.build_bank([r[3] for r in runs],
                                    [r[0] for r in runs])
    db = system.build_db(runs, bank_ref)
    phase("bank")
    svc = system.make_service(cfg, db)
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else None
    drv = traffic.driver(svc, pool, mix, seed, span)
    drv.start()
    phase("in-flight set")
    for jb, npad in drv.verdict_shapes():
        system.verdict_warm(svc, jb, npad, cfg["agents"] == "uncertain")
    phase("verdict shapes")
    if tamper is not None:
        tamper(svc)
    gc.collect()
    compiles0 = counter.count
    setup_s = time.perf_counter() - T_START

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        drv.tracing = True
    gcw = GcWatch()
    gc.callbacks.append(gcw)
    with (jax.profiler.TraceAnnotation("bench.window") if trace
          else contextlib.nullcontext()):
        drv.window(seconds)
    gc.callbacks.remove(gcw)
    if trace:
        jax.profiler.stop_trace()
    rec = drv.rec
    compiles = counter.count - compiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[: cell["chips"]])
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    log(f"device: {d0.platform} {d0.device_kind} x {len(devices)}; cell "
        f"{cell_name} seed {seed}; set-up {setup_s:.3f} s")
    log("set-up reached: " + "; ".join(phases))
    log(f"compiles inside the window: {compiles}")
    log(f"window: {rec.window_s:.3f} s, {rec.ticks} ticks, {rec.pushes} "
        f"pushes, {rec.samples} samples, {len(rec.verdicts)} verdicts, "
        f"{len(rec.early)} early decisions; slot repacks "
        f"{svc.slot_repack_count}, degraded dispatches "
        f"{svc.degraded_dispatch_count}, retries {svc.retry_count}")
    if rec.tick_s:
        ts = 1e3 * np.asarray(rec.tick_s)
        log(f"ticks: median {float(np.median(ts)):.3f} ms, longest "
            f"{float(ts.max()):.3f} ms (tick {int(ts.argmax())} of "
            f"{ts.size})")
    log(gcw.line())
    if rec.late:
        late = 1e3 * np.asarray(rec.late)
        log(f"generator lateness: median {float(np.median(late)):.3f} ms, "
            f"p95 {p95(late):.3f} ms, max {float(late.max()):.3f} ms")

    result = {"correct": False,
              "attempted": rec.pushes + len(rec.verdicts),
              "failed": 0, "metrics": {}, "device": device}
    if trace:
        from tunerbench import tracing
        tr = tracing.Trace.find(TRACE_DIR)
        kind = spec.peaks().get(d0.device_kind)
        if kind is None and chips:
            raise KeyError(f"no peaks for device kind {d0.device_kind!r} "
                           "in peaks.json")
        ctx = tracing.Context(tr, rec, bank_ref.lengths, cfg, kind,
                              spec.work)
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        for m in spec.per_layer(man, cell_name):
            v = spec.reader(m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    else:
        for m in spec.end_to_end(man, cell_name):
            v, line = end_to_end(m["name"], rec, setup_s)
            if line:
                log(line)
            result["metrics"][m["name"]] = {"value": float(v),
                                            "unit": m["unit"]}

    # the comparison, after the program's state is freed
    items = check.sample(rec, seed, limits["sample"])
    del drv, svc, db
    gc.collect()
    t_ref = time.perf_counter()
    refs = check.answers(items, bank_ref, cfg, workers=workers)
    nums = check.numbers(items, refs, bank_ref, cfg)
    for line in check.worst(items, refs, bank_ref, cfg):
        log("widest " + line)
    log("gaps: " + ", ".join(f"{k} {v:.6g}" for k, v in nums.items()))
    if sink is not None:
        sink.update(items=items, refs=refs, bank=bank_ref, numbers=nums)
    ok, rows = check.judge(nums, limits["limits"])
    log(f"reference: {len(items)} items "
        f"({sum(it['kind'] == 'snapshot' for it in items)} in flight, "
        f"{sum(it['kind'] == 'early' for it in items)} early, "
        f"{sum(it['kind'] == 'verdict' for it in items)} verdicts) in "
        f"{time.perf_counter() - t_ref:.3f} s")
    result["correct"] = bool(ok and compiles == 0)
    result["check"] = {name: {"value": value, "limit": lim}
                       for name, value, lim in rows}
    result["check"]["compiles_in_window"] = {"value": compiles, "limit": 0}
    for name, value, lim in rows:
        log(f"check {name} {value!r} limit {lim!r}")
    log(f"check compiles_in_window {compiles} limit 0")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = execute(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
