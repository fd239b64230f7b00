"""Per-layer quantities the readers in ``metrics/`` share."""

from __future__ import annotations

from typing import Optional

#: the tick programs (``core.dtw._scored_kernel_tick*``) and kernels
TICK_PROGRAM = "jit__scored_kernel_tick"
TICK_KERNEL = "dtw_stream_scored"
VERDICT_KERNELS = ("dtw_score_offline_3ch", "dtw_score_offline_6ch")


def _nch(cfg, verdict: bool) -> int:
    sv = cfg["service"]
    if sv.get("min_probability") is None:
        return 3
    if verdict or sv.get("prob_mode", "exact") == "exact":
        return 6
    return 4


def idle_share(ctx) -> Optional[float]:
    t = ctx.trace
    if t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def tick_kernel_roofline(ctx) -> Optional[float]:
    seconds = ctx.trace.op_s(TICK_KERNEL)
    band = ctx.cfg["service"].get("band")
    var = ctx.cfg["agents"] == "uncertain"
    ops = nbytes = 0
    w = ctx.work(TICK_KERNEL)
    for jobs in ctx.rec.tick_work:
        o, b = w.count(jobs, ctx.bank_lengths, band, _nch(ctx.cfg, False),
                       var)
        ops += o
        nbytes += b
    return ctx.share_of_roofline(ops, nbytes, seconds)


def verdict_kernel_roofline(ctx) -> Optional[float]:
    seconds = sum(ctx.trace.op_s(k) for k in VERDICT_KERNELS)
    band = ctx.cfg["service"].get("band")
    var = ctx.cfg["agents"] == "uncertain"
    ops = nbytes = 0
    w = ctx.work("dtw_score_offline")
    for queries in ctx.rec.verdict_work:
        o, b = w.count(queries, ctx.bank_lengths, band, _nch(ctx.cfg, True),
                       var)
        ops += o
        nbytes += b
    return ctx.share_of_roofline(ops, nbytes, seconds)


def tick_device_ms(ctx) -> Optional[float]:
    seconds, _ = ctx.trace.module_s(TICK_PROGRAM)
    ticks = len(ctx.trace.span_list("bench.tick"))
    if not ticks or seconds <= 0:
        return None
    return 1e3 * seconds / ticks


def tick_host_ms(ctx) -> Optional[float]:
    ticks = len(ctx.trace.span_list("bench.tick"))
    if not ticks:
        return None
    return 1e3 * ctx.trace.idle_inside("bench.tick") / ticks
