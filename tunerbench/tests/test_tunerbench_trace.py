"""The trace reduction on a short trace recorded on the chip
(``traces/scored-live-short.xplane.pb``: a half-second window of the
scored-live cell on one TPU v5e, ``--trace 1``).

The same sums are worked out here a second way, straight from the
profiler's events, and pinned to the values read from the file when it
was committed."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import layers, tracing  # noqa: E402

PATH = os.path.join(ROOT, "tunerbench", "traces",
                    "scored-live-short.xplane.pb")


@pytest.fixture(scope="module")
def events():
    """(device ops, device programs, bench spans), each [(start, end,
    name)] in ns, read without the reduction's code."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(PATH)
    ops, mods, spans = [], [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                row = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(row)
                elif plane.name == "/device:TPU:0" and \
                        line.name == "XLA Modules":
                    mods.append(row)
                elif ev.name.startswith("bench."):
                    spans.append(row)
    return ops, mods, spans


@pytest.fixture(scope="module")
def trace():
    return tracing.Trace(PATH)


def test_window_is_the_bench_window_span(events, trace):
    _, _, spans = events
    (s, e, _), = [x for x in spans if x[2] == "bench.window"]
    assert trace.window_s == pytest.approx((e - s) * 1e-9, rel=1e-12)


def test_busy_time_is_the_union_of_op_intervals(events, trace):
    ops, _, spans = events
    (w0, w1, _), = [x for x in spans if x[2] == "bench.window"]
    # sweep over ns ticks of the clipped intervals
    marks = sorted([(max(s, w0), 1) for s, e, _ in ops if e > w0 and s < w1]
                   + [(min(e, w1), -1) for s, e, _ in ops
                      if e > w0 and s < w1])
    busy, depth, last = 0.0, 0, None
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert trace.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    idle = 100.0 * (1.0 - busy / (w1 - w0))
    assert layers.idle_share(type("C", (), {"trace": trace})) == \
        pytest.approx(idle, rel=1e-9)


def test_kernel_and_tick_program_time(events, trace):
    ops, mods, spans = events
    (w0, w1, _), = [x for x in spans if x[2] == "bench.window"]
    inside = [x for x in ops if x[1] > w0 and x[0] < w1]
    kernel = sum(e - s for s, e, n in inside
                 if n.lstrip("%").startswith("dtw_stream_scored."))
    assert trace.op_s("dtw_stream_scored") == pytest.approx(kernel * 1e-9,
                                                            rel=1e-9)
    ticks = [x for x in spans if x[2] == "bench.tick"]
    prog = sum(e - s for s, e, n in mods if e > w0 and s < w1
               and n.startswith("jit__scored_kernel_tick"))
    ctx = type("C", (), {"trace": trace})
    assert layers.tick_device_ms(ctx) == pytest.approx(
        1e-6 * prog / len(ticks), rel=1e-9)
    assert kernel > 0 and prog >= kernel and ticks


def test_pinned_readings(trace):
    """Values read from the file when it was committed."""
    assert trace.window_s == pytest.approx(PINNED["window_s"], rel=1e-9)
    assert trace.busy_s == pytest.approx(PINNED["busy_s"], rel=1e-9)
    assert trace.op_s("dtw_stream_scored") == pytest.approx(
        PINNED["kernel_s"], rel=1e-9)
    assert len(trace.span_list("bench.tick")) == PINNED["ticks"]


PINNED = {"window_s": 0.64029628, "busy_s": 0.3448223979999988,
          "kernel_s": 0.05567098200000009, "ticks": 8}
