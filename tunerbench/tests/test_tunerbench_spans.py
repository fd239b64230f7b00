"""The program-span readers on a short trace recorded on the chip
(``traces/scored-live-spans.xplane.pb``: a half-second window of the
scored-live cell on one TPU v5e, ``--trace 1``, with the service's
``tuner.*`` spans).

The readers' values are worked out here a second way, straight from the
profiler's events, and pinned to the values read from the file when it
was committed; ``tunerbench/spans.py`` run on the file printed the
unattributed idle pinned below.  On the older trace, recorded from a
program without spans, the readers read nothing."""
import os
import sys
import warnings

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import spans, spec, tracing  # noqa: E402

TRACES = os.path.join(ROOT, "tunerbench", "traces")
PATH = os.path.join(TRACES, "scored-live-spans.xplane.pb")
OLD = os.path.join(TRACES, "scored-live-short.xplane.pb")
READERS = ("drain_ms.live", "decide_ms.live", "filter_dispatches.live")


@pytest.fixture(scope="module")
def events():
    """(device ops, host spans) of the file, each [(start, end, name,
    args)] in ns, read without the reduction's code."""
    from jax.profiler import ProfileData
    ops, host = [], []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(PATH).planes:
            for line in plane.lines:
                for ev in line.events:
                    row = (ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name, dict(ev.stats))
                    if plane.name == "/device:TPU:0" and \
                            line.name == "XLA Ops":
                        ops.append(row)
                    elif ev.name.startswith(("bench.", "tuner.")):
                        host.append(row)
    return ops, host


@pytest.fixture(scope="module")
def ctx():
    trace = tracing.Trace(PATH)
    assert spans.attach(trace, PATH) is not None
    return type("C", (), {"trace": trace})


def _named(host, name):
    return [h for h in host if h[2] == name]


def _in_ticks(host, name):
    ticks = _named(host, "bench.tick")
    return [h for h in _named(host, name)
            if any(s <= h[0] < e for s, e, _, _ in ticks)], len(ticks)


def test_drain_and_decide_ms(events, ctx):
    _, host = events
    for name, metric in (("tuner.drain", "drain_ms.live"),
                         ("tuner.decide", "decide_ms.live")):
        sel, ticks = _in_ticks(host, name)
        want = 1e-6 * sum(e - s for s, e, _, _ in sel) / ticks
        assert sel and ticks
        assert spec.reader(metric).read(ctx) == pytest.approx(want,
                                                              rel=1e-9)


def test_filter_dispatches_count_filter_spans(events, ctx):
    _, host = events
    drains, ticks = _in_ticks(host, "tuner.drain")
    filters, _ = _in_ticks(host, "tuner.filter")
    filtered = sum(d[3]["filtered"] for d in drains)
    # one tuner.filter span per causal-filter call the drain counted,
    # and one call per drained job (every job is denoised)
    assert filtered == len(filters) == sum(d[3]["jobs"] for d in drains)
    assert spec.reader("filter_dispatches.live").read(ctx) == \
        pytest.approx(filtered / ticks, rel=1e-12)


def test_unattributed_idle(events, ctx):
    """Device-idle ns inside bench.tick and outside every direct child of
    tuner.tick, by a sweep over the uncovered stretches."""
    ops, host = events
    ticks = _named(host, "bench.tick")
    tops = _named(host, "tuner.tick")
    # the union of the tick's children is that of every tuner.* span
    # inside it (a tuner.filter lies inside its tuner.drain)
    kids = [h for h in host if h[2].startswith("tuner.")
            and h[2] != "tuner.tick"
            and any(t[0] <= h[0] and h[1] <= t[1] for t in tops)]
    total = 0
    for ts, te, _, _ in ticks:
        cover = sorted((max(s, ts), min(e, te)) for s, e, _, _ in kids
                       if s < te and e > ts)
        free, at = [], ts
        for s, e in cover:
            if s > at:
                free.append((at, s))
            at = max(at, e)
        if at < te:
            free.append((at, te))
        for fs, fe in free:
            marks = sorted([(max(s, fs), 1) for s, e, _, _ in ops
                            if s < fe and e > fs]
                           + [(min(e, fe), -1) for s, e, _, _ in ops
                              if s < fe and e > fs])
            busy, depth, last = 0, 0, None
            for t, d in marks:
                if depth > 0:
                    busy += t - last
                depth += d
                last = t
            total += (fe - fs) - busy
    prog = spans.of(ctx)
    assert prog.unattributed_idle_s() == pytest.approx(total * 1e-9,
                                                       rel=1e-6)
    # what `python3 tunerbench/spans.py` printed for the file
    per_tick_ms = 1e3 * prog.unattributed_idle_s() / prog.n_ticks
    assert round(per_tick_ms, 3) == PINNED["unattributed_ms_per_tick"]


def test_idle_gaps_are_named_by_program_spans(ctx):
    """Trace.idle_gaps names every long gap of a tick ``bench.tick``;
    with the program's spans each is named by the phase of the tick the
    host was in."""
    prog = spans.of(ctx)
    old, new = ctx.trace.idle_gaps(), prog.idle_gaps()
    assert [g[1] for g in old] == [g[1] for g in new]
    inside = [(o[0], n[0]) for o, n in zip(old, new)
              if o[0] == "bench.tick"]
    assert inside
    assert all(n.startswith("tuner.") for _, n in inside)
    assert {n for _, n in inside} <= {
        "tuner.drain", "tuner.filter", "tuner.repack", "tuner.chunks",
        "tuner.dispatch", "tuner.pull", "tuner.decide", "tuner.tick"}


def test_span_table_nests_the_tick(ctx):
    prog = spans.of(ctx)
    rows = {n: (c, tot, own) for n, c, tot, own, _ in prog.table()}
    ticks = prog.n_ticks
    assert rows["tuner.tick"][0] == ticks
    # a tick's own time is what its children leave: under 1%
    assert rows["tuner.tick"][2] < 0.01 * rows["tuner.tick"][1]
    assert rows["tuner.filter"][0] == round(
        spec.reader("filter_dispatches.live").read(ctx) * ticks)


def test_pinned_readings(ctx):
    """Values read from the file when it was committed."""
    for name in READERS:
        assert spec.reader(name).read(ctx) == pytest.approx(PINNED[name],
                                                            rel=1e-9)
    assert spans.of(ctx).n_ticks == PINNED["ticks"]


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_program_spans(name):
    trace = tracing.Trace(OLD)
    prog = spans.attach(trace, OLD)
    assert prog is not None and prog.spans == []
    c = type("C", (), {"trace": trace})
    assert spec.reader(name).read(c) is None
    assert prog.unattributed_idle_s() is None


def test_another_runs_file_is_not_attached():
    trace = tracing.Trace(OLD)
    assert spans.attach(trace, PATH) is None
    assert spans.of(type("C", (), {"trace": trace})) is None


PINNED = {"drain_ms.live": 37.98282071428572,
          "decide_ms.live": 3.886591428571411,
          "filter_dispatches.live": 20.142857142857142,
          "ticks": 7, "unattributed_ms_per_tick": 0.097}
