"""The readers of the four-chip cell (``sharded4-backlog``).

On the committed one-chip trace (``traces/scored-live-spans.xplane.pb``)
the per-chip readers equal the one-chip readings, the score pull reads
the program's ``tuner.pull`` spans, and the fan-out readers read nothing,
since that program's dispatch spans carry no ``shards`` argument yet.  A
trace recorded on four forced host devices (a subprocess) gives the
fan-out readers the ``shards`` of a sharded service to read."""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import chips, layers, spans, spec, tracing  # noqa: E402

TRACES = os.path.join(ROOT, "tunerbench", "traces")
PATH = os.path.join(TRACES, "scored-live-spans.xplane.pb")
OLD = os.path.join(TRACES, "scored-live-short.xplane.pb")
SPAN_READERS = ("pull_ms.sharded4", "tick_shards.sharded4",
                "verdict_shards.sharded4")


def _ctx(path):
    trace = tracing.Trace(path)
    spans.attach(trace, path)
    chips.attach(trace, path)
    return type("C", (), {"trace": trace})


@pytest.fixture(scope="module")
def ctx():
    return _ctx(PATH)


def test_pull_ms_reads_the_pull_spans(ctx):
    from jax.profiler import ProfileData
    host = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(PATH).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("bench.tick", "tuner.pull"):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
    ticks = [h for h in host if h[2] == "bench.tick"]
    pulls = [h for h in host if h[2] == "tuner.pull"
             and any(s <= h[0] < e for s, e, _ in ticks)]
    assert pulls and ticks
    want = 1e-6 * sum(e - s for s, e, _ in pulls) / len(ticks)
    assert spec.reader("pull_ms.sharded4").read(ctx) == \
        pytest.approx(want, rel=1e-9)


def test_per_chip_readers_equal_the_one_chip_readings(ctx):
    busy = chips.of(ctx)
    assert list(busy) == ["/device:TPU:0"]
    assert busy["/device:TPU:0"] == pytest.approx(ctx.trace.busy_s,
                                                  rel=1e-12)
    assert spec.reader("idle_share.sharded4").read(ctx) == \
        pytest.approx(layers.idle_share(ctx), rel=1e-12)
    assert spec.reader("tick_device_ms.sharded4").read(ctx) == \
        pytest.approx(layers.tick_device_ms(ctx), rel=1e-12)


def test_fan_out_readers_need_the_shards_argument(ctx):
    assert spans.of(ctx).in_ticks("tuner.dispatch")
    assert spec.reader("tick_shards.sharded4").read(ctx) is None
    assert spec.reader("verdict_shards.sharded4").read(ctx) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_readers_read_nothing_without_program_spans(name):
    assert spec.reader(name).read(_ctx(OLD)) is None


def test_another_runs_file_gives_no_chips():
    assert chips.busy_by_device(tracing.Trace(OLD), PATH) is None


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import glob
    import json
    import sys
    import tempfile
    sys.path.insert(0, "src")
    sys.path.insert(0, ".")
    import jax
    import numpy as np
    from repro.core.database import pack_series
    from repro.serve.tuning import TuningService
    from tunerbench import spans, spec, tracing

    rng = np.random.default_rng(4)
    bank = pack_series([rng.random(int(n)).astype(np.float32)
                        for n in rng.integers(16, 30, size=12)],
                       labels=[f"w{k % 3}" for k in range(12)])
    span = jax.profiler.TraceAnnotation
    svc = TuningService(bank, band=4, slots=4, mesh={"bank": 4})
    jobs = {f"j{i}": rng.random(24).astype(np.float32) for i in range(3)}
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with span("bench.window"):
                for jid in jobs:
                    svc.submit(jid, expected_len=24)
                for t in range(3):
                    for jid, x in jobs.items():
                        svc.push(jid, x[8 * t: 8 * t + 8])
                    with span("bench.tick"):
                        svc.tick()
                with span("bench.finish_many"):
                    svc.finish_many(list(jobs))
        path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        trace = tracing.Trace(path)
        spans.attach(trace, path)
        ctx = type("C", (), {"trace": trace})
        print(json.dumps({n: spec.reader(n).read(ctx) for n in
                          ("tick_shards.sharded4", "verdict_shards.sharded4",
                           "pull_ms.sharded4")}))
""")


def test_fan_out_readers_on_a_sharded_trace():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["tick_shards.sharded4"] == 4.0
    assert got["verdict_shards.sharded4"] == 4.0
    assert got["pull_ms.sharded4"] > 0.0
