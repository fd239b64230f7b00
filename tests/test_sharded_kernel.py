"""The K-sharded service on the kernel path (4 forced host devices in a
subprocess, so the main test process keeps its single real device).

Under a mesh every tick mode shard_maps the same ``core.dtw`` dispatch
it runs on one device, and a batch of verdicts scores each device's
shard of the bank there.  With the Pallas kernels forced (interpret mode
on the CPU), the sharded service must equal the unsharded one: DP rows
and moment slabs bit for bit, scores to 1e-6, the same decisions, and
the same verdicts, over ragged and banded banks.  ``mesh={"bank": 4}``
builds the same service as an explicit mesh, the dispatch spans record
the fan-out, and a small sharded run agrees with the benchmark's plain
float64 reference.

One subprocess runs every part and prints a line per part; each test
reads its own line.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import glob
    import sys
    import tempfile
    import traceback
    import warnings
    sys.path.insert(0, "src")
    sys.path.insert(0, ".")
    import jax
    import numpy as np
    from repro.core import dtw as _dtw
    from repro.core.database import SeriesBank, pack_series
    from repro.serve.tuning import TuningService

    on_cpu = _dtw._kernel_backend

    def kernel_path(on):
        # the dispatchers' default: the Pallas kernels (interpret mode on
        # the CPU) where ``on``, else the jnp twins
        _dtw._kernel_backend = (lambda: True) if on else on_cpu

    def make_bank(rng, k, lo=18, hi=40):
        series = []
        for i in range(k):
            n = int(rng.integers(lo, hi))
            t = np.linspace(0, 1, n, dtype=np.float32)
            s = 0.5 + 0.3 * np.sin(2 * np.pi * (1.5 + 0.7 * i) * t) \\
                + 0.04 * rng.normal(size=n)
            series.append(np.clip(s, 0, 1).astype(np.float32))
        return pack_series(series, labels=[f"w{i % 4}" for i in range(k)])

    def make_queries(rng, n=3, qlen=40, var=False):
        out = {}
        for j in range(n):
            t = np.linspace(0, 1, qlen, dtype=np.float32)
            q = 0.5 + 0.3 * np.sin(2 * np.pi * (1.5 + 0.7 * j) * t) \\
                + 0.04 * rng.normal(size=qlen)
            v = (0.001 + 0.002 * rng.random(qlen)).astype(np.float32) \\
                if var else None
            out[f"job{j}"] = (np.clip(q, 0, 1).astype(np.float32), v)
        return out

    def drive(svc, queries, steps=(7, 3, 9, 0, 5)):
        # ragged pushes: jobs push different sizes, some nothing, so
        # every tick carries padded samples
        for jid, (q, _) in queries.items():
            svc.submit(jid, expected_len=len(q))
        pos = {jid: 0 for jid in queries}
        ticks, t = [], 0
        while any(pos[j] < len(q) for j, (q, _) in queries.items()):
            for i, (jid, (q, v)) in enumerate(queries.items()):
                n = steps[(t + i) % len(steps)]
                a, b = pos[jid], min(pos[jid] + n, len(q))
                if b > a:
                    svc.push(jid, q[a:b],
                             **({} if v is None else {"variance": v[a:b]}))
                pos[jid] = b
            t += 1
            dec = svc.tick()
            k = svc._k
            state = [np.asarray(svc._rows)[..., :k]]
            if svc._moms is not None:
                state.append(np.asarray(svc._moms)[..., :k])
            ticks.append(dict(
                state=state,
                sims={j: svc._jobs[j].last_sims.copy() for j in queries
                      if svc._jobs[j].last_sims is not None},
                probs={j: svc._jobs[j].last_probs.copy() for j in queries
                       if svc._jobs[j].last_probs is not None},
                decisions={j: (d.matched, d.corr) for j, d in dec.items()
                           if d is not None}))
        finals = svc.finish_many(list(queries))
        return ticks, finals

    def same_run(a, b, what):
        ta, fa = a
        tb, fb = b
        assert len(ta) == len(tb), what
        for x, y in zip(ta, tb):
            for sa, sb in zip(x["state"], y["state"]):
                assert np.array_equal(sa, sb), (what, "state differs")
            for key in ("sims", "probs"):
                assert x[key].keys() == y[key].keys(), (what, key)
                for j in x[key]:
                    err = float(np.max(np.abs(x[key][j] - y[key][j])))
                    assert err < 1e-6, (what, key, j, err)
            assert x["decisions"].keys() == y["decisions"].keys(), what
            for j, (m, c) in x["decisions"].items():
                assert y["decisions"][j][0] == m, (what, j)
                assert abs(y["decisions"][j][1] - c) < 1e-6, (what, j)
        same_verdicts(fa, fb, what)

    def same_verdicts(fa, fb, what):
        assert fa.keys() == fb.keys(), what
        for j in fa:
            assert fa[j].matched == fb[j].matched, (what, j)
            assert fa[j].corr == fb[j].corr, (what, j)
            assert fa[j].scores == fb[j].scores, (what, j)
            assert fa[j].probability == fb[j].probability, (what, j)

    mesh = jax.make_mesh((4,), ("bank",))

    def part_tick(mode, band):
        kernel_path(True)
        rng = np.random.default_rng(3 + (band or 0))
        bank = make_bank(rng, 30)        # ragged, not a multiple of 4
        prob = mode == "approx_prob"
        queries = make_queries(rng, var=prob)
        kw = dict(band=band, threshold=0.5, margin=0.01, stable_ticks=2,
                  min_fraction=0.2, slots=4)
        if prob:
            kw.update(min_probability=0.5, prob_mode="approx")
        one = TuningService(bank, **kw)
        four = TuningService(bank, mesh=mesh, **kw)
        assert four._base_mode() == mode
        assert four._kp % 4 == 0 and four._kp >= bank.series.shape[0]
        a, b = drive(one, queries), drive(four, queries)
        same_run(a, b, (mode, band))
        assert four.dispatch_count == four.ticks
        assert four.degraded_dispatch_count == 0

    def part_verdicts(kernel):
        kernel_path(kernel)
        rng = np.random.default_rng(11)
        bank = make_bank(rng, 260, lo=20, hi=60)   # five jnp tiles of 64
        queries = make_queries(rng, n=5, qlen=50)
        for band in (None, 8):
            kw = dict(band=band, threshold=0.5, slots=8)
            one = TuningService(bank, **kw)
            four = TuningService(bank, mesh=mesh, **kw)
            plan = bank.score_plan(four.mesh)
            devs = {next(iter(t.devices())) for t, _ in plan.tiles}
            assert len(devs) == 4, devs
            for svc in (one, four):
                for jid, (q, _) in queries.items():
                    svc.submit(jid, expected_len=len(q))
                    svc.push(jid, q)
                svc.tick()
            same_verdicts(one.finish_many(list(queries)),
                          four.finish_many(list(queries)),
                          ("verdicts", kernel, band))

    def part_mesh_dict():
        kernel_path(True)
        rng = np.random.default_rng(5)
        bank = make_bank(rng, 17)
        queries = make_queries(rng)
        kw = dict(band=6, threshold=0.5, slots=4)
        a = TuningService(bank, mesh={"bank": 4}, **kw)
        b = TuningService(bank, mesh=jax.make_mesh((4,), ("bank",)), **kw)
        assert a.mesh == b.mesh and a._kp == b._kp and a._ndev == 4
        same_run(drive(a, queries), drive(b, queries), "mesh dict")
        c = TuningService(bank, **kw)
        c.rescale({"bank": 2})
        assert c.mesh.devices.size == 2 and c._ndev == 2

    def part_spans():
        kernel_path(True)
        from jax.profiler import ProfileData
        rng = np.random.default_rng(9)
        bank = make_bank(rng, 20)
        queries = make_queries(rng, n=2, qlen=24)
        for m, want in ((None, 1), (mesh, 4)):
            svc = TuningService(bank, band=6, threshold=0.5, slots=2,
                                mesh=m)
            drive(svc, queries)                  # compile first
            svc = TuningService(bank, band=6, threshold=0.5, slots=2,
                                mesh=m)
            with tempfile.TemporaryDirectory() as d:
                with jax.profiler.trace(d):
                    drive(svc, queries)
                path, = glob.glob(os.path.join(d, "plugins", "profile",
                                               "*", "*.xplane.pb"))
                shards = {}
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    for plane in ProfileData.from_file(path).planes:
                        for line in plane.lines:
                            for ev in line.events:
                                if ev.name in ("tuner.dispatch",
                                               "tuner.verdict.dispatch"):
                                    shards.setdefault(ev.name, set()).add(
                                        dict(ev.stats)["shards"])
            assert shards == {"tuner.dispatch": {want},
                              "tuner.verdict.dispatch": {want}}, shards

    def part_reference():
        kernel_path(True)
        from tunerbench import reference as R
        rng = np.random.default_rng(21)
        labels = ("wordcount", "exim", "terasort")

        def dyadic(n):
            return (rng.integers(0, 9, size=n) / 8.0).astype(np.float32)
        series = [dyadic(int(n)) for n in rng.integers(10, 25, size=64)]
        lengths = np.asarray([len(s) for s in series], np.int32)
        packed = np.stack([np.pad(s, (0, lengths.max() - len(s)),
                                  mode="edge") for s in series])
        names = tuple(labels[k % 3] for k in range(64))
        bank = SeriesBank(packed, lengths, names)
        rbank = R.Bank(packed.astype(np.float64), lengths.astype(np.int64),
                       names)
        jobs = {f"j{i}": dyadic(int(n))
                for i, n in enumerate(rng.integers(14, 26, size=4))}
        for band in (None, 3):
            svc = TuningService(bank, band=band, slots=4, mesh=mesh)
            for jid, x in jobs.items():
                svc.submit(jid, expected_len=len(x))
            worst = 0.0
            for t in range(3):
                for jid, x in jobs.items():
                    svc.push(jid, x[4 * t: 4 * t + 4])
                svc.tick()
                for jid, x in jobs.items():
                    n = 4 * (t + 1)
                    yp, fin = R.warped(x[:n].astype(np.float64), rbank,
                                       qlen=len(x), band=band,
                                       open_end=True)
                    assert fin.all()
                    ref = R.correlation(R.sums(x[:n], yp))
                    worst = max(worst, float(np.max(np.abs(
                        svc._jobs[jid].last_sims - ref))))
            assert worst < 1e-5, (band, worst)
            for jid, x in jobs.items():
                svc.push(jid, x[12:])
            final = svc.finish_many(list(jobs))
            for jid, x in jobs.items():
                yp, _ = R.warped(x.astype(np.float64), rbank, qlen=len(x),
                                 band=band, open_end=False)
                ref = R.reduce(R.correlation(R.sums(x, yp)), rbank.labels)
                for w, s in ref.items():
                    assert abs(final[jid].scores[w] - s) < 1e-5, (band, w)

    PARTS = {
        "tick-scored-full": lambda: part_tick("scored", None),
        "tick-scored-band": lambda: part_tick("scored", 6),
        "tick-approx-full": lambda: part_tick("approx_prob", None),
        "tick-approx-band": lambda: part_tick("approx_prob", 6),
        "verdicts-kernel": lambda: part_verdicts(True),
        "verdicts-jnp": lambda: part_verdicts(False),
        "mesh-dict": part_mesh_dict,
        "spans": part_spans,
        "reference": part_reference,
    }
    for name, fn in PARTS.items():
        try:
            fn()
            print(f"PART {name} OK", flush=True)
        except Exception:
            print(f"PART {name} FAILED", flush=True)
            traceback.print_exc()
""")

PARTS = ["tick-scored-full", "tick-scored-band", "tick-approx-full",
         "tick-approx-band", "verdicts-kernel", "verdicts-jnp", "mesh-dict",
         "spans", "reference"]


@pytest.fixture(scope="module")
def run():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    return r


@pytest.mark.parametrize("part", PARTS)
def test_sharded_service_part(run, part):
    assert f"PART {part} OK" in run.stdout, \
        f"rc={run.returncode}\n{run.stdout[-3000:]}\n{run.stderr[-6000:]}"
