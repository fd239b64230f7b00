"""The plain reference agrees with the program where both compute the
same thing exactly, and the generator is a function of the seed.

On dyadic-grid data (multiples of 1/8) every DTW cost and accumulated
distance is exact in float32, so the program's paths equal the
reference's and the scores differ only by the float32 arithmetic of the
correlation.  The filters are compared with the program's float64 path.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import deploy, reference as R  # noqa: E402

from repro.core import filters  # noqa: E402
from repro.core.database import SeriesBank  # noqa: E402
from repro.serve.tuning import TuningService  # noqa: E402

LABELS = ("wordcount", "exim", "terasort")


def dyadic(rng, n):
    return (rng.integers(0, 9, size=n) / 8.0).astype(np.float32)


def small_bank(rng):
    series = [dyadic(rng, int(n)) for n in rng.integers(10, 25, size=9)]
    labels = [LABELS[k % 3] for k in range(9)]
    lengths = np.asarray([len(s) for s in series], np.int32)
    packed = np.stack([np.pad(s, (0, lengths.max() - len(s)), mode="edge")
                       for s in series])
    return (SeriesBank(packed, lengths, tuple(labels)),
            R.Bank(packed.astype(np.float64), lengths.astype(np.int64),
                   tuple(labels)))


def test_filter_design_matches_the_paper_pipeline():
    b0, a0 = filters.cheby1_design(*R.FILTER)
    b1, a1 = R.cheby1(*R.FILTER)
    np.testing.assert_allclose(b1, b0 / a0[0], rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(a1, a0 / a0[0], rtol=1e-12, atol=1e-15)


def test_filters_match_in_float64():
    rng = np.random.default_rng(1)
    x = rng.random((3, 57)).astype(np.float32).astype(np.float64)
    b, a = filters.cheby1_design(*R.FILTER)
    with jax.enable_x64(True):
        causal = np.asarray(filters.lfilter(b, a, jnp.asarray(x)))
        pre = np.asarray(filters.preprocess(jnp.asarray(x)))
    np.testing.assert_allclose(R.lfilter(x), causal, atol=1e-10)
    np.testing.assert_allclose(R.preprocess(x), pre, atol=2e-6)


@pytest.mark.parametrize("band", [None, 3])
def test_scores_and_verdicts_match_the_service(band):
    rng = np.random.default_rng(2 + (band or 0))
    bank, rbank = small_bank(rng)
    jobs = {f"j{i}": dyadic(rng, int(n))
            for i, n in enumerate(rng.integers(14, 26, size=4))}
    svc = TuningService(bank, band=band, slots=4)
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
                               qlen=len(x), band=band, open_end=True)
            ref = R.correlation(R.sums(x[:n], yp))
            assert fin.all()
            worst = max(worst, float(np.max(np.abs(
                svc._jobs[jid].last_sims - ref))))
    assert worst < 1e-5
    for jid, x in jobs.items():
        svc.push(jid, x[12:])
    final = svc.finish_many(list(jobs))
    for jid, x in jobs.items():
        yp, _ = R.warped(x.astype(np.float64), rbank, qlen=len(x),
                         band=band, open_end=False)
        ref = R.reduce(R.correlation(R.sums(x, yp)), rbank.labels)
        for w, s in ref.items():
            assert abs(final[jid].scores[w] - s) < 1e-5


@pytest.mark.parametrize("mode", ["approx", "exact"])
def test_probabilities_match_the_service(mode):
    rng = np.random.default_rng(7)
    bank, rbank = small_bank(rng)
    x = dyadic(rng, 24)
    v = (rng.integers(1, 5, size=24) / 256.0).astype(np.float32)
    svc = TuningService(bank, slots=2, min_probability=0.5, prob_mode=mode)
    svc.submit("j", expected_len=24)
    svc.push("j", x[:16], variance=v[:16])
    svc.tick()
    yp, _ = R.warped(x[:16].astype(np.float64), rbank, qlen=24, band=None,
                     open_end=True)
    ref = R.probability(R.sums(x[:16], yp, v[:16]), 0.9,
                        approx=mode == "approx")
    np.testing.assert_allclose(svc._jobs["j"].last_probs, ref, atol=1e-4)
    svc.push("j", x[16:], variance=v[16:])
    d = svc.finish_many(["j"])["j"]
    yp, _ = R.warped(x.astype(np.float64), rbank, qlen=24, band=None,
                     open_end=False)
    probs = R.reduce(R.probability(R.sums(x, yp, v), 0.9), rbank.labels)
    assert abs(d.probability - probs[max(d.scores, key=d.scores.get)]) \
        < 1e-4


def test_generator_is_a_function_of_the_seed():
    with open(os.path.join(ROOT, "tunerbench", "configs",
                           "hadoop1k-prob.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, bank_size=24, configs_per_app=2, fresh_per_app=3)
    a = deploy.job_pool(cfg, 2**33 + 5, 12)
    b = deploy.job_pool(cfg, 2**33 + 5, 12)
    c = deploy.job_pool(cfg, 17, 12)
    assert [j.job_id for j in a] == [j.job_id for j in b]
    assert all(np.array_equal(p.x, q.x) and np.array_equal(p.v, q.v)
               for p, q in zip(a, b))
    assert sorted(len(j) for j in a) == sorted(len(j) for j in c)
    assert any(not np.array_equal(p.x, q.x) for p, q in zip(a, c))
    r1 = deploy.profiled_runs(cfg, 9, deploy.layout(cfg))
    r2 = deploy.profiled_runs(cfg, 9, deploy.layout(cfg))
    assert all(np.array_equal(p[3], q[3]) for p, q in zip(r1, r2))
