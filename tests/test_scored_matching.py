"""Matrix-free offline scoring (closed-end moment-carrying scorers).

Equivalence contract, mirroring the streaming-kernel suite's regimes:

* on DYADIC-GRID data every DTW cost, path sum and moment sum is exactly
  representable in f32, so the wavefront, the min-plus matrix path and
  the Pallas offline kernel make identical predecessor choices — device
  scores equal the host backtrack + correlation reference to float64-
  rounding tolerance (<= 1e-6), and the jnp wavefront equals the Pallas
  kernel BITWISE;
* on continuous-noise data, near-tie argmin flips move individual warp
  paths (~1e-3 score motion) — agreement is pinned at that tolerance.

Plus: batching invariance (J-batched == single bitwise), the Table-1
golden re-lock through the rewired engine, and the guard on the unsound
pure-wavelet prune mode.
"""
import json
import os

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import dtw, similarity, similarity_bank
from repro.core.database import pack_series


def _dyadic_series(rng, n, denom=8, hi=9):
    return (rng.integers(0, hi, n) / float(denom)).astype(np.float32)


def _dyadic_bank(rng, k, lo=12, hi=30):
    series = [_dyadic_series(rng, int(rng.integers(lo, hi)))
              for _ in range(k)]
    return series, pack_series(series)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_score_bank_equals_host_backtrack_on_dyadic(seed):
    """Property (ragged + banded): the closed-end moment scorer equals
    ``similarity_bank``'s host-backtrack matrix path on random
    dyadic-grid banks to float64-rounding tolerance."""
    rng = np.random.default_rng(seed)
    series, bank = _dyadic_bank(rng, int(rng.integers(3, 9)))
    x = _dyadic_series(rng, int(rng.integers(8, 26)))
    for band in (None, int(rng.integers(3, 8))):
        got = np.asarray(dtw.dtw_score_bank(
            x, bank.series, bank.lengths, band=band, use_kernel=False))
        want = similarity_bank(x, bank, band=band, matrix_path=True)
        np.testing.assert_allclose(got, want, atol=1e-6)
        # and similarity_bank's default engine IS this scorer
        np.testing.assert_array_equal(
            got, np.asarray(similarity_bank(x, bank, band=band),
                            np.float32))


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_score_bank_many_ragged_equals_per_query_solve(seed):
    """Property: J ragged queries scored in one batched dispatch equal
    each query's own single-dispatch solve BITWISE (per-cell arithmetic
    never sees the batch), and the host reference to 1e-6 on dyadic
    data."""
    rng = np.random.default_rng(seed)
    series, bank = _dyadic_bank(rng, int(rng.integers(3, 8)))
    j = int(rng.integers(2, 5))
    xlens = rng.integers(4, 24, size=j).astype(np.int32)
    xs = np.zeros((j, int(xlens.max())), np.float32)
    for i, l in enumerate(xlens):
        xs[i, :l] = _dyadic_series(rng, int(l))
    band = None if seed % 2 == 0 else 5
    got = np.asarray(dtw.dtw_score_bank_many(
        xs, bank.series, bank.lengths, xlens=xlens, band=band,
        use_kernel=False))
    for i in range(j):
        one = np.asarray(dtw.dtw_score_bank(
            xs[i, :xlens[i]], bank.series, bank.lengths, band=band,
            use_kernel=False))
        np.testing.assert_array_equal(got[i], one)
        want = similarity_bank(xs[i, :xlens[i]], bank, band=band,
                               matrix_path=True)
        np.testing.assert_allclose(got[i], want, atol=1e-6)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_score_pairs_equals_scalar_similarity_on_dyadic(seed):
    """Property: the pairs scorer (ragged both sides, banded) equals the
    scalar ``similarity`` pipeline on dyadic-grid pairs."""
    rng = np.random.default_rng(seed)
    p = int(rng.integers(2, 6))
    qs = [_dyadic_series(rng, int(rng.integers(6, 24))) for _ in range(p)]
    rs = [_dyadic_series(rng, int(rng.integers(6, 24))) for _ in range(p)]
    qb, rb = pack_series(qs), pack_series(rs)
    for band in (None, 4):
        got = np.asarray(dtw.dtw_score_pairs(
            qb.series, rb.series, qb.lengths, rb.lengths, band=band))
        want = np.array([similarity(qs[i], rs[i], band=band)
                         for i in range(p)])
        np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128),
                                          (None, 4), (6, 4)])
def test_offline_kernel_bitwise_vs_jnp_wavefront(band, block_k):
    """The Pallas offline kernel (interpret mode) == the jnp wavefront
    scorer BITWISE — scores and endpoint distances — on dyadic-grid
    ragged banks and ragged queries, including a block_k that forces
    reference-tile padding."""
    rng = np.random.default_rng(7 if band is None else band + block_k)
    series, bank = _dyadic_bank(rng, 7)
    j = 3
    xlens = np.asarray([21, 9, 16], np.int32)
    xs = np.zeros((j, 24), np.float32)
    for i, l in enumerate(xlens):
        xs[i, :l] = _dyadic_series(rng, int(l))
    jn = dtw.dtw_score_bank_many(xs, bank.series, bank.lengths,
                                 xlens=xlens, band=band, use_kernel=False,
                                 return_distances=True)
    from repro.kernels.dtw import score_bank_offline_kernel
    folds = [dtw.query_moments(xs[i, :xlens[i]]) for i in range(j)]
    kr = score_bank_offline_kernel(
        xs, xlens, bank.series, bank.lengths,
        np.asarray([f[0] for f in folds], np.float32),
        np.asarray([f[1] for f in folds], np.float32),
        band=band, block_k=block_k, interpret=True)
    np.testing.assert_array_equal(np.asarray(jn[0]), np.asarray(kr[0]))
    np.testing.assert_array_equal(np.asarray(jn[1]), np.asarray(kr[1]))


def test_scorer_distances_equal_distance_bank_bitwise():
    """The scorer's endpoint distances are the SAME wavefront arithmetic
    as ``dtw_distance_bank`` — bitwise equal even on continuous data."""
    rng = np.random.default_rng(3)
    series = [rng.random(int(rng.integers(12, 40))).astype(np.float32)
              for _ in range(9)]
    bank = pack_series(series)
    x = rng.random(31).astype(np.float32)
    for band in (None, 6):
        _, dists = dtw.dtw_score_bank(x, bank.series, bank.lengths,
                                      band=band, use_kernel=False,
                                      return_distances=True)
        want = np.asarray(dtw.dtw_distance_bank(
            x, bank.series, bank.lengths, band=band))
        np.testing.assert_array_equal(np.asarray(dists), want)


def test_score_bank_smooth_data_tolerance():
    """On continuous-noise data the scorer tracks the host backtrack to
    warp-path-tie tolerance (same contract as the streaming kernel's
    host comparison)."""
    rng = np.random.default_rng(11)
    series = []
    for i in range(8):
        l = int(rng.integers(30, 70))
        t = np.linspace(0, 1, l, dtype=np.float32)
        series.append(np.clip(
            0.5 + 0.3 * np.sin(2 * np.pi * (1.5 + i) * t)
            + 0.05 * rng.normal(size=l), 0, 1).astype(np.float32))
    bank = pack_series(series)
    x = np.clip(0.5 + 0.3 * np.sin(np.linspace(0, 9, 48)), 0, 1) \
        .astype(np.float32)
    got = np.asarray(dtw.dtw_score_bank(x, bank.series, bank.lengths,
                                        use_kernel=False))
    want = similarity_bank(x, bank, matrix_path=True)
    np.testing.assert_allclose(got, want, atol=5e-3)


def test_empty_and_degenerate_banks():
    assert dtw.dtw_score_bank_many(
        np.zeros((2, 8), np.float32), np.zeros((0, 8), np.float32),
        np.zeros((0,), np.int32)).shape == (2, 0)
    # constant query vs constant identical reference -> 1.0, constant
    # different reference -> 0.0 (RunningMoments' degenerate convention)
    x = np.full((12,), 0.25, np.float32)
    bank = pack_series([np.full((9,), 0.25, np.float32),
                        np.full((15,), 0.75, np.float32)])
    got = np.asarray(dtw.dtw_score_bank(x, bank.series, bank.lengths,
                                        use_kernel=False))
    np.testing.assert_allclose(got, [1.0, 0.0], atol=1e-6)


def test_table1_golden_relock_through_matrix_free_engine():
    """Golden re-lock: the rewired (matrix-free) batched engine
    reproduces the committed Table-1 similarity matrix within the golden
    tolerance — the offline rewiring moved no paper-facing number.  (The
    golden file itself is produced by the scalar pipeline, which is
    untouched; this pins the REWIRED path against it.)"""
    from repro import mrsim
    from repro.core import filters

    path = os.path.join(os.path.dirname(__file__), "golden",
                        "table1_similarity.json")
    with open(path) as f:
        golden = json.load(f)
    psets = mrsim.paper_param_sets()
    queries = [mrsim.simulate_cpu_series(golden["query_app"], p,
                                         run=golden["query_run"])
               for p in psets]
    band = golden["band"]
    for app, want in golden["similarity"].items():
        refs = pack_series([np.asarray(filters.preprocess(np.asarray(
            mrsim.simulate_cpu_series(app, p), np.float32)))
            for p in psets])
        got = np.stack([similarity_bank(
            np.asarray(filters.preprocess(np.asarray(q, np.float32))),
            refs, band=band) for q in queries], axis=1)   # [ref i, query j]
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-3)


def test_score_plan_is_memoized_per_bank():
    """The finish-path double-pack fix: one SeriesBank builds its tiled
    device upload exactly once (whatever mix of similarity_bank /
    finish / match calls reuse it), and a DB-cached bank therefore
    shares one plan across verdicts.  A replace()d bank starts fresh."""
    import dataclasses

    from repro.core.database import ReferenceDB

    rng = np.random.default_rng(5)
    db = ReferenceDB()
    for i in range(5):
        db.add(f"w{i}", {"i": i}, rng.random(20 + i).astype(np.float32))
    bank = db.bank()
    plan = bank.score_plan()
    assert bank.score_plan() is plan                  # memoized
    assert db.bank().score_plan() is plan             # DB bank cache too
    assert plan.k == len(bank)
    # scoring through the plan == scoring without it
    x = rng.random(17).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(dtw.dtw_score_bank(x, bank.series, bank.lengths,
                                      plan=plan, use_kernel=False)),
        np.asarray(dtw.dtw_score_bank(x, bank.series, bank.lengths,
                                      use_kernel=False)))
    fresh = dataclasses.replace(bank)
    assert fresh._score_plan is None                  # no stale carry


def test_preprocessed_bank_is_memoized():
    """preprocess=True scoring must not rebuild/re-upload the bank per
    call: the filtered pack (and therefore its score plan) is memoized
    on the source SeriesBank."""
    rng = np.random.default_rng(9)
    bank = pack_series([rng.random(int(rng.integers(16, 40)))
                        .astype(np.float32) for _ in range(5)])
    pb = bank.preprocessed()
    assert bank.preprocessed() is pb
    plan = pb.score_plan()
    x = rng.random(20).astype(np.float32)
    a = similarity_bank(x, bank, preprocess=True, band=4)
    b = similarity_bank(x, bank, preprocess=True, band=4)
    np.testing.assert_array_equal(a, b)
    assert bank.preprocessed().score_plan() is plan   # no re-upload


def test_final_scores_banded_misprediction_without_rows():
    """collect_rows=False + banded stream whose query_len prediction was
    wrong: final_scores self-corrects via the matrix-free solve (corridor
    re-derived from the true length == offline similarity_bank) instead
    of crashing on the missing rows."""
    from repro.core import OnlineMatcher

    rng = np.random.default_rng(13)
    bank = pack_series([np.clip(rng.normal(0.5, 0.2, 40), 0, 1)
                        .astype(np.float32) for _ in range(4)])
    q = np.clip(rng.normal(0.5, 0.2, 30), 0, 1).astype(np.float32)
    om = OnlineMatcher(bank, band=6, query_len=50, collect_rows=False)
    om.extend(q)                          # stream ends early: n=30 != 50
    got = om.final_scores()
    want = similarity_bank(q, bank, band=6)
    np.testing.assert_array_equal(got, want)


def test_distance_only_prefilter_mode_is_guarded():
    """Satellite guard: a distance-only service (score_in_flight=False)
    with prefilter_top set would prune on the wavelet ranking ALONE —
    no in-flight DTW veto — which evicts warp-matching references.  The
    construction must refuse."""
    from repro.serve.tuning import TuningService

    rng = np.random.default_rng(0)
    bank = pack_series([rng.random(32).astype(np.float32)
                        for _ in range(4)])
    with pytest.raises(ValueError, match="score_in_flight"):
        TuningService(bank, score_in_flight=False, prefilter_top=2)


def test_pure_wavelet_pruning_would_evict_warp_match():
    """WHY the guard exists: on the paper's exim trace the warp-matching
    wordcount references rank so poorly in the rigid wavelet domain that
    a pure-wavelet top-P prune (no DTW veto) evicts every one of them —
    the reference family the full pipeline ultimately matches."""
    from repro import mrsim
    from repro.core import filters, wavelet
    from repro.core.database import SeriesBank
    from repro.serve.tuning import TuningService

    psets = mrsim.paper_param_sets()
    series, labels = [], []
    for app in sorted(mrsim.APPS):
        for p in psets:
            series.append(mrsim.simulate_cpu_series(app, p, dt=0.25))
            labels.append(app)
    packed = pack_series(series, labels=labels)
    bank = SeriesBank(np.asarray(filters.preprocess_bank(
        packed.series, packed.lengths)), packed.lengths, packed.labels)

    svc = TuningService(bank, band=16, denoise=True, prefilter_top=2,
                        prefilter_min_fraction=0.1)
    p = psets[0]
    q = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc.submit("exim", expected_len=len(q))
    half = len(q) // 2
    for lo in range(0, half, 8):
        svc.push("exim", q[lo: lo + 8])
        svc.tick()
    job = svc._jobs["exim"]
    # the vetoed (real) prune keeps at least one wordcount reference live
    assert job.allowed is not None
    labels_arr = np.asarray(bank.labels)
    assert job.allowed[labels_arr == "wordcount"].any()
    # ...but the PURE-WAVELET top-P ranking alone (what a distance-only
    # service would have pruned on) evicts every wordcount reference:
    wkeep = TuningService._top_p_with_margin(
        wavelet.coeff_similarity_bank(
            job.haar.compressed(svc.prefilter_coeffs),
            svc._ref_prefix_coeffs(job.haar.size, job.n)),
        np.ones(len(bank), bool), svc.prefilter_top,
        svc.prefilter_margin)
    assert not wkeep[labels_arr == "wordcount"].any(), \
        "wavelet ranking unexpectedly kept wordcount - guard test stale"
    # verdict sanity: the full pipeline does match wordcount
    for lo in range(half, len(q), 8):
        svc.push("exim", q[lo: lo + 8])
        svc.tick()
    assert svc.finish("exim").matched == "wordcount"
