"""Streaming prefix-DTW matching stack.

The tentpole invariants:

* carrying the DP state across arriving chunks reproduces the one-shot
  batched solve EXACTLY, for any chunking, ragged and banded banks alike;
* prefix (open-end) distances are monotone in information — more samples
  never destroy evidence, so early pruning is sound and no prefix can
  certify an exact match for a reference the complete series rejects;
* once the series completes, the streamed score IS the offline
  ``similarity_bank`` score;
* a multi-job service tick is ONE device dispatch, however many jobs are
  in flight.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import mrsim
from repro.core import (OnlineMatcher, StreamingFilter, dtw, similarity_bank)
from repro.core.database import pack_series
from repro.core.filters import cheby1_design, lfilter
from repro.core.similarity import prefix_similarity_bank
from repro.serve.tuning import TuningService


def _random_chunks(rng, x):
    """Split x into random-size chunks (including size-1 and large)."""
    chunks = []
    lo = 0
    while lo < len(x):
        c = int(rng.integers(1, max(2, len(x) // 2)))
        chunks.append(x[lo: lo + c])
        lo += c
    return chunks


def _stream(x, bank, rng, band=None):
    st_ = dtw.dtw_bank_init(bank.series, bank.lengths, band=band,
                            query_len=len(x))
    for chunk in _random_chunks(rng, x):
        st_, _ = dtw.dtw_bank_extend(st_, chunk)
    return st_


# ---------------------------------------------------------------------------
# Property: any chunking == one-shot (ragged + banded)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_streaming_equals_oneshot_any_chunking(seed):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(3, 40, size=int(rng.integers(2, 7)))
    series = [rng.normal(size=int(l)).astype(np.float32) for l in lengths]
    bank = pack_series(series)
    x = rng.normal(size=int(rng.integers(2, 48))).astype(np.float32)

    got = np.asarray(_stream(x, bank, rng).distances())
    want = np.asarray(dtw.dtw_distance_bank(x, bank.series, bank.lengths))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_streaming_equals_oneshot_banded_any_chunking(seed):
    rng = np.random.default_rng(seed ^ 0x5EED)
    lengths = rng.integers(3, 40, size=int(rng.integers(2, 7)))
    series = [rng.normal(size=int(l)).astype(np.float32) for l in lengths]
    bank = pack_series(series)
    # n and band keep the Sakoe-Chiba corridor connected (per-row center
    # jump < band) — with a disconnected corridor the distance is the
    # +inf-saturated sentinel, where the two formulations may saturate
    # differently and comparison is meaningless.
    x = rng.normal(size=int(rng.integers(16, 48))).astype(np.float32)
    band = int(rng.integers(6, 10))

    got = np.asarray(_stream(x, bank, rng, band=band).distances())
    want = np.asarray(dtw.dtw_distance_bank(x, bank.series, bank.lengths,
                                            band=band))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_prefix_distances_monotone_in_information(seed):
    """Open-end prefix distances never decrease as samples arrive: every
    longer-prefix alignment extends a shorter one with non-negative cost.
    Corollary (tested below): no prefix can undercut the final distance,
    so a workload the complete series rejects can never be exact-matched
    from a prefix."""
    rng = np.random.default_rng(seed ^ 0xD15C0)
    series = [rng.normal(size=int(l)).astype(np.float32)
              for l in rng.integers(4, 30, size=4)]
    bank = pack_series(series)
    x = rng.normal(size=40).astype(np.float32)

    st_ = dtw.dtw_bank_init(bank.series, bank.lengths)
    prev = np.zeros((len(series),))
    history = []
    for chunk in _random_chunks(rng, x):
        st_, _ = dtw.dtw_bank_extend(st_, chunk)
        cur = np.asarray(st_.prefix_distances())
        assert (cur >= prev - 1e-4).all(), "prefix distance decreased"
        history.append(cur)
        prev = cur
    final = history[-1]
    for cur in history:          # no prefix undercuts the final evidence
        assert (cur <= final + 1e-4).all()


def test_prefix_exact_match_soundness():
    """A reference the full series rejects (positive final open-end
    distance) is never reported as an exact (zero-distance) match once any
    evidence against it has accumulated — monotonicity makes the early
    exact-match claim one-way."""
    y = np.linspace(0.0, 1.0, 24, dtype=np.float32)
    bank = pack_series([y])
    # query tracks y for 12 samples then diverges hard
    x = np.concatenate([y[:12], np.full(12, 5.0, np.float32)])

    st_ = dtw.dtw_bank_init(bank.series, bank.lengths)
    st_, _ = dtw.dtw_bank_extend(st_, x[:12])
    assert float(st_.prefix_distances()[0]) == pytest.approx(0.0, abs=1e-6)
    st_, _ = dtw.dtw_bank_extend(st_, x[12:])
    rejected_at = float(st_.prefix_distances()[0])
    assert rejected_at > 1.0
    # further samples can only pile on: streaming more of the divergent
    # tail never resurrects the exact match
    st_, _ = dtw.dtw_bank_extend(st_, np.full(6, 5.0, np.float32))
    assert float(st_.prefix_distances()[0]) >= rejected_at - 1e-4


# ---------------------------------------------------------------------------
# Rows / scoring layer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wave_set():
    rng = np.random.default_rng(7)
    series = [np.clip(0.5 + 0.3 * np.sin(np.linspace(0, 5 + i, l))
                      + 0.05 * rng.normal(size=l), 0, 1).astype(np.float32)
              for i, l in enumerate((50, 80, 65))]
    x = np.clip(0.5 + 0.3 * np.sin(np.linspace(0, 6, 70))
                + 0.05 * rng.normal(size=70), 0, 1).astype(np.float32)
    return x, pack_series(series)


def test_collected_rows_match_matrix_bank(wave_set):
    x, bank = wave_set
    st_ = dtw.dtw_bank_init(bank.series, bank.lengths)
    rows = []
    for lo in range(0, len(x), 9):
        st_, r = dtw.dtw_bank_extend(st_, x[lo: lo + 9], collect_rows=True)
        rows.append(np.asarray(r))
    D = np.concatenate(rows).transpose(1, 0, 2)
    want = np.asarray(dtw.dtw_matrix_bank(x, bank.series, bank.lengths))
    np.testing.assert_allclose(D, want, rtol=1e-4, atol=1e-4)


def test_streamed_final_score_equals_offline(wave_set):
    x, bank = wave_set
    om = OnlineMatcher(bank)
    for lo in range(0, len(x), 13):
        om.extend(x[lo: lo + 13])
    np.testing.assert_allclose(om.final_scores(), similarity_bank(x, bank),
                               rtol=1e-4, atol=1e-4)


def test_streamed_final_score_equals_offline_banded(wave_set):
    x, bank = wave_set
    om = OnlineMatcher(bank, band=6, query_len=len(x))
    for lo in range(0, len(x), 7):
        om.extend(x[lo: lo + 7])
    np.testing.assert_allclose(om.final_scores(),
                               similarity_bank(x, bank, band=6),
                               rtol=1e-4, atol=1e-4)


def test_prefix_scores_need_collected_rows(wave_set):
    x, bank = wave_set
    om = OnlineMatcher(bank, collect_rows=False)
    om.extend(x[:16])
    with pytest.raises(ValueError, match="collect_rows"):
        om.prefix_scores()
    assert om.distances().shape == (len(bank),)


def test_prefix_similarity_rejects_row_mismatch(wave_set):
    x, bank = wave_set
    om = OnlineMatcher(bank)
    om.extend(x[:16])
    with pytest.raises(ValueError, match="rows"):
        prefix_similarity_bank(x[:10], bank, om._rows.view())


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_running_moments_match_two_pass_correlation(seed):
    """RunningMoments (single-pass, chunked) must agree with the offline
    two-pass correlation() it stands in for — pins the two implementations
    together so they can't drift apart."""
    from repro.core.similarity import RunningMoments, correlation

    rng = np.random.default_rng(seed ^ 0xC022)
    n = int(rng.integers(2, 200))
    x = rng.normal(size=n)
    y = 0.4 * x + rng.normal(size=n)
    rm = RunningMoments()
    lo = 0
    while lo < n:
        c = int(rng.integers(1, n + 1))
        rm.update(x[lo: lo + c], y[lo: lo + c])
        lo += c
    want = float(np.clip(correlation(x, y), -1.0, 1.0))
    assert rm.corr == pytest.approx(want, abs=1e-9)


def test_running_moments_degenerate_conventions():
    from repro.core.similarity import RunningMoments, correlation

    ones = np.ones(10)
    assert RunningMoments().update(ones, ones).corr == 1.0 \
        == correlation(ones, ones)
    assert RunningMoments().update(ones, 2 * ones).corr == 0.0 \
        == correlation(ones, 2 * ones)
    assert RunningMoments().corr == 0.0


def test_streaming_filter_chunking_invariant():
    rng = np.random.default_rng(3)
    x = rng.normal(size=200).astype(np.float32)
    b, a = cheby1_design(6, 1.0, 0.125)
    want = np.asarray(lfilter(b, a, x))
    for chunks in ((200,), (1, 199), (7, 64, 129), (50, 50, 50, 50)):
        sf = StreamingFilter()
        got = np.concatenate([sf(c) for c in np.split(x, np.cumsum(chunks))[:-1]])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_iter_cpu_series_concatenates_to_simulate():
    p = mrsim.paper_param_sets()[0]
    want = mrsim.simulate_cpu_series("terasort", p, run=2)
    got = np.concatenate(list(mrsim.iter_cpu_series("terasort", p, run=2,
                                                    chunk=7)))
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError):
        next(mrsim.iter_cpu_series("terasort", p, chunk=0))


# ---------------------------------------------------------------------------
# TuningService
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paper_bank():
    """Preprocessed references, as AutoTuner.profile stores them."""
    from repro.core.database import SeriesBank
    from repro.core.filters import preprocess_bank

    psets = mrsim.paper_param_sets()
    series, labels = [], []
    for app in ("wordcount", "terasort"):
        for p in psets:
            series.append(mrsim.simulate_cpu_series(app, p, dt=0.25))
            labels.append(app)
    bank = pack_series(series, labels=labels)
    return SeriesBank(preprocess_bank(bank.series, bank.lengths),
                      bank.lengths, bank.labels, bank.entries)


def test_service_lifecycle_and_one_dispatch_per_tick(paper_bank):
    svc = TuningService(paper_bank, band=16, threshold=0.85, denoise=True,
                        slots=4, min_fraction=0.15, stable_ticks=2)
    p = mrsim.paper_param_sets()[0]
    queries = {f"job{r}": mrsim.simulate_cpu_series("exim", p, run=r,
                                                    dt=0.25)
               for r in (1, 2, 3)}
    for jid, q in queries.items():
        svc.submit(jid, expected_len=len(q))
    assert svc.n_active == 3
    with pytest.raises(ValueError):
        svc.submit("job1", expected_len=10)

    n = max(len(q) for q in queries.values())
    for lo in range(0, n, 8):
        for jid, q in queries.items():
            svc.push(jid, q[lo: lo + 8])
        svc.tick()
    assert svc.dispatch_count <= svc.ticks          # ONE dispatch per tick

    for jid in queries:
        d = svc.finish(jid)
        assert d.final and d.matched == "wordcount"
        assert d.fraction_seen == 1.0
        assert set(d.scores) == {"wordcount", "terasort"}
    assert svc.n_active == 0
    # slots were freed: a fresh submit succeeds
    svc.submit("again", expected_len=32)


def test_service_slot_exhaustion(paper_bank):
    svc = TuningService(paper_bank, slots=1)
    svc.submit("a", expected_len=8)
    with pytest.raises(RuntimeError, match="slots busy"):
        svc.submit("b", expected_len=8)


def test_service_early_decision_abstains_below_min_fraction(paper_bank):
    """The confidence rule must hold fire before min_fraction even if the
    leader is already stable and above threshold."""
    svc = TuningService(paper_bank, band=16, threshold=0.5, margin=0.0,
                        stable_ticks=1, min_fraction=0.9, denoise=True)
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc.submit("q", expected_len=len(q))
    seen = 0
    for lo in range(0, len(q) // 2, 8):             # only half the job
        svc.push("q", q[lo: lo + 8])
        decisions = svc.tick()
        seen += 1
        assert decisions.get("q") is None, "decided below min_fraction"
    assert seen > 0


def test_service_emits_early_then_final(paper_bank):
    svc = TuningService(paper_bank, band=16, threshold=0.85, margin=0.02,
                        stable_ticks=3, min_fraction=0.15, denoise=True)
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc.submit("q", expected_len=len(q))
    early = None
    for lo in range(0, len(q), 8):
        svc.push("q", q[lo: lo + 8])
        d = svc.tick().get("q")
        if d is not None and early is None:
            early = d
    assert early is not None and not early.final
    assert early.matched == "wordcount"
    assert 0.0 < early.fraction_seen < 1.0
    final = svc.finish("q")
    assert final.final and final.matched == "wordcount"


def test_service_distance_only_mode_matches_offline(paper_bank):
    """collect_rows=False: no in-flight scoring, but finish() still agrees
    with the offline batch engine."""
    svc = TuningService(paper_bank, band=16, collect_rows=False)
    p = mrsim.paper_param_sets()[1]
    q = mrsim.simulate_cpu_series("wordcount", p, run=1, dt=0.25)
    svc.submit("q", expected_len=len(q))
    svc.push("q", q)
    assert svc.tick() == {"q": None}
    d = svc.finish("q")
    off = similarity_bank(q, paper_bank, band=16)
    best = {}
    for lbl, s in zip(paper_bank.labels, off):
        best[lbl] = max(best.get(lbl, -1.0), float(s))
    assert d.scores == pytest.approx(best, abs=1e-6)


def test_service_rejects_empty_bank():
    with pytest.raises(ValueError, match="empty"):
        TuningService(pack_series([]))


def test_service_banded_finish_self_corrects_wrong_expected_len(paper_bank):
    """expected_len is a runtime *prediction*; if the job ends at a
    different length, the streamed banded corridor was misplaced — the
    final verdict must fall back to the offline solve (band re-derived
    from the true length) instead of scoring through the stale corridor."""
    p = mrsim.paper_param_sets()[1]
    q = mrsim.simulate_cpu_series("wordcount", p, run=1, dt=0.25)
    svc = TuningService(paper_bank, band=16, collect_rows=True)
    svc.submit("q", expected_len=2 * len(q))        # prediction way off
    svc.push("q", q)
    svc.tick()
    d = svc.finish("q")
    off = similarity_bank(q, paper_bank, band=16)
    best = {}
    for lbl, s in zip(paper_bank.labels, off):
        best[lbl] = max(best.get(lbl, -1.0), float(s))
    assert d.scores == pytest.approx(best, abs=1e-6)
    assert d.matched == "wordcount"


def test_finish_does_not_drop_other_jobs_decisions(paper_bank):
    """finish() drains buffers with an internal tick; an early decision
    that tick emits for a DIFFERENT job must surface from the next
    tick() instead of vanishing."""
    p = mrsim.paper_param_sets()[0]
    qa = mrsim.simulate_cpu_series("terasort", p, run=1, dt=0.25)
    qb = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc = TuningService(paper_bank, band=16, threshold=0.5, margin=0.0,
                        stable_ticks=1, min_fraction=0.1, denoise=True)
    svc.submit("ja", expected_len=len(qa))
    svc.submit("jb", expected_len=len(qb))
    # jb gets enough samples that the (deliberately lax) rule decides on
    # the very tick that finish("ja") runs internally
    svc.push("ja", qa)
    svc.push("jb", qb[: len(qb) // 2])
    svc.finish("ja")
    assert svc._jobs["jb"].early is not None       # decided internally...
    later = svc.tick()                              # ...and not lost:
    assert later.get("jb") is svc._jobs["jb"].early


def test_finish_purges_undelivered_decision_of_finished_job(paper_bank):
    """A parked early decision must not outlive its job: finishing the job
    before the next tick() removes it, so a reused job_id can never
    receive a ghost decision from its predecessor."""
    p = mrsim.paper_param_sets()[0]
    qa = mrsim.simulate_cpu_series("terasort", p, run=1, dt=0.25)
    qb = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc = TuningService(paper_bank, band=16, threshold=0.5, margin=0.0,
                        stable_ticks=1, min_fraction=0.1, denoise=True)
    svc.submit("ja", expected_len=len(qa))
    svc.submit("jb", expected_len=len(qb))
    svc.push("ja", qa)
    svc.push("jb", qb[: len(qb) // 2])
    svc.finish("ja")                   # parks jb's early decision
    assert "jb" in svc._undelivered
    svc.finish("jb")                   # jb ends before any tick()
    assert svc.tick() == {}            # no ghost delivery
    svc.submit("jb", expected_len=len(qb))      # id reuse is clean
    assert svc.tick() == {}


# ---------------------------------------------------------------------------
# Device-resident tick (wavefront extend + fused on-device scoring)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_wavefront_tick_equals_bank_extend_many(seed):
    """The K-last wavefront tick (``dtw.bank_extend_tick``) must agree
    cell-for-cell with the row-formulation reference, across random
    ragged chunkings, ragged banks, banded and unbanded."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed ^ 0xD1A6)
    series = [rng.normal(size=int(l)).astype(np.float32)
              for l in rng.integers(4, 30, size=int(rng.integers(2, 6)))]
    bank = pack_series(series)
    k, m = bank.series.shape
    J, C = int(rng.integers(1, 4)), 8
    band = int(rng.integers(6, 10)) if rng.integers(2) else None
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    rows_w = jnp.full((J, m, k), dtw._INF)
    ns_w = jnp.zeros((J,), jnp.int32)
    rows_h = jnp.full((J, k, m), dtw._INF)
    ns_h = jnp.zeros((J,), jnp.int32)
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray(rng.random((J, C)).astype(np.float32))
        rows_w, ns_w = dtw.bank_extend_tick(
            rows_w, ns_w, jnp.asarray(bank.series.T),
            jnp.asarray(bank.lengths), ch, nv, qlens, band=band)
        rows_h, ns_h, _ = dtw._bank_extend_many(
            rows_h, ns_h, jnp.asarray(bank.series),
            jnp.asarray(bank.lengths), ch, nv, qlens, band, False)
    r1 = np.asarray(rows_w).transpose(0, 2, 1)
    r2 = np.asarray(rows_h)
    finite = r2 < 1e37
    assert (finite == (r1 < 1e37)).all()
    np.testing.assert_allclose(r1[finite], r2[finite], rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ns_w), np.asarray(ns_h))


@pytest.mark.parametrize("band", [None, 9])
def test_fused_device_scores_match_host_prefix_scoring(band):
    """The on-device warp-path-moment scores of the fused tick reproduce
    the host backtrack scorer (``prefix_similarity_bank`` over collected
    rows) at every tick — the tentpole claim that moving scoring
    on-device costs no fidelity."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3 if band is None else band)
    series = []
    for i in range(5):
        l = int(rng.integers(16, 40))
        t = np.linspace(0, 1, l, dtype=np.float32)
        series.append(np.clip(
            0.5 + 0.3 * np.sin(2 * np.pi * (1.5 + i) * t)
            + 0.05 * rng.normal(size=l), 0, 1).astype(np.float32))
    bank = pack_series(series)
    k, m = bank.series.shape
    J, C, nticks = 2, 8, 4
    qlen = nticks * C
    qs = np.stack([np.clip(
        0.5 + 0.3 * np.sin(2 * np.pi * (2 + j) * np.linspace(0, 1, qlen))
        + 0.05 * rng.normal(size=qlen), 0, 1).astype(np.float32)
        for j in range(J)])
    rows = jnp.full((J, m, k), dtw._INF)
    moms = jnp.zeros((3, J, m, k))
    ns = jnp.zeros((J,), jnp.int32)
    sx = jnp.zeros((J,))
    sxx = jnp.zeros((J,))
    qlens = jnp.full((J,), qlen, jnp.int32)
    rows_h = jnp.full((J, k, m), dtw._INF)
    ns_h = jnp.zeros((J,), jnp.int32)
    collected = []
    for t0 in range(nticks):
        ch = jnp.asarray(qs[:, t0 * C:(t0 + 1) * C])
        nv = jnp.full((J,), C, jnp.int32)
        rows, moms, ns, sx, sxx, scores = dtw.bank_extend_tick_scored(
            rows, moms, ns, sx, sxx, jnp.asarray(bank.series.T),
            jnp.asarray(bank.lengths), ch, nv, qlens, band=band)
        rows_h, ns_h, coll = dtw._bank_extend_many(
            rows_h, ns_h, jnp.asarray(bank.series),
            jnp.asarray(bank.lengths), ch, nv, qlens, band, True)
        collected.append(np.asarray(coll))
        stack = np.concatenate(collected)
        dev = np.asarray(scores)
        for j in range(J):
            host = prefix_similarity_bank(qs[j, :(t0 + 1) * C], bank,
                                          stack[:, j])
            np.testing.assert_allclose(dev[j], host, atol=2e-3)


def test_service_margin_needs_two_workloads(paper_bank):
    """A single-workload bank has no runner-up, so the margin gate must
    not pass vacuously: the service abstains in flight (finish() still
    delivers the final verdict)."""
    from repro.core.database import SeriesBank

    rows = [i for i, lbl in enumerate(paper_bank.labels)
            if lbl == "wordcount"]
    solo = SeriesBank(paper_bank.series[rows], paper_bank.lengths[rows],
                      tuple(paper_bank.labels[i] for i in rows))
    # deliberately lax rule: threshold/margin/stability would all pass
    # trivially if the vacuous runner-up (-1.0) were allowed
    svc = TuningService(solo, band=16, threshold=0.3, margin=0.0,
                        stable_ticks=1, min_fraction=0.05, denoise=True)
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("wordcount", p, run=1, dt=0.25)
    svc.submit("q", expected_len=len(q))
    for lo in range(0, len(q), 8):
        svc.push("q", q[lo: lo + 8])
        assert svc.tick().get("q") is None, \
            "early decision from a single-workload bank"
    final = svc.finish("q")
    assert final.final and final.matched == "wordcount"


def test_service_scoring_tick_moves_no_rows(paper_bank):
    """The scoring tick's device->host traffic is the [S, K] score array:
    the job objects hold no DP-row history any more (finish() recomputes
    offline instead)."""
    svc = TuningService(paper_bank, band=16, denoise=True)
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc.submit("q", expected_len=len(q))
    svc.push("q", q[:32])
    svc.tick()
    job = svc._jobs["q"]
    assert not hasattr(job, "rows")
    assert job.last_sims is not None
    assert job.last_sims.shape == (len(paper_bank),)
    assert svc.dispatch_count == 1
    d = svc.finish("q")
    assert svc.offline_dispatch_count == 1 and svc.dispatch_count == 1
    assert set(d.scores) == {"wordcount", "terasort"}


@pytest.fixture(scope="module")
def golden_bank():
    """All three mrsim apps x paper param sets — the golden-trace bank
    the pruned-vs-unpruned decision property runs against."""
    from repro.core.database import SeriesBank
    from repro.core.filters import preprocess_bank

    psets = mrsim.paper_param_sets()
    series, labels = [], []
    for app in mrsim.APPS:
        for p in psets:
            series.append(mrsim.simulate_cpu_series(app, p, dt=0.25))
            labels.append(app)
    bank = pack_series(series, labels=labels)
    return SeriesBank(preprocess_bank(bank.series, bank.lengths),
                      bank.lengths, bank.labels, bank.entries)


@pytest.mark.parametrize("app", sorted(mrsim.APPS))
def test_pruned_tick_decisions_equal_unpruned_on_golden_traces(
        golden_bank, app):
    """Property: with the streaming wavelet prefilter pruning the bank,
    every in-flight decision (matched workload, correlation,
    decided_at_fraction) and the final verdict equal the unpruned
    service's, tick for tick, on the golden exim/wordcount/terasort
    traces — the prefilter's soundness-margin contract."""
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series(app, p, run=1, dt=0.25)
    runs = []
    for pf in (None, 4):
        svc = TuningService(golden_bank, band=16, threshold=0.85,
                            margin=0.02, stable_ticks=3, min_fraction=0.15,
                            denoise=True, prefilter_top=pf)
        svc.submit(app, expected_len=len(q))
        seq = []
        for lo in range(0, len(q), 8):
            svc.push(app, q[lo: lo + 8])
            d = svc.tick().get(app)
            seq.append(None if d is None else
                       (d.matched, d.corr, d.decided_at_fraction))
        final = svc.finish(app)
        assert svc.dispatch_count == svc.ticks, \
            "pruning must not change the one-dispatch-per-tick invariant"
        runs.append((seq, final))
    (seq_u, fin_u), (seq_p, fin_p) = runs
    assert seq_p == seq_u
    assert fin_p.matched == fin_u.matched
    assert fin_p.corr == pytest.approx(fin_u.corr, abs=1e-12)
    assert fin_p.decided_at_fraction == fin_u.decided_at_fraction


def _diverse_bank(rng, k, min_len=64):
    series = []
    for i in range(k):
        l = int(rng.integers(min_len, min_len + 40))
        t = np.linspace(0, 1, l, dtype=np.float32)
        s = (0.5 + 0.28 * np.sin(2 * np.pi * (1.5 + 0.3 * i) * t + 0.7 * i)
             + 0.06 * rng.normal(size=l).astype(np.float32))
        series.append(np.clip(s, 0, 1).astype(np.float32))
    return pack_series(series)


def test_prefilter_repack_accounting_and_dispatch_invariant():
    """Re-packs are counted separately and never inflate dispatch_count:
    dispatches == data-carrying ticks holds through prune-driven shrinks
    AND the re-grow when a fresh job re-widens the survivor union."""
    rng = np.random.default_rng(42)
    bank = _diverse_bank(rng, 24)
    qlen = 64
    svc = TuningService(bank, prefilter_top=2, prefilter_margin=0.0,
                        prefilter_min_fraction=0.1, slots=4)
    for j in range(2):
        svc.submit(f"job{j}", expected_len=qlen)
    qs = np.stack([np.clip(bank.row(7 * j)[:qlen]
                           + 0.04 * rng.normal(size=qlen), 0, 1)
                   .astype(np.float32) for j in range(2)])
    data_ticks = 0
    for lo in range(0, qlen, 8):
        for j in range(2):
            svc.push(f"job{j}", qs[j, lo: lo + 8])
        svc.tick()
        data_ticks += 1
    assert svc.dispatch_count == data_ticks == svc.ticks
    shrink_repacks = svc.repack_count
    assert shrink_repacks >= 1, "prune never re-packed the device state"
    assert len(svc._packed_idx) < len(bank)
    # an empty tick moves nothing: no dispatch, no re-pack
    svc.tick()
    assert svc.dispatch_count == data_ticks
    assert svc.repack_count == shrink_repacks
    # pruned-for-this-job references surface as -inf, never a leader
    for j in range(2):
        job = svc._jobs[f"job{j}"]
        assert job.allowed is not None and not job.allowed.all()
        assert np.isneginf(job.last_sims[~job.allowed]).all()
        assert np.isfinite(job.last_sims[int(np.argmax(job.last_sims))])
    for j in range(2):
        svc.finish(f"job{j}")
    # a fresh job needs the whole bank again: the next data tick re-grows
    # the pack (one more re-pack, still one dispatch per data tick)
    svc.submit("fresh", expected_len=qlen)
    svc.push("fresh", qs[0, :8])
    svc.tick()
    assert len(svc._packed_idx) == len(bank)
    assert svc.repack_count == shrink_repacks + 1
    assert svc.dispatch_count == data_ticks + 1


def test_service_decision_history_recorded(paper_bank):
    """A DB-backed service records finished decisions (with
    decided_at_fraction) into the ReferenceDB history."""
    from repro.core import ReferenceDB

    db = ReferenceDB()
    for i, lbl in enumerate(paper_bank.labels):
        db.add(lbl, {"i": i}, paper_bank.row(i))
    svc = TuningService(db, band=16, threshold=0.85, margin=0.02,
                        stable_ticks=3, min_fraction=0.15, denoise=True)
    p = mrsim.paper_param_sets()[0]
    q = mrsim.simulate_cpu_series("exim", p, run=1, dt=0.25)
    svc.submit("exim", expected_len=len(q))
    early = None
    for lo in range(0, len(q), 8):
        svc.push("exim", q[lo: lo + 8])
        d = svc.tick().get("exim")
        if d is not None and early is None:
            early = d
    final = svc.finish("exim")
    assert early is not None
    assert early.decided_at_fraction == pytest.approx(early.fraction_seen)
    assert final.decided_at_fraction == pytest.approx(
        early.decided_at_fraction)
    hist = db.decision_history(matched="wordcount")
    assert len(hist) == 1 and hist[0]["workload"] == "exim"
    fracs = db.decided_at_fractions("wordcount")
    assert fracs == [pytest.approx(early.decided_at_fraction)]
