"""Per-kernel shape/dtype sweeps vs ref.py oracles (interpret mode)."""
import numpy as np
import pytest

from repro.kernels.dtw import dtw_batched, dtw_matrix_ref
from repro.kernels.iir import lfilter_batched, lfilter_ref
from repro.kernels.attention import flash_attention, attention_ref
from repro.kernels.gla import gla_scan, gla_ref
from repro.core.filters import cheby1_design


@pytest.mark.parametrize("n,m,k", [(16, 16, 1), (33, 57, 3), (64, 40, 2),
                                   (8, 128, 4)])
def test_dtw_kernel_vs_ref(n, m, k):
    rng = np.random.default_rng(n * m + k)
    x = rng.normal(size=n).astype(np.float32)
    ys = rng.normal(size=(k, m)).astype(np.float32)
    D = np.asarray(dtw_batched(x, ys))
    for i in range(k):
        np.testing.assert_allclose(D[i], dtw_matrix_ref(x, ys[i]),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("order,cutoff,B,T", [(6, 0.125, 3, 100),
                                              (4, 0.3, 130, 64),
                                              (2, 0.5, 1, 257)])
def test_iir_kernel_vs_ref(order, cutoff, B, T):
    b, a = cheby1_design(order, 1.0, cutoff)
    x = np.random.default_rng(B * T).normal(size=(B, T)).astype(np.float32)
    y = np.asarray(lfilter_batched(b, a, x))
    yr = lfilter_ref(b, a, x)
    np.testing.assert_allclose(y, yr, atol=5e-3)


@pytest.mark.parametrize("B,H,KV,S,dh,bq,bk,dtype", [
    (1, 2, 2, 128, 32, 64, 64, np.float32),
    (2, 4, 2, 256, 32, 128, 128, np.float32),
    (1, 8, 1, 128, 64, 32, 64, np.float32),
    (2, 4, 4, 128, 16, 64, 32, "bfloat16"),
])
def test_flash_attention_vs_ref(B, H, KV, S, dh, bq, bk, dtype):
    import jax.numpy as jnp
    rng = np.random.default_rng(S + H)
    q = rng.normal(size=(B, H, S, dh)).astype(np.float32)
    k = rng.normal(size=(B, KV, S, dh)).astype(np.float32)
    v = rng.normal(size=(B, KV, S, dh)).astype(np.float32)
    if dtype == "bfloat16":
        q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v))
        tol = 5e-2
    else:
        tol = 1e-5
    o = np.asarray(flash_attention(q, k, v, bq=bq, bk=bk),
                   np.float32)
    r = attention_ref(np.asarray(q, np.float32), np.asarray(k, np.float32),
                      np.asarray(v, np.float32))
    np.testing.assert_allclose(o, r, rtol=tol, atol=tol)


def test_flash_attention_non_causal():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(1, 2, 64, 16)).astype(np.float32)
    k = rng.normal(size=(1, 2, 64, 16)).astype(np.float32)
    v = rng.normal(size=(1, 2, 64, 16)).astype(np.float32)
    o = np.asarray(flash_attention(q, k, v, bq=32, bk=32, causal=False))
    r = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(o, r, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,H,S,dk,dv,chunk", [
    (1, 2, 32, 8, 8, 8), (2, 3, 64, 16, 8, 16), (1, 1, 128, 64, 64, 32),
])
def test_gla_kernel_vs_ref(B, H, S, dk, dv, chunk):
    rng = np.random.default_rng(S + dk)
    q = rng.normal(size=(B, H, S, dk)).astype(np.float32)
    k = (rng.normal(size=(B, H, S, dk)) * 0.3).astype(np.float32)
    v = rng.normal(size=(B, H, S, dv)).astype(np.float32)
    log_a = -np.abs(rng.normal(size=(B, H, S)) * 0.2).astype(np.float32)
    o, s = gla_scan(q, k, v, log_a, chunk=chunk)
    orf, srf = gla_ref(q, k, v, log_a)
    np.testing.assert_allclose(np.asarray(o), orf, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), srf, rtol=1e-3, atol=1e-4)


def test_gla_kernel_matches_model_path():
    """Kernel and the jnp gla_chunked used by the models agree."""
    import jax.numpy as jnp
    from repro.models.ssm import gla_chunked
    rng = np.random.default_rng(7)
    q = rng.normal(size=(1, 2, 64, 8)).astype(np.float32)
    k = rng.normal(size=(1, 2, 64, 8)).astype(np.float32)
    v = rng.normal(size=(1, 2, 64, 4)).astype(np.float32)
    log_a = -np.abs(rng.normal(size=(1, 2, 64)) * 0.1).astype(np.float32)
    o1, s1 = gla_scan(q, k, v, log_a, chunk=16)
    o2, s2 = gla_chunked(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.asarray(log_a), 16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-5)


def _dyadic_series(rng, n, denom=8, hi=9):
    """Series on a coarse dyadic grid: every DTW cost, path sum and
    correlation-moment sum is exactly representable in f32, so the scan,
    wavefront and Pallas formulations produce BIT-IDENTICAL cells and the
    warp-path predecessor argmin has no float-noise tie ambiguity —
    cell-by-cell equivalence can be asserted exactly instead of modulo
    tie-flip propagation."""
    return (rng.integers(0, hi, n) / float(denom)).astype(np.float32)


@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128), (None, 4),
                                          (6, 4)])
def test_stream_scored_kernel_cell_by_cell(band, block_k):
    """Moment-carrying Pallas streaming kernel == the jnp scored wavefront
    (`bank_extend_tick_scored`) AND the row-formulation reference
    (`_bank_extend_many`) on every cell — DP rows, (sy, syy, sxy) moment
    slabs and open-end scores alike — across ragged reference banks,
    Sakoe-Chiba bands, ragged per-job chunks (including empty ones) and a
    block_k that forces reference-tile padding.  Exact comparison on
    dyadic-grid data (see `_dyadic_series`)."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw
    from repro.core.database import pack_series

    rng = np.random.default_rng(11 if band is None else band + block_k)
    series = [_dyadic_series(rng, int(rng.integers(12, 30)))
              for _ in range(7)]
    bank = pack_series(series)
    k, m = bank.series.shape
    J, C = 3, 8
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    rows_w = jnp.full((J, m, k), _dtw._INF)
    moms_w = jnp.zeros((3, J, m, k))
    ns_w = jnp.zeros((J,), jnp.int32)
    sx_w = jnp.zeros((J,))
    sxx_w = jnp.zeros((J,))
    rows_p, moms_p, ns_p, sx_p, sxx_p = rows_w, moms_w, ns_w, sx_w, sxx_w
    rows_h = jnp.full((J, k, m), _dtw._INF)
    ns_h = jnp.zeros((J,), jnp.int32)
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray((rng.integers(0, 9, (J, C)) / 8.0)
                         .astype(np.float32))
        rows_w, moms_w, ns_w, sx_w, sxx_w, sc_w = \
            _dtw.bank_extend_tick_scored(
                rows_w, moms_w, ns_w, sx_w, sxx_w, bank_t, lengths, ch,
                nv, qlens, band=band)
        rows_p, moms_p, ns_p, sx_p, sxx_p, sc_p = \
            _dtw.bank_extend_tick_scored_dispatch(
                rows_p, moms_p, ns_p, sx_p, sxx_p, bank_t, lengths,
                ch, nv, qlens, band=band, use_kernel=True,
                interpret=True, block_k=block_k)
        rows_h, ns_h, _ = _dtw._bank_extend_many(
            rows_h, ns_h, jnp.asarray(bank.series), lengths, ch, nv,
            qlens, band, False)
        rp, rw = np.asarray(rows_p), np.asarray(rows_w)
        rh = np.asarray(rows_h).transpose(0, 2, 1)
        finite = rw < 1e37
        assert (finite == (rp < 1e37)).all()
        assert (finite == (rh < 1e37)).all()
        np.testing.assert_array_equal(rp[finite], rw[finite])
        np.testing.assert_array_equal(rp[finite], rh[finite])
        mp, mw = np.asarray(moms_p), np.asarray(moms_w)
        fin3 = np.broadcast_to(finite[None], mp.shape)
        np.testing.assert_array_equal(mp[fin3], mw[fin3])
        np.testing.assert_array_equal(np.asarray(sc_p), np.asarray(sc_w))
    np.testing.assert_array_equal(np.asarray(ns_p), np.asarray(ns_w))
    np.testing.assert_array_equal(np.asarray(ns_p), np.asarray(ns_h))


def test_stream_scored_kernel_scores_match_host_backtrack():
    """Through the Pallas path, the fused on-device scores still reproduce
    the host backtrack scorer on smooth real-valued data (tolerance-level:
    float noise can flip warp-path ties, which moves individual moments
    but not scores)."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw
    from repro.core.database import pack_series
    from repro.core.similarity import prefix_similarity_bank

    rng = np.random.default_rng(5)
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
    rows = jnp.full((J, m, k), _dtw._INF)
    moms = jnp.zeros((3, J, m, k))
    ns = jnp.zeros((J,), jnp.int32)
    sx = jnp.zeros((J,))
    sxx = jnp.zeros((J,))
    qlens = jnp.full((J,), qlen, jnp.int32)
    rows_h = jnp.full((J, k, m), _dtw._INF)
    ns_h = jnp.zeros((J,), jnp.int32)
    collected = []
    for t0 in range(nticks):
        ch = jnp.asarray(qs[:, t0 * C:(t0 + 1) * C])
        nv = jnp.full((J,), C, jnp.int32)
        rows, moms, ns, sx, sxx, scores = \
            _dtw.bank_extend_tick_scored_dispatch(
                rows, moms, ns, sx, sxx, jnp.asarray(bank.series.T),
                jnp.asarray(bank.lengths), ch, nv, qlens,
                use_kernel=True, interpret=True)
        rows_h, ns_h, coll = _dtw._bank_extend_many(
            rows_h, ns_h, jnp.asarray(bank.series),
            jnp.asarray(bank.lengths), ch, nv, qlens, None, True)
        collected.append(np.asarray(coll))
        stack = np.concatenate(collected)
        dev = np.asarray(scores)
        for j in range(J):
            host = prefix_similarity_bank(qs[j, :(t0 + 1) * C], bank,
                                          stack[:, j])
            np.testing.assert_allclose(dev[j], host, atol=2e-3)


@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128), (None, 4)])
def test_stream_kernel_cell_by_cell_vs_bank_extend(band, block_k):
    """Pallas streaming bank-extend == core.dtw._bank_extend_many on every
    cell, across multiple ragged chunks (random per-job nvalid), ragged
    reference lengths, banded and unbanded, including a block_k that
    forces reference-tile padding."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw
    from repro.core.database import pack_series
    from repro.kernels.dtw import stream_bank_extend_kernel

    rng = np.random.default_rng(0 if band is None else band)
    series = [rng.random(int(rng.integers(12, 30))).astype(np.float32)
              for _ in range(7)]
    bank = pack_series(series)
    k, m = bank.series.shape
    J, C = 3, 8
    rows_p = jnp.full((J, k, m), _dtw._INF)
    ns_p = jnp.zeros((J,), jnp.int32)
    rows_h, ns_h = rows_p, ns_p
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray(rng.random((J, C)).astype(np.float32))
        rows_p, ns_p = stream_bank_extend_kernel(
            rows_p, ns_p, bank.series, bank.lengths, ch, nv, qlens,
            band=band, block_k=block_k, interpret=True)
        rows_h, ns_h, _ = _dtw._bank_extend_many(
            rows_h, ns_h, jnp.asarray(bank.series),
            jnp.asarray(bank.lengths), ch, nv, qlens, band, False)
    r1, r2 = np.asarray(rows_p), np.asarray(rows_h)
    finite = r2 < 1e37
    assert (finite == (r1 < 1e37)).all()
    np.testing.assert_allclose(r1[finite], r2[finite], rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ns_p), np.asarray(ns_h))


# ---------------------------------------------------------------------------
# Variance-carrying (uncertain-series) DP
# ---------------------------------------------------------------------------

def _var_bank(rng, k=7):
    from repro.core.database import pack_series
    return pack_series([_dyadic_series(rng, int(rng.integers(12, 30)))
                        for _ in range(k)])


@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128), (None, 4),
                                          (6, 4)])
def test_stream_scored_var_kernel_cell_by_cell(band, block_k):
    """Variance-mode Pallas streaming kernel == the jnp variance wavefront
    on every cell — DP rows, all SIX moment slabs, scores, probs and the
    (sv, svx, svxx) folds — across ragged banks, bands, ragged chunks and
    tile padding; exact on dyadic grids (variances dyadic too, so every
    variance-channel product is exactly representable)."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw

    rng = np.random.default_rng(23 if band is None else band + block_k)
    bank = _var_bank(rng)
    k, m = bank.series.shape
    J, C = 3, 8
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    rows_w = jnp.full((J, m, k), _dtw._INF)
    moms_w = jnp.zeros((6, J, m, k))
    ns_w = jnp.zeros((J,), jnp.int32)
    sx_w = jnp.zeros((J,))
    sxx_w = jnp.zeros((J,))
    vst_w = jnp.zeros((J, 3))
    state_p = (rows_w, moms_w, ns_w, sx_w, sxx_w, vst_w)
    state_w = state_p
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray((rng.integers(0, 9, (J, C)) / 8.0)
                         .astype(np.float32))
        vch = jnp.asarray((rng.integers(0, 5, (J, C)) / 64.0)
                          .astype(np.float32))
        *state_w, sc_w, vw, pr_w = _dtw.bank_extend_tick_scored_var(
            state_w[0], state_w[1], state_w[2], state_w[3], state_w[4],
            state_w[5], bank_t, lengths, ch, vch, nv, qlens, band=band,
            threshold=0.85)
        state_w = state_w[:5] + [vw]
        *state_p, sc_p, vp, pr_p = \
            _dtw.bank_extend_tick_scored_var_dispatch(
                state_p[0], state_p[1], state_p[2], state_p[3], state_p[4],
                state_p[5], bank_t, lengths, ch, vch, nv, qlens, band=band,
                threshold=0.85, use_kernel=True, interpret=True,
                block_k=block_k)
        state_p = state_p[:5] + [vp]
        rp, rw = np.asarray(state_p[0]), np.asarray(state_w[0])
        finite = rw < 1e37
        assert (finite == (rp < 1e37)).all()
        np.testing.assert_array_equal(rp[finite], rw[finite])
        mp, mw = np.asarray(state_p[1]), np.asarray(state_w[1])
        fin6 = np.broadcast_to(finite[None], mp.shape)
        np.testing.assert_array_equal(mp[fin6], mw[fin6])
        np.testing.assert_array_equal(np.asarray(sc_p), np.asarray(sc_w))
        np.testing.assert_array_equal(np.asarray(pr_p), np.asarray(pr_w))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(vw))
    np.testing.assert_array_equal(np.asarray(state_p[2]),
                                  np.asarray(state_w[2]))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_stream_var_zero_variance_reduces_bitwise(use_kernel):
    """With all-zero per-sample variances the variance tick's rows, base
    moment slabs and scores are BIT-IDENTICAL to the exact scored tick's,
    and every probability is exactly 0.0 or 1.0 with
    ``prob == 1 <=> score >= threshold``."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw

    rng = np.random.default_rng(31)
    bank = _var_bank(rng)
    k, m = bank.series.shape
    J, C = 3, 8
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    rows_e = jnp.full((J, m, k), _dtw._INF)
    moms_e = jnp.zeros((3, J, m, k))
    ns_e = jnp.zeros((J,), jnp.int32)
    sx_e = jnp.zeros((J,))
    sxx_e = jnp.zeros((J,))
    rows_v, moms_v = rows_e, jnp.zeros((6, J, m, k))
    ns_v, sx_v, sxx_v = ns_e, sx_e, sxx_e
    vst = jnp.zeros((J, 3))
    thr = 0.85
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray((rng.integers(0, 9, (J, C)) / 8.0)
                         .astype(np.float32))
        vch = jnp.zeros((J, C))
        rows_e, moms_e, ns_e, sx_e, sxx_e, sc_e = \
            _dtw.bank_extend_tick_scored(rows_e, moms_e, ns_e, sx_e,
                                         sxx_e, bank_t, lengths, ch, nv,
                                         qlens, band=6)
        (rows_v, moms_v, ns_v, sx_v, sxx_v, sc_v, vst,
         pr) = _dtw.bank_extend_tick_scored_var_dispatch(
            rows_v, moms_v, ns_v, sx_v, sxx_v, vst, bank_t, lengths, ch,
            vch, nv, qlens, band=6, threshold=thr, use_kernel=use_kernel,
            interpret=True if use_kernel else None)
        np.testing.assert_array_equal(np.asarray(rows_v),
                                      np.asarray(rows_e))
        np.testing.assert_array_equal(np.asarray(moms_v)[:3],
                                      np.asarray(moms_e))
        np.testing.assert_array_equal(np.asarray(sc_v), np.asarray(sc_e))
        assert np.asarray(vst).max() == 0.0
        pr, sc = np.asarray(pr), np.asarray(sc_e)
        assert set(np.unique(pr)) <= {0.0, 1.0}
        np.testing.assert_array_equal(pr == 1.0, sc >= thr)


def test_stream_var_chunking_invariance():
    """Any chunking of the variance-mode stream reproduces the one-shot
    solve BITWISE on dyadic grids (samples and variances both dyadic):
    rows, all six moment slabs, variance folds, scores and probs."""
    from hypothesis import given, settings, strategies as st
    import jax.numpy as jnp
    from repro.core import dtw as _dtw

    rng0 = np.random.default_rng(47)
    bank = _var_bank(rng0, k=5)
    k, m = bank.series.shape
    N = 24
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    q = _dyadic_series(rng0, N)
    v = (rng0.integers(0, 5, N) / 64.0).astype(np.float32)

    def run(chunk_sizes):
        rows = jnp.full((1, m, k), _dtw._INF)
        moms = jnp.zeros((6, 1, m, k))
        ns = jnp.zeros((1,), jnp.int32)
        sx = jnp.zeros((1,))
        sxx = jnp.zeros((1,))
        vst = jnp.zeros((1, 3))
        lo = 0
        out = None
        for c in chunk_sizes:
            ch, vch = q[lo:lo + c], v[lo:lo + c]
            lo += c
            (rows, moms, ns, sx, sxx, sc, vst,
             pr) = _dtw.bank_extend_tick_scored_var(
                rows, moms, ns, sx, sxx, vst, bank_t, lengths,
                jnp.asarray(ch[None]), jnp.asarray(vch[None]),
                jnp.asarray([len(ch)], np.int32),
                jnp.asarray([N], np.int32), band=4, threshold=0.85)
            out = (rows, moms, vst, sc, pr)
        return [np.asarray(a) for a in out]

    ref = run([N])

    @settings(max_examples=12, deadline=None)
    @given(st.integers(min_value=1, max_value=N - 1))
    def prop(c):
        sizes = [c] * (N // c)
        if N % c:
            sizes.append(N % c)
        got = run(sizes)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)

    prop()


@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128), (None, 4),
                                          (6, 4)])
def test_offline_var_kernel_cell_by_cell(band, block_k):
    """Offline variance Pallas kernel == the variance wavefront tile
    scorer bitwise (scores, probs, distances) on dyadic data across
    ragged banks/queries, bands and tile padding; zero variance further
    reduces bitwise to the exact offline kernel."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw
    from repro.kernels.dtw import (score_bank_offline_kernel,
                                   score_bank_offline_var_kernel)

    rng = np.random.default_rng(3 if band is None else 10 * band + block_k)
    bank = _var_bank(rng)
    k, m = bank.series.shape
    J, n = 3, 20
    xlens = np.asarray([20, 13, 17], np.int32)
    xs = np.zeros((J, n), np.float32)
    xv = np.zeros((J, n), np.float32)
    for i, L in enumerate(xlens):
        xs[i, :L] = _dyadic_series(rng, L)
        xv[i, :L] = (rng.integers(0, 5, L) / 64.0).astype(np.float32)
    sx = np.zeros(J, np.float32)
    sxx = np.zeros(J, np.float32)
    vst = np.zeros((J, 3), np.float32)
    for i, L in enumerate(xlens):
        sx[i], sxx[i] = _dtw.query_moments(xs[i, :L])
        vst[i] = _dtw.query_var_moments(xs[i, :L], xv[i, :L])
    ks, kp, kd = score_bank_offline_var_kernel(
        xs, xv, xlens, bank.series, bank.lengths, sx, sxx, vst,
        band=band, threshold=0.85, block_k=block_k, interpret=True)
    ws, wp, wd = _dtw._score_tile_var_many(
        jnp.asarray(xs), jnp.asarray(xv), jnp.asarray(xlens),
        jnp.asarray(bank.series), jnp.asarray(bank.lengths),
        jnp.asarray(sx), jnp.asarray(sxx), jnp.asarray(vst), band, 0.85)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(wp))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(wd))

    zs, zp, zd = score_bank_offline_var_kernel(
        xs, np.zeros_like(xv), xlens, bank.series, bank.lengths, sx, sxx,
        np.zeros_like(vst), band=band, threshold=0.85, block_k=block_k,
        interpret=True)
    es, ed = score_bank_offline_kernel(xs, xlens, bank.series,
                                       bank.lengths, sx, sxx, band=band,
                                       block_k=block_k, interpret=True)
    np.testing.assert_array_equal(np.asarray(zs), np.asarray(es))
    np.testing.assert_array_equal(np.asarray(zd), np.asarray(ed))
    zp = np.asarray(zp)
    assert set(np.unique(zp)) <= {0.0, 1.0}
    np.testing.assert_array_equal(zp == 1.0, np.asarray(zs) >= 0.85)


def test_score_bank_many_var_jnp_vs_kernel():
    """`dtw_score_bank_many(xvars=...)`'s jnp tile path and its Pallas
    kernel path agree bitwise on dyadic data, and the returned probs are
    finite probabilities."""
    from repro.core import dtw as _dtw

    rng = np.random.default_rng(91)
    bank = _var_bank(rng, k=9)
    J, n = 2, 24
    xs = np.stack([_dyadic_series(rng, n) for _ in range(J)])
    xv = (rng.integers(0, 5, (J, n)) / 64.0).astype(np.float32)
    a = _dtw.dtw_score_bank_many(xs, bank.series, bank.lengths,
                                 band=6, xvars=xv, threshold=0.85,
                                 use_kernel=False)
    b = _dtw.dtw_score_bank_many(xs, bank.series, bank.lengths,
                                 band=6, xvars=xv, threshold=0.85,
                                 use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    p = np.asarray(a[1])
    assert np.isfinite(p).all() and (p >= 0).all() and (p <= 1).all()

# ---------------------------------------------------------------------------
# Approximate-tail (4-channel) variance DP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128), (None, 4),
                                          (6, 4)])
def test_stream_scored_var_approx_kernel_cell_by_cell(band, block_k):
    """Approx-mode (FOUR moment channels) Pallas streaming kernel == the
    jnp variance wavefront on every cell — DP rows, all four slabs, the
    (sv, svx, svxx) folds, scores and approx probs — across ragged
    banks, bands, ragged chunks and tile padding; exact on dyadic
    grids."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw

    rng = np.random.default_rng(53 if band is None else band + block_k)
    bank = _var_bank(rng)
    k, m = bank.series.shape
    J, C = 3, 8
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    rows_w = jnp.full((J, m, k), _dtw._INF)
    moms_w = jnp.zeros((4, J, m, k))
    ns_w = jnp.zeros((J,), jnp.int32)
    sx_w = jnp.zeros((J,))
    sxx_w = jnp.zeros((J,))
    vst_w = jnp.zeros((J, 3))
    state_p = (rows_w, moms_w, ns_w, sx_w, sxx_w, vst_w)
    state_w = state_p
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray((rng.integers(0, 9, (J, C)) / 8.0)
                         .astype(np.float32))
        vch = jnp.asarray((rng.integers(0, 5, (J, C)) / 64.0)
                          .astype(np.float32))
        *state_w, sc_w, vw, pr_w = _dtw.bank_extend_tick_scored_var_approx(
            state_w[0], state_w[1], state_w[2], state_w[3], state_w[4],
            state_w[5], bank_t, lengths, ch, vch, nv, qlens, band=band,
            threshold=0.85)
        state_w = state_w[:5] + [vw]
        *state_p, sc_p, vp, pr_p = \
            _dtw.bank_extend_tick_scored_var_approx_dispatch(
                state_p[0], state_p[1], state_p[2], state_p[3], state_p[4],
                state_p[5], bank_t, lengths, ch, vch, nv, qlens, band=band,
                threshold=0.85, use_kernel=True, interpret=True,
                block_k=block_k)
        state_p = state_p[:5] + [vp]
        rp, rw = np.asarray(state_p[0]), np.asarray(state_w[0])
        finite = rw < 1e37
        assert (finite == (rp < 1e37)).all()
        np.testing.assert_array_equal(rp[finite], rw[finite])
        mp, mw = np.asarray(state_p[1]), np.asarray(state_w[1])
        fin4 = np.broadcast_to(finite[None], mp.shape)
        np.testing.assert_array_equal(mp[fin4], mw[fin4])
        np.testing.assert_array_equal(np.asarray(sc_p), np.asarray(sc_w))
        np.testing.assert_array_equal(np.asarray(pr_p), np.asarray(pr_w))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(vw))
    np.testing.assert_array_equal(np.asarray(state_p[2]),
                                  np.asarray(state_w[2]))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_stream_var_approx_zero_variance_reduces_bitwise(use_kernel):
    """With all-zero per-sample variances the APPROX tick's rows, base
    moment slabs and scores are BIT-IDENTICAL to the exact scored
    tick's, every probability is exactly 0.0 or 1.0 with
    ``prob == 1 <=> score >= threshold`` — and the probs are bitwise
    equal to the exact six-channel variance tick's (the approx tail
    reduces to the same point rule, not just the same set)."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw

    rng = np.random.default_rng(59)
    bank = _var_bank(rng)
    k, m = bank.series.shape
    J, C = 3, 8
    qlens = jnp.full((J,), 4 * C, jnp.int32)
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    rows_e = jnp.full((J, m, k), _dtw._INF)
    moms_e = jnp.zeros((3, J, m, k))
    ns_e = jnp.zeros((J,), jnp.int32)
    sx_e = jnp.zeros((J,))
    sxx_e = jnp.zeros((J,))
    rows_a, moms_a = rows_e, jnp.zeros((4, J, m, k))
    ns_a, sx_a, sxx_a = ns_e, sx_e, sxx_e
    vst_a = jnp.zeros((J, 3))
    rows_v, moms_v = rows_e, jnp.zeros((6, J, m, k))
    ns_v, sx_v, sxx_v, vst_v = ns_e, sx_e, sxx_e, vst_a
    thr = 0.85
    for _ in range(4):
        nv = jnp.asarray(rng.integers(0, C + 1, size=J).astype(np.int32))
        ch = jnp.asarray((rng.integers(0, 9, (J, C)) / 8.0)
                         .astype(np.float32))
        vch = jnp.zeros((J, C))
        rows_e, moms_e, ns_e, sx_e, sxx_e, sc_e = \
            _dtw.bank_extend_tick_scored(rows_e, moms_e, ns_e, sx_e,
                                         sxx_e, bank_t, lengths, ch, nv,
                                         qlens, band=6)
        (rows_a, moms_a, ns_a, sx_a, sxx_a, sc_a, vst_a,
         pr_a) = _dtw.bank_extend_tick_scored_var_approx_dispatch(
            rows_a, moms_a, ns_a, sx_a, sxx_a, vst_a, bank_t, lengths, ch,
            vch, nv, qlens, band=6, threshold=thr, use_kernel=use_kernel,
            interpret=True if use_kernel else None)
        (rows_v, moms_v, ns_v, sx_v, sxx_v, sc_v, vst_v,
         pr_v) = _dtw.bank_extend_tick_scored_var_dispatch(
            rows_v, moms_v, ns_v, sx_v, sxx_v, vst_v, bank_t, lengths, ch,
            vch, nv, qlens, band=6, threshold=thr, use_kernel=use_kernel,
            interpret=True if use_kernel else None)
        np.testing.assert_array_equal(np.asarray(rows_a),
                                      np.asarray(rows_e))
        np.testing.assert_array_equal(np.asarray(moms_a)[:3],
                                      np.asarray(moms_e))
        np.testing.assert_array_equal(np.asarray(sc_a), np.asarray(sc_e))
        assert np.asarray(vst_a).max() == 0.0
        pr, sc = np.asarray(pr_a), np.asarray(sc_e)
        assert set(np.unique(pr)) <= {0.0, 1.0}
        np.testing.assert_array_equal(pr == 1.0, sc >= thr)
        np.testing.assert_array_equal(pr, np.asarray(pr_v))


def test_stream_var_approx_chunking_invariance():
    """Any chunking of the approx-mode stream reproduces the one-shot
    solve BITWISE on dyadic grids: rows, all four moment slabs,
    variance folds, scores and approx probs."""
    from hypothesis import given, settings, strategies as st
    import jax.numpy as jnp
    from repro.core import dtw as _dtw

    rng0 = np.random.default_rng(61)
    bank = _var_bank(rng0, k=5)
    k, m = bank.series.shape
    N = 24
    bank_t = jnp.asarray(bank.series.T)
    lengths = jnp.asarray(bank.lengths)
    q = _dyadic_series(rng0, N)
    v = (rng0.integers(0, 5, N) / 64.0).astype(np.float32)

    def run(chunk_sizes):
        rows = jnp.full((1, m, k), _dtw._INF)
        moms = jnp.zeros((4, 1, m, k))
        ns = jnp.zeros((1,), jnp.int32)
        sx = jnp.zeros((1,))
        sxx = jnp.zeros((1,))
        vst = jnp.zeros((1, 3))
        lo = 0
        out = None
        for c in chunk_sizes:
            ch, vch = q[lo:lo + c], v[lo:lo + c]
            lo += c
            (rows, moms, ns, sx, sxx, sc, vst,
             pr) = _dtw.bank_extend_tick_scored_var_approx(
                rows, moms, ns, sx, sxx, vst, bank_t, lengths,
                jnp.asarray(ch[None]), jnp.asarray(vch[None]),
                jnp.asarray([len(ch)], np.int32),
                jnp.asarray([N], np.int32), band=4, threshold=0.85)
            out = (rows, moms, vst, sc, pr)
        return [np.asarray(a) for a in out]

    ref = run([N])

    @settings(max_examples=12, deadline=None)
    @given(st.integers(min_value=1, max_value=N - 1))
    def prop(c):
        sizes = [c] * (N // c)
        if N % c:
            sizes.append(N % c)
        got = run(sizes)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)

    prop()


@pytest.mark.parametrize("band,block_k", [(None, 128), (6, 128), (None, 4),
                                          (6, 4)])
def test_offline_var_approx_kernel_cell_by_cell(band, block_k):
    """Offline approx-tail Pallas kernel == the variance wavefront tile
    scorer with ``approx=True`` bitwise (scores, probs, distances) on
    dyadic data across ragged banks/queries, bands and tile padding;
    zero variance further reduces bitwise to the exact offline
    kernel."""
    import jax.numpy as jnp
    from repro.core import dtw as _dtw
    from repro.kernels.dtw import (score_bank_offline_kernel,
                                   score_bank_offline_var_approx_kernel)

    rng = np.random.default_rng(7 if band is None else 20 * band + block_k)
    bank = _var_bank(rng)
    k, m = bank.series.shape
    J, n = 3, 20
    xlens = np.asarray([20, 13, 17], np.int32)
    xs = np.zeros((J, n), np.float32)
    xv = np.zeros((J, n), np.float32)
    for i, L in enumerate(xlens):
        xs[i, :L] = _dyadic_series(rng, L)
        xv[i, :L] = (rng.integers(0, 5, L) / 64.0).astype(np.float32)
    sx = np.zeros(J, np.float32)
    sxx = np.zeros(J, np.float32)
    vst = np.zeros((J, 3), np.float32)
    for i, L in enumerate(xlens):
        sx[i], sxx[i] = _dtw.query_moments(xs[i, :L])
        vst[i] = _dtw.query_var_moments(xs[i, :L], xv[i, :L])
    ks, kp, kd = score_bank_offline_var_approx_kernel(
        xs, xv, xlens, bank.series, bank.lengths, sx, sxx, vst,
        band=band, threshold=0.85, block_k=block_k, interpret=True)
    ws, wp, wd = _dtw._score_tile_var_many(
        jnp.asarray(xs), jnp.asarray(xv), jnp.asarray(xlens),
        jnp.asarray(bank.series), jnp.asarray(bank.lengths),
        jnp.asarray(sx), jnp.asarray(sxx), jnp.asarray(vst), band, 0.85,
        approx=True)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(wp))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(wd))

    zs, zp, zd = score_bank_offline_var_approx_kernel(
        xs, np.zeros_like(xv), xlens, bank.series, bank.lengths, sx, sxx,
        np.zeros_like(vst), band=band, threshold=0.85, block_k=block_k,
        interpret=True)
    es, ed = score_bank_offline_kernel(xs, xlens, bank.series,
                                       bank.lengths, sx, sxx, band=band,
                                       block_k=block_k, interpret=True)
    np.testing.assert_array_equal(np.asarray(zs), np.asarray(es))
    np.testing.assert_array_equal(np.asarray(zd), np.asarray(ed))
    zp = np.asarray(zp)
    assert set(np.unique(zp)) <= {0.0, 1.0}
    np.testing.assert_array_equal(zp == 1.0, np.asarray(zs) >= 0.85)


def test_score_bank_many_prob_mode_approx_jnp_vs_kernel():
    """`dtw_score_bank_many(prob_mode="approx")`'s jnp tile path and its
    Pallas kernel path agree bitwise on dyadic data, the returned probs
    are finite probabilities, and scores/distances are bitwise
    independent of prob_mode (the approx tail only changes the
    probability channel)."""
    from repro.core import dtw as _dtw

    rng = np.random.default_rng(97)
    bank = _var_bank(rng, k=9)
    J, n = 2, 24
    xs = np.stack([_dyadic_series(rng, n) for _ in range(J)])
    xv = (rng.integers(0, 5, (J, n)) / 64.0).astype(np.float32)
    a = _dtw.dtw_score_bank_many(xs, bank.series, bank.lengths,
                                 band=6, xvars=xv, threshold=0.85,
                                 prob_mode="approx", use_kernel=False)
    b = _dtw.dtw_score_bank_many(xs, bank.series, bank.lengths,
                                 band=6, xvars=xv, threshold=0.85,
                                 prob_mode="approx", use_kernel=True,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    p = np.asarray(a[1])
    assert np.isfinite(p).all() and (p >= 0).all() and (p <= 1).all()
    e = _dtw.dtw_score_bank_many(xs, bank.series, bank.lengths,
                                 band=6, xvars=xv, threshold=0.85,
                                 use_kernel=False)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(e[0]))

    with pytest.raises(ValueError):
        _dtw.dtw_score_bank_many(xs, bank.series, bank.lengths, band=6,
                                 xvars=xv, prob_mode="bogus")
