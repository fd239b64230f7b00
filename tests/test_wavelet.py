"""Haar wavelet compression (paper §5 future plan)."""
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import wavelet


@given(st.integers(0, 2**31 - 1), st.integers(2, 200))
@settings(max_examples=25, deadline=None)
def test_perfect_reconstruction(seed, n):
    x = np.random.default_rng(seed).normal(size=n)
    xr = wavelet.reconstruct(wavelet.haar_dwt(x), n)
    np.testing.assert_allclose(x, xr, atol=1e-9)


def test_compression_keeps_top_energy():
    x = np.sin(np.linspace(0, 4 * np.pi, 128))
    c_full = wavelet.haar_dwt(x)
    c16 = wavelet.compress(x, 16)
    assert (c16 != 0).sum() <= 16
    # kept coefficients carry most of the energy
    assert np.sum(c16 ** 2) >= 0.95 * np.sum(c_full ** 2)


def test_wavelet_similarity_self():
    x = np.random.default_rng(0).normal(size=100)
    assert wavelet.wavelet_similarity(x, x) > 0.999


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_streaming_haar_equals_offline_at_every_chunk_boundary(seed):
    """StreamingHaar prefix coefficients == offline haar_dwt of the same
    edge-extended prefix, bitwise, at EVERY chunk boundary of a random
    chunking — the soundness contract the online prefilter rests on."""
    rng = np.random.default_rng(seed)
    total = int(rng.integers(2, 200))
    x = rng.normal(size=total)
    sh = wavelet.StreamingHaar(total)
    lo = 0
    while lo < total:
        c = int(rng.integers(1, max(2, total // 3)))
        sh.update(x[lo: lo + c])
        lo = min(lo + c, total)
        prefix = np.pad(x[:lo], (0, sh.size - lo), mode="edge")
        np.testing.assert_array_equal(sh.coeffs(), wavelet.haar_dwt(prefix))
    assert sh.size == wavelet._next_pow2(max(total, 2))


def test_streaming_haar_regrows_past_expected_len():
    """expected_len is a prediction: a job that overruns the power-of-two
    target regrows transparently and stays equal to the offline
    transform."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=70)
    sh = wavelet.StreamingHaar(16)          # predicted 16, actual 70
    for lo in range(0, 70, 7):
        sh.update(x[lo: lo + 7])
    assert sh.size == 128
    want = wavelet.haar_dwt(np.pad(x, (0, 128 - 70), mode="edge"))
    np.testing.assert_array_equal(sh.coeffs(), want)
    # compressed() keeps at most m nonzeros of the same coefficients
    cm = sh.compressed(16)
    assert (cm != 0).sum() <= 16
    assert set(np.flatnonzero(cm)) <= set(np.flatnonzero(want))


def test_coeff_similarity_bank_matches_offline_tail():
    """The split-out cosine tail reproduces wavelet_similarity_bank."""
    rng = np.random.default_rng(9)
    x = rng.random(100)
    bank = rng.random((5, 90)).astype(np.float64)
    lengths = np.full((5,), 90, np.int64)
    want = wavelet.wavelet_similarity_bank(x, bank, lengths, m=32)
    n = max(wavelet._next_pow2(100), wavelet._next_pow2(90))
    xp = np.pad(x, (0, n - 100), mode="edge")
    bp = np.pad(bank, ((0, 0), (0, n - 90)), mode="edge")
    cx = wavelet.compress(xp, 32)
    cb = wavelet.compress_bank(wavelet.haar_dwt_bank(bp), 32)
    np.testing.assert_array_equal(wavelet.coeff_similarity_bank(cx, cb),
                                  want)


def test_wavelet_matching_agrees_with_dtw_on_easy_cases():
    from repro import mrsim
    p = mrsim.paper_param_sets()[0]
    exim = mrsim.simulate_cpu_series("exim", p)
    wc = mrsim.simulate_cpu_series("wordcount", p)
    ts = mrsim.simulate_cpu_series("terasort", p)
    s_wc = wavelet.wavelet_similarity(exim, wc, m=64)
    s_ts = wavelet.wavelet_similarity(exim, ts, m=64)
    assert s_wc > s_ts
