"""Chebyshev design + filtering (paper §3.1.1)."""
import numpy as np
import pytest
import scipy.signal as ss

from hypothesis import given, settings, strategies as st

from repro.core import filters


@pytest.mark.parametrize("order,ripple,cutoff", [
    (6, 1.0, 0.125), (6, 0.5, 0.3), (4, 2.0, 0.05), (2, 1.0, 0.6),
    (7, 1.0, 0.2), (3, 3.0, 0.9),
])
def test_cheby1_matches_scipy(order, ripple, cutoff):
    b, a = filters.cheby1_design(order, ripple, cutoff)
    bs, as_ = ss.cheby1(order, ripple, cutoff)
    np.testing.assert_allclose(b, bs, atol=1e-9)
    np.testing.assert_allclose(a, as_, atol=1e-9)


def test_lfilter_matches_scipy():
    b, a = filters.cheby1_design(6, 1.0, 0.125)
    x = np.random.default_rng(0).normal(size=(4, 300)).astype(np.float32)
    y = np.asarray(filters.lfilter(b, a, x))
    ys = ss.lfilter(b, a, x, axis=-1)
    np.testing.assert_allclose(y, ys, atol=5e-3)


def test_denoise_reduces_noise_power():
    rng = np.random.default_rng(1)
    t = np.linspace(0, 10, 500)
    clean = np.sin(2 * np.pi * 0.2 * t)
    noisy = clean + rng.normal(0, 0.3, size=t.shape)
    den = np.asarray(filters.denoise(noisy.astype(np.float32)))
    assert np.mean((den - clean) ** 2) < 0.5 * np.mean((noisy - clean) ** 2)


@given(st.integers(0, 2**31 - 1), st.integers(30, 200))
@settings(max_examples=20, deadline=None)
def test_normalize01_bounds(seed, n):
    x = np.random.default_rng(seed).normal(size=n).astype(np.float32) * 10
    y = np.asarray(filters.normalize01(x))
    assert y.min() >= -1e-6 and y.max() <= 1 + 1e-6
    assert y.max() >= 1 - 1e-5  # hits both bounds


def test_preprocess_pipeline_shape_and_range():
    x = np.random.default_rng(2).normal(size=(3, 128)).astype(np.float32)
    y = np.asarray(filters.preprocess(x))
    assert y.shape == x.shape
    assert np.isfinite(y).all()
    assert (y >= -1e-6).all() and (y <= 1 + 1e-6).all()
