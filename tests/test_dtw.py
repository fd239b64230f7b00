"""DTW (paper Eq. 1-2): jnp min-plus scan vs brute force + properties."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import dtw
from repro.kernels.dtw.ref import dtw_matrix_ref


@given(st.integers(0, 2**31 - 1), st.integers(2, 24), st.integers(2, 31))
@settings(max_examples=25, deadline=None)
def test_dtw_matrix_matches_bruteforce(seed, n, m):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n).astype(np.float32)
    y = rng.normal(size=m).astype(np.float32)
    D = np.asarray(dtw.dtw_matrix(x, y))
    Dr = dtw_matrix_ref(x, y)
    np.testing.assert_allclose(D, Dr, rtol=1e-4, atol=1e-4)


def test_identity_distance_zero():
    x = np.random.default_rng(0).normal(size=50).astype(np.float32)
    assert float(dtw.dtw_distance(x, x)) < 1e-4


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_distance_symmetry(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=17).astype(np.float32)
    y = rng.normal(size=23).astype(np.float32)
    assert abs(float(dtw.dtw_distance(x, y))
               - float(dtw.dtw_distance(y, x))) < 1e-3


def test_banded_equals_full_for_wide_band():
    rng = np.random.default_rng(3)
    x = rng.normal(size=20).astype(np.float32)
    y = rng.normal(size=25).astype(np.float32)
    Df = np.asarray(dtw.dtw_matrix(x, y))
    Db = np.asarray(dtw.dtw_matrix_banded(x, y, band=30))
    np.testing.assert_allclose(Df, Db, rtol=1e-4, atol=1e-4)


def test_backtrack_path_valid():
    rng = np.random.default_rng(4)
    x = rng.normal(size=30).astype(np.float32)
    y = rng.normal(size=40).astype(np.float32)
    D = np.asarray(dtw.dtw_matrix(x, y))
    path = dtw.backtrack(D)
    assert tuple(path[0]) == (0, 0)
    assert tuple(path[-1]) == (29, 39)
    steps = np.diff(path, axis=0)
    assert ((steps >= 0) & (steps <= 1)).all()
    assert (steps.sum(axis=1) >= 1).all()


def test_warp_to_length_and_monotonicity():
    rng = np.random.default_rng(5)
    x = rng.normal(size=30).astype(np.float32)
    y = rng.normal(size=12).astype(np.float32)
    yp, dist = dtw.dtw_warp(x, y)
    assert yp.shape == (30,)
    assert set(np.unique(yp)).issubset(set(np.unique(y)))
    assert dist >= 0
