"""The kernels' work counts equal a brute-force count of live cells, with
and without a band, and depend on nothing but the algorithm's shapes."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import spec  # noqa: E402

STREAM = spec.work("dtw_stream_scored")
OFFLINE = spec.work("dtw_score_offline")


def brute(rows, qlen, lengths, band):
    cells = 0
    for r in rows:
        for ln in lengths:
            c = (r * (ln - 1)) // max(qlen - 1, 1)
            for j in range(ln):
                if band is None or abs(j - c) <= band:
                    cells += 1
    return cells


@pytest.mark.parametrize("band", [None, 1, 3])
@pytest.mark.parametrize("nch", [3, 4, 6])
def test_tick_counts_live_cells(band, nch):
    rng = np.random.default_rng(band or 0)
    lengths = rng.integers(3, 30, size=7)
    jobs = [(0, 5, 12), (4, 3, 20), (9, 8, 9), (2, 0, 11)]
    ops, nbytes = STREAM.count(jobs, lengths, band, nch, nch > 3)
    cells = sum(brute(range(n0, n0 + nv), q, lengths, band)
                for n0, nv, q in jobs)
    assert ops == cells * STREAM.ops_per_cell(nch)
    assert nbytes > 0


@pytest.mark.parametrize("band", [None, 2])
def test_verdict_counts_live_cells(band):
    lengths = np.asarray([5, 17, 9, 30])
    queries = [6, 13, 1, 21]
    ops, _ = OFFLINE.count(queries, lengths, band, 6, True)
    cells = sum(brute(range(n), n, lengths, band) for n in queries if n > 1)
    assert ops == cells * STREAM.ops_per_cell(6)


def test_counts_ignore_order_and_padding():
    """Reordering the bank, or adding padding the kernel carries (an
    empty chunk slot, a longer padded width), changes nothing."""
    lengths = np.asarray([7, 12, 3, 25])
    jobs = [(1, 8, 30), (0, 4, 10)]
    base = STREAM.count(jobs, lengths, 2, 3, False)
    assert STREAM.count(jobs + [(5, 0, 40)], lengths, 2, 3, False) == base
    assert STREAM.count(jobs[::-1], lengths[::-1], 2, 3, False) == base
    assert OFFLINE.count([9, 4], lengths, None, 3, False) == \
        OFFLINE.count([9, 4], lengths[::-1], None, 3, False)


def test_ops_per_cell_grows_with_channels():
    assert STREAM.ops_per_cell(3) < STREAM.ops_per_cell(4) \
        < STREAM.ops_per_cell(6)
