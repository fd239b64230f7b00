"""Operations and bytes of the streaming tick kernel ``dtw_stream_scored``.

Counted from the algorithm's shapes, never from tiles, padding or grid:
a job that brings ``nvalid`` samples at row ``n0`` extends its DP by
those rows against every reference, and a cell is live when it lies
inside the reference's length and, banded, within ``band`` columns of
the row's Sakoe-Chiba centre ``row * (len - 1) // (qlen - 1)``.

Operations per live cell, for ``nch`` moment channels:

* distance: |x - y| (2) and the add of the best predecessor to it (1);
  the best of three predecessors (2);
* predecessor choice for the moments: 2 compares;
* per channel: pick the chosen predecessor's value (2 selects) and add
  this cell's aligned pair (1);
* the pair's terms that depend on the query sample: x y (1), and in
  variance mode v times each twinned channel (nch - 3).

Bytes: the DP row and its ``nch`` moment slabs over the live cells of
the row before and after the chunk, read and written once; the bank
columns the chunk touches, read once (for each reference the widest
single job's span, a lower bound of their union); the chunk's samples
(and variances).
"""

from __future__ import annotations

import numpy as np


def ops_per_cell(nch: int) -> int:
    return 5 + 2 + 3 * nch + 1 + (nch - 3)


def live_span(rows: np.ndarray, qlen: int, lengths: np.ndarray,
              band) -> np.ndarray:
    """Live cells of each (row, reference) -> [R, K]."""
    lengths = np.asarray(lengths, np.int64)
    rows = np.asarray(rows, np.int64)[:, None]
    if band is None:
        return np.broadcast_to(lengths[None, :], (rows.shape[0],
                                                  lengths.shape[0]))
    c = (rows * (lengths[None, :] - 1)) // max(int(qlen) - 1, 1)
    lo = np.maximum(c - band, 0)
    hi = np.minimum(c + band, lengths[None, :] - 1)
    return np.maximum(hi - lo + 1, 0)


def count(jobs, lengths, band, nch: int, variance: bool):
    """``jobs``: (n0, nvalid, qlen) of every job with samples in the tick
    -> (operations, bytes)."""
    cells = 0
    state = 0
    bank = np.zeros(len(lengths), np.int64)
    for n0, nv, qlen in jobs:
        if nv <= 0:
            continue
        rows = np.arange(n0, n0 + nv)
        live = live_span(rows, qlen, lengths, band)
        cells += int(live.sum())
        edge = live_span(np.asarray([max(n0 - 1, 0), n0 + nv - 1]), qlen,
                         lengths, band)
        state += int(edge[0].sum() if n0 > 0 else 0) + int(edge[1].sum())
        if band is None:
            bank = np.asarray(lengths, np.int64)
        else:
            lens = np.asarray(lengths, np.int64)
            q = max(int(qlen) - 1, 1)
            lo = np.maximum(n0 * (lens - 1) // q - band, 0)
            hi = np.minimum((n0 + nv - 1) * (lens - 1) // q + band,
                            lens - 1)
            bank = np.maximum(bank, np.maximum(hi - lo + 1, 0))
    samples = sum(nv for _, nv, _ in jobs)
    nbytes = 4 * (state * (1 + nch) + int(bank.sum())
                  + samples * (2 if variance else 1))
    return cells * ops_per_cell(nch), nbytes
