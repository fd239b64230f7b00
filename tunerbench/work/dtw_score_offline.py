"""Operations and bytes of the verdict kernel ``dtw_score_offline_{3,6}ch``.

A verdict scores a finished query of ``n`` samples against every
reference with the closed end, the band (if any) centred on row
``i * (len - 1) // (n - 1)``.  Live cells and operations per cell are
those of the streaming tick (``dtw_stream_scored.py``): the same row
update and moment carry.  Bytes: the bank read once per verdict call,
the queries (and variances) and the [J, K] scores (and probabilities)
written.  The DP state never needs to leave the chip, so it is not
counted.
"""

from __future__ import annotations

import os
import importlib.util

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "tunerbench_work_stream", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "dtw_stream_scored.py"))
_stream = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_stream)


def count(queries, lengths, band, nch: int, variance: bool):
    """``queries``: lengths of the verdicts of one call -> (ops, bytes)."""
    lengths = np.asarray(lengths, np.int64)
    cells = 0
    for n in queries:
        if n < 2:
            continue
        cells += int(_stream.live_span(np.arange(n), n, lengths,
                                       band).sum())
    j = sum(1 for n in queries if n >= 2)
    if not j:
        return 0, 0
    nbytes = 4 * (int(lengths.sum())
                  + sum(queries) * (2 if variance else 1)
                  + j * len(lengths) * (2 if variance else 1))
    return cells * _stream.ops_per_cell(nch), nbytes
