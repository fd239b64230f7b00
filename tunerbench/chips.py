"""Per-chip device time of a traced run on several chips.

``tracing.Trace`` merges the ``XLA Ops`` of every device into one union,
so on four chips an op on any one of them counts the window as busy.
This module reads the same ``.xplane.pb`` again and keeps each device
plane's ops apart: a chip's busy time is the union of its own op
intervals inside the window.  On one chip the two readings agree.
"""

from __future__ import annotations

import os
import sys
import warnings
from typing import Dict, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import spans, tracing  # noqa: E402

_ATTACHED: Dict[int, tuple] = {}


def busy_by_device(trace: "tracing.Trace", path: str
                   ) -> Optional[Dict[str, float]]:
    """Busy seconds inside ``trace``'s window per device plane of the
    file at ``path``; None when the file holds another run."""
    from jax.profiler import ProfileData
    ops: Dict[str, list] = {}
    window = None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith("/device:TPU:")
            for line in plane.lines:
                if device and line.name == "XLA Ops":
                    rows = ops.setdefault(plane.name, [])
                    for ev in line.events:
                        s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                        if e > trace.t0 and s < trace.t1:
                            rows.append((max(s, trace.t0), min(e, trace.t1)))
                elif not device:
                    for ev in line.events:
                        if ev.name == "bench.window":
                            window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
    if window != (trace.t0, trace.t1):
        return None
    out = {}
    for name, rows in ops.items():
        busy = tracing.union(np.asarray(rows, np.float64).reshape(-1, 2))
        out[name] = float(np.sum(busy[:, 1] - busy[:, 0]))
    return out


def attach(trace: "tracing.Trace", path: str) -> Optional[Dict[str, float]]:
    """The per-device busy seconds of the run ``trace`` was read from,
    read from ``path`` and kept for the readers."""
    got = busy_by_device(trace, path)
    _ATTACHED[id(trace)] = (trace, got)
    return got


def of(ctx) -> Optional[Dict[str, float]]:
    """Per-device busy seconds beside a reader's trace: those attached to
    it, else those of the last traced run (``.tunerbench/trace``)."""
    got = _ATTACHED.get(id(ctx.trace))
    if got is not None and got[0] is ctx.trace:
        return got[1]
    path = spans.newest(spans.TRACE_DIR)
    return attach(ctx.trace, path) if path else None
