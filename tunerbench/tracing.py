"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The benchmark's own host spans (``bench.window`` around the measured
window, ``bench.push``, ``bench.tick``, ``bench.finish_many`` and
``bench.generate`` around the calls into the service) sit on the same
clock as the device's ``XLA Ops`` and ``XLA Modules`` lines.  Busy time
is the union of the ``XLA Ops`` intervals inside the window; a kernel's
time is the sum of its events' durations there (a Pallas kernel's op is
named after the kernel); a program's time is the sum of its events on
``XLA Modules``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%dtw_stream_scored.1 = (f32[...]) custom-call(...)`` ->
    ``dtw_stream_scored``; ``jit_f(123)`` -> ``jit_f``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    head = head.split("(", 1)[0]
    return _SUFFIX.sub("", head)


def union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of [start, end) rows."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def overlap(busy: np.ndarray, s: float, e: float) -> float:
    """Length of [s, e) covered by the disjoint sorted ``busy``."""
    if not len(busy):
        return 0.0
    lo = np.clip(busy[:, 0], s, e)
    hi = np.clip(busy[:, 1], s, e)
    return float(np.sum(hi - lo))


class Trace:
    """One traced run, times in seconds."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops: List[Tuple[float, float, str]] = []
        mods: List[Tuple[float, float, str]] = []
        spans: List[Tuple[float, float, str]] = []
        self.devices = 0
        for plane in pd.planes:
            device = plane.name.startswith("/device:TPU:")
            self.devices += device
            for line in plane.lines:
                if device and line.name in ("XLA Ops", "XLA Modules"):
                    dst = ops if line.name == "XLA Ops" else mods
                    for ev in line.events:
                        dst.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                    op_name(ev.name)))
                elif not device:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            spans.append((ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9, ev.name))
        win = [s for s in spans if s[2] == "bench.window"]
        if not win:
            raise ValueError(f"{path}: no bench.window span")
        self.t0, self.t1 = win[0][0], win[0][1]
        self.ops = [o for o in ops if o[1] > self.t0 and o[0] < self.t1]
        self.modules = [m for m in mods if m[1] > self.t0 and m[0] < self.t1]
        self.spans = [s for s in spans if s[2] != "bench.window"]
        iv = np.asarray([(max(s, self.t0), min(e, self.t1))
                         for s, e, _ in self.ops], np.float64).reshape(-1, 2)
        self.busy = union(iv)
        self.devices = max(self.devices, 1)

    @classmethod
    def find(cls, log_dir: str) -> "Trace":
        files = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no trace under {log_dir}")
        return cls(files[-1])

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return float(np.sum(self.busy[:, 1] - self.busy[:, 0])) \
            / self.devices

    def op_s(self, name: str) -> float:
        return sum(e - s for s, e, n in self.ops if n == name)

    def module_s(self, prefix: str) -> Tuple[float, int]:
        sel = [(s, e) for s, e, n in self.modules if n.startswith(prefix)]
        return sum(e - s for s, e in sel), len(sel)

    def span_list(self, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for s, e, n in self.spans if n == name]

    def idle_inside(self, name: str) -> float:
        """Seconds inside ``name`` spans in which the device was idle."""
        return sum((e - s) - overlap(self.busy, s, e)
                   for s, e in self.span_list(name))

    def top_ops(self, k: int = 10) -> List[list]:
        agg: Dict[str, float] = {}
        for s, e, n in self.ops:
            agg[n] = agg.get(n, 0.0) + (e - s)
        return [[n, t] for n, t in sorted(agg.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The longest idle stretches of the device in the window, each
        named by the innermost benchmark span the host was in at its
        middle (``idle`` outside any)."""
        edges = np.concatenate([[self.t0], self.busy.ravel(), [self.t1]])
        gaps = edges.reshape(-1, 2)
        out = []
        for s, e in gaps:
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            inner = [(se - ss, n) for ss, se, n in self.spans
                     if ss <= mid < se]
            out.append([min(inner)[1] if inner else "idle", float(e - s)])
        out.sort(key=lambda g: -g[1])
        return out[:k]


class Context:
    """What a per-layer metric's reader sees: the trace, the work the
    window did while it was traced, the deployment and the peaks."""

    def __init__(self, trace: Trace, rec, bank_lengths, cfg, peak,
                 work_of):
        self.trace = trace
        self.rec = rec
        self.bank_lengths = np.asarray(bank_lengths, np.int64)
        self.cfg = cfg
        self.peak = peak
        self.work = work_of

    def share_of_roofline(self, ops: float, nbytes: float,
                          seconds: float) -> Optional[float]:
        """100 x (least time the chip could take) / ``seconds``, or None
        when there is nothing to read."""
        if seconds <= 0 or (ops <= 0 and nbytes <= 0):
            return None
        least = max(ops / self.peak["flops_per_s"],
                    nbytes / self.peak["bytes_per_s"])
        return 100.0 * least / seconds
