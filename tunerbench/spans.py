#!/usr/bin/env python3
"""The program's own spans (``tuner.*``) in a traced run.

The service opens ``jax.profiler.TraceAnnotation`` spans around the
phases of its tick (``tuner.tick`` holding ``tuner.drain`` with one
``tuner.filter`` per causal-filter call, ``tuner.repack``,
``tuner.chunks``, ``tuner.dispatch``, ``tuner.pull``, ``tuner.decide``,
``tuner.prefilter``) and of its verdicts (``tuner.finish_many``,
``tuner.retire``, ``tuner.verdict.*``), with counts as arguments.  They
lie on the profiler's host timeline, on the clock of the device's ops.
``tracing.Trace`` keeps the benchmark's own spans; this module reads the
same ``.xplane.pb`` again for the program's and splits the device-idle
time of the window by them.  A program without these spans gives an
empty list, and the readers built on it read nothing.

    python3 tunerbench/spans.py [<trace dir or .xplane.pb>]

prints, for the window of the trace (by default the last traced run's,
``.tunerbench/trace``), one line per span name: count, total ms, self ms
(duration less its direct children) and device-idle ms inside; then the
device-idle time inside ``bench.tick`` that no child of ``tuner.tick``
covers, per tick; then the longest idle gaps, each named by the
innermost span among ``bench.*`` and ``tuner.*``.
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import tracing  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".tunerbench", "trace")
PREFIX = "tuner."

Span = Tuple[float, float, str, Dict[str, object]]


def newest(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


class ProgramSpans:
    """The ``tuner.*`` spans of one traced run that overlap its window,
    as ``(start, end, name, args)`` in seconds, with their nesting."""

    def __init__(self, trace: "tracing.Trace", path: str):
        from jax.profiler import ProfileData
        self.trace = trace
        spans: List[Span] = []
        line_of: List[int] = []
        window = None
        lines = 0
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                if plane.name.startswith("/device:"):
                    continue
                for line in plane.lines:
                    lines += 1
                    for ev in line.events:
                        if ev.name == "bench.window":
                            window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                        elif ev.name.startswith(PREFIX) and \
                                ev.end_ns * 1e-9 > trace.t0 and \
                                ev.start_ns * 1e-9 < trace.t1:
                            spans.append((ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9, ev.name,
                                          dict(ev.stats)))
                            line_of.append(lines)
        #: whether the file is the one ``trace`` was read from
        self.matches = window == (trace.t0, trace.t1)
        order = sorted(range(len(spans)),
                       key=lambda i: (line_of[i], spans[i][0], -spans[i][1]))
        self.spans = [spans[i] for i in order]
        #: index of each span's direct parent among ``spans`` (-1: none)
        self.parent = []
        stack: List[int] = []
        for i, j in enumerate(order):
            s = self.spans[i][0]
            while stack and (line_of[order[stack[-1]]] != line_of[j]
                             or s >= self.spans[stack[-1]][1]):
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)
        busy = trace.busy
        self._busy_starts = busy[:, 0]
        self._busy_cum = np.concatenate(
            [[0.0], np.cumsum(busy[:, 1] - busy[:, 0])])
        ticks = sorted(trace.span_list("bench.tick"))
        self._tick_starts = [s for s, _ in ticks]
        self._ticks = ticks

    # -- what the readers read -------------------------------------------------
    @property
    def n_ticks(self) -> int:
        return len(self._ticks)

    def in_ticks(self, name: str) -> List[Span]:
        """``name`` spans that start inside a ``bench.tick`` span."""
        out = []
        for sp in self.spans:
            if sp[2] != name:
                continue
            i = bisect.bisect_right(self._tick_starts, sp[0]) - 1
            if i >= 0 and sp[0] < self._ticks[i][1]:
                out.append(sp)
        return out

    def ms_per_tick(self, name: str) -> Optional[float]:
        """Wall time of the ``name`` spans inside the benchmark's ticks,
        per tick, in ms."""
        sel = self.in_ticks(name)
        if not sel or not self.n_ticks:
            return None
        return 1e3 * sum(e - s for s, e, _, _ in sel) / self.n_ticks

    def arg_per_tick(self, name: str, arg: str) -> Optional[float]:
        """The ``arg`` of the ``name`` spans inside the benchmark's ticks,
        summed, per tick."""
        sel = [sp for sp in self.in_ticks(name) if arg in sp[3]]
        if not sel or not self.n_ticks:
            return None
        return sum(float(sp[3][arg]) for sp in sel) / self.n_ticks

    # -- the breakdown ---------------------------------------------------------
    def _busy_until(self, t: float) -> float:
        """Device-busy seconds of the window before ``t``."""
        i = int(np.searchsorted(self._busy_starts, t, side="right")) - 1
        if i < 0:
            return 0.0
        s, e = self.trace.busy[i]
        return float(self._busy_cum[i] + min(t, e) - s)

    def idle(self, s: float, e: float) -> float:
        """Device-idle seconds in [s, e) clipped to the window."""
        s, e = max(s, self.trace.t0), min(e, self.trace.t1)
        if e <= s:
            return 0.0
        return (e - s) - (self._busy_until(e) - self._busy_until(s))

    def table(self) -> List[Tuple[str, int, float, float, float]]:
        """(name, count, total s, self s, device-idle s) per span name,
        by self time."""
        child = [0.0] * len(self.spans)
        for i, p in enumerate(self.parent):
            if p >= 0:
                child[p] += self.spans[i][1] - self.spans[i][0]
        rows: Dict[str, List[float]] = {}
        for i, (s, e, n, _) in enumerate(self.spans):
            r = rows.setdefault(n, [0, 0.0, 0.0, 0.0])
            r[0] += 1
            r[1] += e - s
            r[2] += (e - s) - child[i]
            r[3] += self.idle(s, e)
        return sorted(((n, int(r[0]), r[1], r[2], r[3])
                       for n, r in rows.items()), key=lambda r: -r[2])

    def unattributed_idle_s(self) -> Optional[float]:
        """Device-idle seconds inside ``bench.tick`` spans that fall in no
        direct child of a ``tuner.tick`` span, over the whole window; None
        without ``tuner.tick`` spans inside the ticks."""
        kids: List[Tuple[float, float]] = []
        top = {i for i, sp in enumerate(self.spans) if sp[2] == "tuner.tick"}
        if not top:
            return None
        for i, p in enumerate(self.parent):
            if p in top:
                kids.append(self.spans[i][:2])
        total = 0.0
        for ts, te in self._ticks:
            total += self.idle(ts, te)
            for s, e in kids:
                if s < te and e > ts:
                    total -= self.idle(max(s, ts), min(e, te))
        return total

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The longest idle stretches of the device in the window, each
        named by the innermost span, among the benchmark's and the
        program's together, that the host was in at its middle."""
        tr = self.trace
        named = [(s, e, n) for s, e, n in tr.spans] + \
            [(s, e, n) for s, e, n, _ in self.spans]
        edges = [tr.t0] + [float(x) for x in tr.busy.ravel()] + [tr.t1]
        gaps = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2])
                       if e > s), key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            inner = [(se - ss, n) for ss, se, n in named if ss <= mid < se]
            out.append([min(inner)[1] if inner else "idle", e - s])
        return out


_ATTACHED: Dict[int, Tuple["tracing.Trace", Optional[ProgramSpans]]] = {}


def attach(trace: "tracing.Trace", path: str) -> Optional[ProgramSpans]:
    """The program spans of the run ``trace`` was read from, read from
    ``path``; None when ``path`` holds another run."""
    got = ProgramSpans(trace, path)
    got = got if got.matches else None
    _ATTACHED[id(trace)] = (trace, got)
    return got


def of(ctx) -> Optional[ProgramSpans]:
    """The program spans beside a reader's trace: those attached to it,
    else those of the last traced run (``.tunerbench/trace``, where
    ``run.py`` writes it)."""
    got = _ATTACHED.get(id(ctx.trace))
    if got is not None and got[0] is ctx.trace:
        return got[1]
    path = newest(TRACE_DIR)
    return attach(ctx.trace, path) if path else None


def report(prog: ProgramSpans, tick_host_ms: Optional[float]) -> List[str]:
    lines = ["program spans (count, total ms, self ms, device-idle ms "
             "inside):"]
    for n, c, tot, own, idle in prog.table():
        lines.append(f"  {n} {c} {1e3 * tot:.3f} {1e3 * own:.3f} "
                     f"{1e3 * idle:.3f}")
    un = prog.unattributed_idle_s()
    if un is not None and prog.n_ticks:
        per = 1e3 * un / prog.n_ticks
        share = f", {100 * per / tick_host_ms:.2f}% of tick_host_ms " \
            f"{tick_host_ms:.3f}" if tick_host_ms else ""
        lines.append(f"unattributed idle inside bench.tick: {per:.3f} ms "
                     f"a tick over {prog.n_ticks} ticks{share}")
    lines.append("longest idle gaps: " + ", ".join(
        f"{n} {1e3 * t:.3f} ms" for n, t in prog.idle_gaps()))
    return lines


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    where = argv[0] if argv else TRACE_DIR
    path = where if where.endswith(".xplane.pb") else newest(where)
    if path is None:
        sys.exit(f"spans: no trace under {where}")
    tr = tracing.Trace(path)
    prog = ProgramSpans(tr, path)
    ticks = len(tr.span_list("bench.tick"))
    host = 1e3 * tr.idle_inside("bench.tick") / ticks if ticks else None
    for line in report(prog, host):
        print(line, flush=True)


if __name__ == "__main__":
    main()
