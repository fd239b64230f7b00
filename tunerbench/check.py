"""The comparison that decides ``correct``.

After the window has closed, a sample drawn from the seed of what the
timed window produced — in-flight scores of one job at each tick, early
decisions and verdicts, always with the longest verdict in it — is
answered again by the plain reference (``reference.py``, float64) from
the raw samples the agents pushed and the raw profiled runs of the bank.
Each kind of gap below is taken per item, and a cell's limits
(``limits/<cell>.json``) name which are compared: ``<kind>_gap``, the
widest over the sample, ``<kind>_gap_q75``, its upper quartile, or
``<kind>_gap_median``, the median item's.  Per-workload values are the
best over that workload's bank rows, as the service ranks them.

* ``inflight``: in-flight score of a workload, program against
  reference (snapshots and early decisions).
* ``leader``: how far the workload a decision names lies below the
  reference's best (early decisions and verdicts): its match, else the
  program's best score.
* ``verdict``: final score of a workload.
* ``match``: where the program's verdict matches and the reference's
  would not (or the other way round), how far the reference's value lies
  from the gate; 0 when they agree.
* ``early``: how far the reference falls short of the gates an early
  decision claims to have passed: its leader, the threshold (or the
  probability floor) and the margin over the runner-up.
* ``inflight_prob`` / ``verdict_prob`` (probabilistic
  deployments): match probability of a workload in flight, and of the
  verdict's leader.
"""

from __future__ import annotations

import multiprocessing
import os
from typing import Dict, List, Optional

import numpy as np

from . import reference as R


_BANK: Optional[R.Bank] = None


def _init(bank):
    global _BANK
    _BANK = bank


def _pool_task(args):
    (x, v, kw) = args
    return R.answer(x, v, _BANK, **kw)


def answers(items, bank: R.Bank, cfg: Dict, prec: R.Precision = R.FLOAT64,
            workers: Optional[int] = None) -> List[Dict]:
    """The reference's answer to every sampled item, in parallel worker
    processes (numpy only: they never touch the chip)."""
    sv = cfg["service"]
    prob_tail = sv.get("prob_mode", "exact") \
        if sv.get("min_probability") is not None else None
    jobs = []
    for it in items:
        final = it["kind"] == "verdict"
        kw = dict(n=it["n"], qlen=len(it["job"]), band=sv.get("band"),
                  final=final, threshold=sv.get("threshold", 0.9),
                  prob=None if prob_tail is None else
                  ("exact" if final else prob_tail), prec=prec)
        jobs.append((it["job"].x, it["job"].v, kw))
    if workers is None:
        workers = min(len(jobs), max(1, (os.cpu_count() or 2) - 2), 12)
    if workers <= 1:
        _init(bank)
        return [_pool_task(j) for j in jobs]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers, initializer=_init, initargs=(bank,)) as pool:
        return pool.map(_pool_task, jobs, chunksize=1)


def sample(rec, seed: int, plan: Dict) -> List[Dict]:
    """Items to compare, drawn from the seed: ``plan`` gives how many
    snapshots, early decisions and verdicts; the longest verdict is
    always among them."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 13])

    def pick(seq, k):
        k = min(k, len(seq))
        return [seq[i] for i in sorted(rng.choice(len(seq), k,
                                                  replace=False))]
    items = []
    for job, n, sims, probs in pick(rec.snapshots, plan["snapshots"]):
        items.append(dict(kind="snapshot", job=job, n=n, sims=sims,
                          probs=probs))
    for job, n, d in pick(rec.early, plan["early"]):
        items.append(dict(kind="early", job=job, n=n, decision=d))
    if rec.verdicts:
        longest = max(range(len(rec.verdicts)),
                      key=lambda i: len(rec.verdicts[i][0]))
        rest = [v for i, v in enumerate(rec.verdicts) if i != longest]
        for job, d in [rec.verdicts[longest]] + pick(rest,
                                                     plan["verdicts"] - 1):
            items.append(dict(kind="verdict", job=job, n=len(job),
                              decision=d))
    return items


def _reduce(values, labels, finite=None) -> Dict[str, float]:
    return R.reduce(values, labels, floor=-1.0, finite=finite)


def _leader(scores: Dict[str, float]) -> str:
    """The service's ranking: first strict best in workload order."""
    best, lead = -np.inf, None
    for w, s in scores.items():
        if s > best:
            best, lead = s, w
    return lead


def gaps(items, refs, bank: R.Bank, cfg: Dict) -> Dict[str, List]:
    """Per-item gaps, program against reference, by kind -> {name:
    [(gap, item index, workload), ...]}."""
    sv = cfg["service"]
    prob = sv.get("min_probability") is not None
    thr = sv.get("threshold", 0.9)
    gate = sv["min_probability"] if prob else thr
    margin = sv.get("margin", 0.02)
    out: Dict[str, List] = {k: [] for k in (
        "inflight", "leader", "verdict", "match", "early")}
    if prob:
        out.update(inflight_prob=[], verdict_prob=[])
    labels = bank.labels
    for i, (it, ref) in enumerate(zip(items, refs)):
        rs = _reduce(ref["scores"], labels, ref["finite"])
        rp = _reduce(ref["probs"], labels, ref["finite"]) if prob else None
        best = max(rs.values())
        if it["kind"] == "snapshot":
            ps = _reduce(it["sims"], labels)
            pp = _reduce(it["probs"], labels) if prob else None
        else:
            d = it["decision"]
            ps = dict(d.scores)
            pp = None
        lead = _leader(ps)
        if it["kind"] != "snapshot":
            if d.matched is not None:
                lead = d.matched
            out["leader"].append((best - rs[lead], i, lead))
        w = max(rs, key=lambda w: abs(ps[w] - rs[w]))
        gap = (abs(ps[w] - rs[w]), i, w)
        if it["kind"] == "verdict":
            out["verdict"].append(gap)
            ref_val = rp[lead] if prob else rs[lead]
            miss = (d.matched is not None) != (ref_val >= gate)
            out["match"].append((abs(ref_val - gate) if miss else 0.0, i,
                                 lead))
            if prob:
                out["verdict_prob"].append(
                    (abs(float(d.probability) - rp[lead]), i, lead))
            continue
        out["inflight"].append(gap)
        if pp is not None:
            w = max(rp, key=lambda w: abs(pp[w] - rp[w]))
            out["inflight_prob"].append((abs(pp[w] - rp[w]), i, w))
        if it["kind"] == "early":
            ref_val = rp[lead] if prob else rs[lead]
            runner = max((s for w, s in rs.items() if w != lead),
                         default=-1.0)
            short = max(best - rs[lead], gate - ref_val,
                        margin - (rs[lead] - runner), 0.0)
            out["early"].append((short, i, lead))
    return out


def numbers(items, refs, bank: R.Bank, cfg: Dict) -> Dict[str, float]:
    """Every candidate number of a cell: ``<kind>_gap``, the widest gap
    over the sample, ``<kind>_gap_q75``, the upper quartile of the items'
    gaps, and ``<kind>_gap_median``, the median item's."""
    out = {}
    for kind, rows in gaps(items, refs, bank, cfg).items():
        vals = [g for g, _, _ in rows] or [0.0]
        out[f"{kind}_gap"] = float(max(vals))
        out[f"{kind}_gap_q75"] = float(np.percentile(vals, 75))
        out[f"{kind}_gap_median"] = float(np.median(vals))
    return out


def worst(items, refs, bank: R.Bank, cfg: Dict) -> List[str]:
    """A line for the widest gap of each kind: which item, where."""
    lines = []
    for kind, rows in gaps(items, refs, bank, cfg).items():
        if not rows:
            continue
        g, i, w = max(rows)
        it = items[i]
        lines.append(f"{kind}: {g:.6g} at {it['kind']} {it['job'].job_id} "
                     f"n={it['n']} of {len(it['job'])} ({w})")
    return lines


def judge(nums: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, [(name, value, limit), ...]) over the numbers the
    cell's limits name; a named number the run did not produce fails."""
    rows = []
    ok = True
    for name, lim in limits.items():
        value = float(nums.get(name, np.nan))
        good = np.isfinite(value) and value <= lim
        ok &= bool(good)
        rows.append((name, value, float(lim)))
    return ok, rows
