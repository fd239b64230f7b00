"""Deployments of the tuner: the reference bank and the in-flight jobs.

A configuration file (``configs/<name>.json``) fixes the cluster: the
applications, how many recurring Hadoop job configurations each has, the
ranges the configurations are drawn from, the bank size and the longest
trace.  Its ``layout_seed`` draws the configurations themselves, so every
run of a cell sees the same set of trace lengths; ``--seed`` draws the
runs (the noise, wave phases and spikes of every trace), the order in
which jobs arrive and, in the probabilistic deployments, the agents'
per-sample variances.  Nothing here imports JAX or the program.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from . import mrsim

#: runs of one seed are numbered from ``seed << RUN_SHIFT``: bank runs
#: first, in-flight runs from ``JOB_RUN0`` on.
RUN_SHIFT = 24
JOB_RUN0 = 1 << 16


@dataclasses.dataclass(frozen=True)
class Job:
    """One in-flight job as its node agents report it."""
    job_id: str
    app: str
    params: mrsim.JobParams
    recurring: bool
    run: int
    x: np.ndarray                    # raw 1 Hz CPU samples, float32 [n]
    v: Optional[np.ndarray] = None   # per-sample variances (uncertain agents)

    def __len__(self) -> int:
        return self.x.shape[0]


def hadoop_config(rng, cfg, app: str) -> mrsim.JobParams:
    """One Hadoop job configuration whose trace fits ``max_len`` (a
    trace's length is set by its configuration alone)."""
    lo_m, hi_m = cfg["mappers"]
    lo_r, hi_r = cfg["reducers"]
    lo_i, hi_i = cfg["input_mb"]
    while True:
        p = mrsim.JobParams(mappers=int(rng.integers(lo_m, hi_m + 1)),
                            reducers=int(rng.integers(lo_r, hi_r + 1)),
                            split_mb=int(rng.choice(cfg["split_mb"])),
                            input_mb=int(rng.integers(lo_i, hi_i + 1)))
        if len(mrsim.simulate_cpu_series(app, p, noise=0.0)) \
                <= cfg["max_len"]:
            return p


def layout(cfg) -> Dict[str, Dict[str, List[mrsim.JobParams]]]:
    """The deployment's job configurations, from ``layout_seed``:
    ``{"recurring": {app: [...]}, "fresh": {app: [...]}}``."""
    rng = np.random.default_rng(cfg["layout_seed"])
    rec = {app: [hadoop_config(rng, cfg, app)
                 for _ in range(cfg["configs_per_app"])]
           for app in cfg["apps"]}
    fresh = {app: [hadoop_config(rng, cfg, app)
                   for _ in range(cfg["fresh_per_app"])]
             for app in cfg["apps"]}
    return {"recurring": rec, "fresh": fresh}


def run_base(seed: int) -> int:
    return int(seed) << RUN_SHIFT


def profiled_runs(cfg, seed: int, lay=None):
    """The bank's ``bank_size`` profiled runs, round-robin over the
    recurring configurations -> list of (app, params, run, raw series)."""
    lay = lay or layout(cfg)
    pairs = [(app, p) for app in cfg["apps"] for p in lay["recurring"][app]]
    base = run_base(seed)
    out = []
    r = 0
    while len(out) < cfg["bank_size"]:
        for app, p in pairs[: cfg["bank_size"] - len(out)]:
            run = base + r
            out.append((app, p, run, mrsim.simulate_cpu_series(
                app, p, run=run, noise=cfg["noise"])))
        r += 1
    return out


def job_pool(cfg, seed: int, size: int, lay=None) -> List[Job]:
    """``size`` in-flight jobs in arrival order.  The multiset of
    configurations is the same for every seed (the recurring share cycles
    through the recurring configurations, the rest through the fresh
    ones); the seed permutes it and draws every run."""
    lay = lay or layout(cfg)
    apps = cfg["apps"]
    n_rec = int(round(size * cfg["recurring_share"]))
    kinds = []
    for i in range(size):
        app = apps[i % len(apps)]
        rec = i < n_rec
        pool = lay["recurring" if rec else "fresh"][app]
        kinds.append((app, pool[(i // len(apps)) % len(pool)], rec))
    order = np.random.default_rng([int(seed) & (2**63 - 1), 7]).permutation(
        size)
    base = run_base(seed) + JOB_RUN0
    jobs = []
    for n, idx in enumerate(order):
        app, p, rec = kinds[int(idx)]
        run = base + n
        if cfg["agents"] == "uncertain":
            x, v = mrsim.simulate_cpu_series_uncertain(app, p, run=run,
                                                       noise=cfg["noise"])
        else:
            x, v = mrsim.simulate_cpu_series(app, p, run=run,
                                             noise=cfg["noise"]), None
        jobs.append(Job(f"{app}-{n:05d}", app, p, rec, run, x, v))
    return jobs

