"""Faults planted under the timed path, and their readings on the chip.

Each fault takes the service after set-up and breaks one layer the
comparison has to cover:

* ``unchanged_state``: the tick returns the state it was given (its
  scores still come out of this tick's DP).
* ``half_batch``: the tick leaves the second half of the slots out.
* ``altered_answer``: the verdict call's answers come out raised by 0.1.
* ``gate_dropped``: early decisions are taken without the confidence
  gate (threshold or probability floor) and without the margin.
* ``runner_up``: early decisions and verdicts name the runner-up.

A cell's ``limits/<cell>.json`` names, under ``faults``, the faults its
compared numbers are held against.  The cells run on one chip, so there
is no exchange between chips to leave out.

    python3 tunerbench/faults.py --workload <cell> --faults <name> [...] \\
        --seeds <n> [<n> ...] [--seconds <s>]

runs, in one process on the chip, one window per fault and seed and
prints for each the numbers the comparison reads beside the cell's
limits.  The benchmark's own runs never plant a fault.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import check, spec  # noqa: E402


def _wrap_tick(svc, change):
    mode = svc._base_mode()
    fn, fallback = svc._tick_fn_for(mode)

    def broken(*args):
        return change(fn, args)
    svc._tick_fns[mode] = (broken, fallback)


def unchanged_state(svc):
    def change(fn, args):
        out = list(fn(*args))
        out[:5] = args[:5]
        if len(out) == 8:
            out[6] = args[5]
        return tuple(out)
    _wrap_tick(svc, change)


def half_batch(svc):
    import jax.numpy as jnp

    def change(fn, args):
        args = list(args)
        nvalid = np.asarray(args[-2]).copy()
        nvalid[len(nvalid) // 2:] = 0
        args[-2] = jnp.asarray(nvalid)
        return fn(*args)
    _wrap_tick(svc, change)


def altered_answer(svc):
    orig = svc._verdict_scores

    def broken(queries, variances=None):
        scores, probs = orig(queries, variances)
        return np.minimum(scores + 0.1, 1.0), probs
    svc._verdict_scores = broken


def gate_dropped(svc):
    orig = svc._maybe_decide

    def broken(job):
        saved = svc.threshold, svc.margin, svc.min_probability
        svc.threshold, svc.margin = -np.inf, -np.inf
        if svc.min_probability is not None:
            svc.min_probability = 0.0
        try:
            return orig(job)
        finally:
            svc.threshold, svc.margin, svc.min_probability = saved
    svc._maybe_decide = broken


def _second(d):
    """The decision with its match moved to the runner-up workload."""
    if d is None or d.matched is None:
        return d
    rest = [w for w in d.scores if w != d.matched]
    if not rest:
        return d
    return dataclasses.replace(d, matched=max(rest, key=d.scores.get))


def runner_up(svc):
    decide, render = svc._maybe_decide, svc._render_verdict
    svc._maybe_decide = lambda job: _second(decide(job))
    svc._render_verdict = lambda *a, **k: _second(render(*a, **k))


FAULTS = {f.__name__: f for f in (unchanged_state, half_batch,
                                  altered_answer, gate_dropped, runner_up)}


def main(argv=None) -> None:
    from tunerbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults", nargs="+", required=True,
                    choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    man = spec.manifest()
    lim = spec.limits(args.workload)
    seconds = args.seconds or man["run_seconds"]
    for fault in args.faults:
        for seed in args.seeds:
            sink = {}
            res = run.execute(args.workload, seed, seconds, False, sink=sink,
                              tamper=FAULTS[fault])
            gc.collect()
            ok, _ = check.judge(sink["numbers"], lim["limits"])
            print(json.dumps({"cell": args.workload, "fault": fault,
                              "seed": seed, "correct": res["correct"],
                              "limits_pass": ok,
                              "numbers": sink["numbers"]}), flush=True)


if __name__ == "__main__":
    main()
