"""The control of the comparison, and the readings its limits rest on.

The control is the reference put in the program's place one precision
below what the configuration states: the configurations compute in
float32, so the control computes each step in float32 and stores its
inputs (the bank the program is given, the samples), its DP rows and its
filtered series in bfloat16 (``reference.BF16_STATE``) — the step that
would tempt a later change to halve the state's bytes.
It answers the same sampled items as the program did, and ``check``
compares those answers with the float64 reference exactly as it compares
the program's.  The control has to come out not correct.

    python3 tunerbench/control.py --workload <cell> --seeds <n> [<n> ...] \\
        [--seconds <s>]

runs, in one process on the chip, one window per seed, and prints for
each seed the program's numbers and the control's beside the cell's
limits.  The benchmark's own runs never run it.
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

from tunerbench import check, reference as R, spec  # noqa: E402


@dataclasses.dataclass
class Verdict:
    """What the service answers, as the control would answer it."""
    scores: dict
    matched: object
    probability: object


def control_items(items, answers, bank: R.Bank, cfg):
    """The sampled items with the program's outputs replaced by the
    control's ``answers`` to them."""
    sv = cfg["service"]
    prob = sv.get("min_probability") is not None
    gate = sv["min_probability"] if prob else sv.get("threshold", 0.9)
    out = []
    for it, a in zip(items, answers):
        scores = R.reduce(np.nan_to_num(a["scores"], nan=-1.0), bank.labels)
        probs = R.reduce(np.nan_to_num(a["probs"], nan=0.0), bank.labels) \
            if prob else None
        if it["kind"] == "snapshot":
            out.append(dict(it, sims=np.nan_to_num(a["scores"], nan=-1.0),
                            probs=None if not prob else
                            np.nan_to_num(a["probs"], nan=0.0)))
            continue
        lead = check._leader(scores)
        val = probs[lead] if prob else scores[lead]
        out.append(dict(it, decision=Verdict(
            scores, lead if val >= gate else None,
            None if not prob else probs[lead])))
    return out


def readings(sink, cfg, workers=None):
    """-> (program numbers, control numbers) of one run's sample."""
    items, bank = sink["items"], sink["bank"]
    ans = check.answers(items, bank, cfg, prec=R.BF16_STATE,
                        workers=workers)
    ctrl = check.numbers(control_items(items, ans, bank, cfg), sink["refs"],
                         bank, cfg)
    return sink["numbers"], ctrl



def main(argv=None) -> None:
    from tunerbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    man = spec.manifest()
    cell = spec.cell(man, args.workload)
    cfg = spec.config(man, cell["config"])
    lim = spec.limits(args.workload)
    seconds = args.seconds or man["run_seconds"]
    for seed in args.seeds:
        sink = {}
        res = run.execute(args.workload, seed, seconds, False, sink=sink)
        gc.collect()
        prog, ctrl = readings(sink, cfg)
        ok_ctrl, _ = check.judge(ctrl, lim["limits"])
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "correct": res["correct"], "program": prog,
                          "control": ctrl, "control_correct": ok_ctrl,
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
