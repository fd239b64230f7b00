"""Benchmark harness: one module per paper table/figure + framework
deployment benches.  Prints ``name,us_per_call,derived`` CSV; ``--json``
additionally writes the rows as a JSON document (what CI uploads as the
perf-trajectory artifact) and refreshes the checked-in per-bench
baselines (``BENCH_<name>.json`` at the repo root, for the benches listed
in :data:`BASELINE_BENCHES`) so the perf trajectory is visible in-repo.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from repro.runtime.compile_cache import enable_compile_cache

from . import (bench_paper_table1, bench_matching, bench_streaming,
               bench_dtw, bench_wavelet, bench_autotune, bench_roofline)

BENCHES = {
    "paper_table1": bench_paper_table1.run,   # paper Table 1
    "matching": bench_matching.run,           # paper Fig. 4-b / §5
    "streaming": bench_streaming.run,         # online matching service
    "dtw": bench_dtw.run,                     # paper §3.1.2 scaling
    "wavelet": bench_wavelet.run,             # paper §5 future plan
    "autotune": bench_autotune.run,           # paper §4 end goal, on JAX
    "roofline": bench_roofline.run,           # dry-run aggregation
}

#: benches whose rows are checked in as BENCH_<name>.json baselines (the
#: matching-stack hot paths — the numbers PRs claim speedups against).
BASELINE_BENCHES = ("matching", "streaming")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None,
                    help="also write rows (+ failures) to this JSON file")
    args = ap.parse_args()
    enable_compile_cache()

    rows = []
    failed = []
    per_bench = {}
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        print(f"\n===== bench: {name} =====")
        try:
            bench_rows = fn()
            rows.extend(bench_rows)
            per_bench[name] = bench_rows
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failed.append((name, repr(e)))
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": [{"name": n, "us_per_call": us, "derived": d}
                                for n, us, d in rows],
                       "failed": [{"bench": n, "error": e}
                                  for n, e in failed]}, f, indent=1)
        for name in BASELINE_BENCHES:
            if name not in per_bench:
                continue
            path = os.path.join(_REPO_ROOT, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"bench": name,
                           "rows": [{"name": n, "us_per_call": us,
                                     "derived": d}
                                    for n, us, d in per_bench[name]]},
                          f, indent=1)
            print(f"[baseline] wrote {path}")
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
