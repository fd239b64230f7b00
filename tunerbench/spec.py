"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``mixes/<traffic>.json``); its comparison limits are
``limits/<cell>.json``; a per-layer metric is read by
``metrics/<metric>.py``; a kernel's operations and bytes are counted by
``work/<kernel>.py``; peaks are ``peaks.json`` keyed by ``device_kind``.
Adding a cell or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> Dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(man: Dict, name: str) -> Dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def config(man: Dict, name: str, root: str = ROOT) -> Dict:
    for c in man["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def mix(name: str) -> Dict:
    return _json(os.path.join(HERE, "mixes", f"{name}.json"))


def limits(cell_name: str) -> Dict:
    return _json(os.path.join(HERE, "limits", f"{cell_name}.json"))


def peaks() -> Dict:
    return _json(os.path.join(HERE, "peaks.json"))


def reports(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(man: Dict, cell_name: str) -> List[Dict]:
    return [m for m in man["end_to_end"] if reports(m, cell_name)]


def per_layer(man: Dict, cell_name: str) -> List[Dict]:
    return [m for m in man["per_layer"] if reports(m, cell_name)]


def _module(path: str, label: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    sp = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric_name: str):
    """The reader of one per-layer metric: ``read(ctx)`` -> value or
    None when the run gave it nothing to read.  A metric split by a
    suffix (``tick_host_ms.live``) is read by ``metrics/<name>.py`` where
    that exists, else by the quantity's own ``metrics/tick_host_ms.py``."""
    path = os.path.join(HERE, "metrics", f"{metric_name}.py")
    if not os.path.exists(path) and "." in metric_name:
        path = os.path.join(HERE, "metrics",
                            f"{metric_name.rsplit('.', 1)[0]}.py")
    return _module(path, "tunerbench_metric_" + metric_name.replace(".", "_"))


def work(kernel: str):
    """The work count of one kernel: ``count(...)`` -> (ops, bytes)."""
    return _module(os.path.join(HERE, "work", f"{kernel}.py"),
                   "tunerbench_work_" + kernel)
