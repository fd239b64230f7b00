"""``BENCHMARK.json`` and every file it names hold to the benchmark's
contract: names, units, keys, the files found by name, and which cells
report which metrics."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import faults, spec  # noqa: E402

MAN = spec.manifest(ROOT)
LINE = re.compile(r"^[^\t\n\r]{1,200}$")
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "tunerbench/run.py"]
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in MAN["command"]:
        assert LINE.match(word)
    assert isinstance(MAN["run_seconds"], int)
    assert 1 <= MAN["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(MAN)) <= 64 * 1024


def test_names_units_and_entry_keys():
    names = []
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert spec.NAME.match(c["name"]) and LINE.match(c["source"])
        assert LINE.match(c["why"]) and len(c["reduced"]) <= 16
        assert all(spec.NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("tunerbench/")
        names.append(c["name"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert spec.NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert LINE.match(w["why"])
        names.append(w["name"])
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert spec.UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                              "higher")
        names.append(m["name"])
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
    assert all(spec.NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_pairs_unique_and_every_config_keeps_a_cell():
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_layers_move(cell):
    e2e = {m["name"] for m in spec.end_to_end(MAN, cell)}
    layers = spec.per_layer(MAN, cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    for m in layers:
        assert m["moves"] in e2e, (cell, m["name"])
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_files_found_by_name(cell):
    w = spec.cell(MAN, cell)
    cfg = spec.config(MAN, w["config"], ROOT)
    mix = spec.mix(w["traffic"])
    lim = spec.limits(cell)
    assert cfg["name"] == w["config"] and mix["loop"] in ("closed", "open")
    assert set(lim) == {"sample", "limits", "faults"}
    assert set(lim["faults"]) <= set(faults.FAULTS)
    for m in spec.per_layer(MAN, cell):
        assert callable(spec.reader(m["name"]).read)
    for kernel in ("dtw_stream_scored", "dtw_score_offline"):
        assert callable(spec.work(kernel).count)


def test_layer_names_agree():
    layers = {}
    for m in MAN["per_layer"]:
        base = m["name"].split(".")[0]
        layers.setdefault(base, set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_peaks_keyed_by_device_kind():
    peaks = spec.peaks()
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["source"]
