"""A whole run of a cell at a small size on the CPU (the look for a chip
skipped): the sound program comes out correct, and each fault the cells
can have, planted under the timed path, comes out not correct — as does
the control, the reference in bfloat16 put in the program's place.

The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import itertools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tunerbench import check, control, faults, run, spec, traffic  # noqa: E402

MAN = spec.manifest(ROOT)
SEED = 2**31 + 1234


def small(cell):
    """The cell at a size a test run holds: 24 profiled runs of 6
    recurring configurations, 4 slots."""
    w = spec.cell(MAN, cell)
    cfg = spec.config(MAN, w["config"], ROOT)
    cfg = dict(cfg, bank_size=24, configs_per_app=2, fresh_per_app=2,
               service=dict(cfg["service"], slots=4))
    mix = dict(spec.mix(w["traffic"]), jobs_in_flight=4, pool=32,
               max_finish_batch=2)
    lim = spec.limits(cell)
    lim = dict(lim, sample=dict(snapshots=8, early=4, verdicts=4))
    return cfg, mix, lim


def compared_ok(res) -> bool:
    """Every compared number within its limit.  (On the CPU the banded
    verdict's jnp path compiles per batch shape inside the window, so the
    compile count, which the chip's runs hold to 0, is left out here.)"""
    return all(c["value"] <= c["limit"] for name, c in res["check"].items()
               if name != "compiles_in_window")


def _run(cell, monkeypatch, tamper=None, sink=None):
    """One run; the driver's clock steps 10 ms a reading, so the window
    is a fixed amount of work and the sample the same every time."""
    monkeypatch.setattr(run, "setup_jax", lambda: None)
    steps = itertools.count()
    monkeypatch.setattr(traffic, "clock", lambda: 0.01 * next(steps))
    cfg, mix, lim = small(cell)
    return run.execute(cell, SEED, 2.0, False, man=MAN, cfg=cfg, mix=mix,
                       limits=lim, chips=False, tamper=tamper, workers=1,
                       sink=sink)


#: the closed-loop cells: their window is a fixed amount of work under
#: the stepping clock
CELLS = [w["name"] for w in MAN["workloads"]
         if spec.mix(w["traffic"])["loop"] == "closed"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell, monkeypatch):
    sink = {}
    res = _run(cell, monkeypatch, sink=sink)
    assert compared_ok(res), res["check"]
    cfg, _, lim = small(cell)
    _, ctrl = control.readings(sink, cfg, workers=1)
    ok, rows = check.judge(ctrl, lim["limits"])
    print(cell, "program", res["check"], "control", rows)
    assert not ok, rows


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in CELLS for fault in spec.limits(cell)["faults"]])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    res = _run(cell, monkeypatch, tamper=faults.FAULTS[fault])
    assert not compared_ok(res), res["check"]


#: faults the cells' compared numbers are not yet held against (no reading
#: of them on the chip), and the number the comparison computes that each
#: moves: what a later limit would hold.
MOVES = {"half_batch": "inflight_gap_q75", "gate_dropped": "early_gap",
         "runner_up": "leader_gap"}


@pytest.mark.parametrize("fault", sorted(MOVES))
def test_fault_moves_its_number(fault, monkeypatch):
    sound, broken = {}, {}
    _run(CELLS[0], monkeypatch, sink=sound)
    _run(CELLS[0], monkeypatch, tamper=faults.FAULTS[fault], sink=broken)
    name = MOVES[fault]
    assert broken["numbers"][name] > sound["numbers"][name] + 0.01
