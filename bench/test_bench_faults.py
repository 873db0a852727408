"""``correct`` comes out false when the served path is broken underneath
a run, and when the control answers in the program's place.

Whole runs of the harness on the CPU (its look for a chip skipped), at a
small size: a tiny model for the planted faults, each configuration's
real widths for the control.
"""
from __future__ import annotations

import pytest

import faults
import run
import testkit

SEED = 2**32 + 12345


def _run(spec, hook=None):
    return run.run_cell(spec, SEED, 1.0, False, require_chip=False,
                        on_engine=hook)


def test_sound_run_is_correct():
    out = _run(testkit.tiny_spec())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 4


@pytest.mark.parametrize("fault", ["alter_answer", "swap_answers",
                                   "drop_tap"])
def test_planted_fault_is_not_correct(fault):
    undo = None
    if fault == "alter_answer":
        hook = faults.alter_answer()
    elif fault == "swap_answers":
        hook = faults.swap_answers
    else:
        hook, undo = faults.drop_tap()
    try:
        out = _run(testkit.tiny_spec(), hook)
    finally:
        if undo is not None:
            undo()
    c = out["checks"]["max_rel_err"]
    assert not out["correct"] and c["value"] > c["limit"], c


@pytest.mark.parametrize("workload", ["scannet.fresh-c4",
                                      "semkitti.fresh-c4"])
def test_control_is_not_correct(workload):
    spec = run.cell_spec(run.ROOT, workload)
    spec["traffic"] = dict(spec["traffic"], **testkit.TINY_TRAFFIC,
                           generator="indoor")
    fam = spec["family"]
    out = _run(spec, fam.control(fam.arch(spec["config"])))
    c = out["checks"]["max_rel_err"]
    assert not out["correct"] and c["value"] > c["limit"], c
