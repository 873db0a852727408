#!/usr/bin/env python3
"""The readings that a cell's correctness limit is set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--seconds 3] [--first-seed N] [--faults]

In one process: the program's runs over ``--seeds`` seeds (the lower
reading is the largest ``max_rel_err`` they give), then the control's —
the family's plain reference at bf16x3 answering every request in the
program's place (its ``control`` hook) — over ``--control-seeds`` (the
upper reading is the smallest), and with ``--faults`` the planted faults of faults.py. Each run is a
whole run of the harness at the cell's size with a short window. Prints
one JSON line per run and a summary last. Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import faults  # noqa: E402
import run  # noqa: E402


def reading(spec, seed, seconds, label, hook=None, undo=None):
    """One run's ``max_rel_err``; None where the run crashed (a control
    or fault that crashes has failed and sets no upper reading)."""
    try:
        out = run.run_cell(spec, seed, seconds, False, on_engine=hook)
    except Exception as e:                           # noqa: BLE001
        print(json.dumps({"run": label, "seed": seed,
                          "error": repr(e)[:500]}), flush=True)
        return None
    finally:
        if undo is not None:
            undo()
    c = out["checks"]
    print(json.dumps({"run": label, "seed": seed, "correct": out["correct"],
                      "attempted": out["attempted"], "failed": out["failed"],
                      **{k: v["value"] for k, v in c.items()}}), flush=True)
    return c["max_rel_err"]["value"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    spec = run.cell_spec(run.ROOT, args.workload)
    run.setup_jax(run.ROOT)
    fam = spec["family"]
    arch = fam.arch(spec["config"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    prog = [reading(spec, s, args.seconds, "program") for s in seeds]
    ctrl = [reading(spec, s, args.seconds, "control", fam.control(arch))
            for s in seeds[:args.control_seeds]]
    def ok(xs):
        return [x for x in xs if x is not None]

    summary = {"workload": args.workload,
               "lower": max(ok(prog), default=None),
               "upper": min(ok(ctrl), default=None), "program": prog,
               "control": ctrl}
    if args.faults:
        s = seeds[0]
        hook, undo = faults.drop_tap()
        summary["faults"] = {
            "alter_answer": reading(spec, s, args.seconds, "alter_answer",
                                    faults.alter_answer()),
            "swap_answers": reading(spec, s, args.seconds, "swap_answers",
                                    faults.swap_answers),
            "drop_tap": reading(spec, s, args.seconds, "drop_tap", hook,
                                undo),
        }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
