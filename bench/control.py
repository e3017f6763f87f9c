#!/usr/bin/env python3
"""Readings that set a cell's limit, in one process on the chip.

    python3 bench/control.py --workload resnet50.offline --seconds 3 \\
        --seeds 1,2,3 --control-seeds 4,5,6

Each seed is one whole run of the cell as ``run.py`` makes it (set-up from
the seed, the cell's own mix for ``--seconds``, the seeded sample of
served requests, the check).  For a control seed the control takes the
program's place before the check: the system's reference one precision
step below the configuration's (``control_precision``; bf16_3x for the
conv graphs' ``float32, highest``) on the same sampled requests, judged
by the same ``run.judge`` and the system's ``gap`` as every run.  One
JSON line per seed gives ``correct`` and the numbers compared; the last
line gives the lower reading (the largest the program gives), the upper
(the smallest the control gives), and whether every control run read
``correct`` false.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    args = ap.parse_args(argv)
    try:
        cell = run.load_cell(run.HERE.parent, run.HERE, args.workload)
        name, _ = run.check_limit(cell.config)
    except run.SetupError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    readings = {"program": [], "control": []}
    verdicts = {"program": [], "control": []}
    for seed, control in ([(s, False) for s in args.seeds]
                          + [(s, True) for s in args.control_seeds]):
        try:
            out = run.run_cell(run.HERE.parent, run.HERE, args.workload, seed,
                               args.seconds, False, control=control,
                               t_start=time.perf_counter())
        except run.SetupError as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        side = "control" if control else "program"
        readings[side].append(out["check"][name]["value"])
        verdicts[side].append(out["correct"])
        print(json.dumps({"seed": seed, "side": side,
                          "correct": out["correct"], "check": out["check"]}),
              flush=True)
    lower = max(readings["program"], default=None)
    upper = min(readings["control"], default=None)
    print(json.dumps({
        "workload": args.workload, "lower": lower, "upper": upper,
        "ratio": upper / lower if lower and upper else None,
        "program_all_correct": all(verdicts["program"]),
        "control_all_incorrect": not any(verdicts["control"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
