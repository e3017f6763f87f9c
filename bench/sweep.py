#!/usr/bin/env python3
"""Find the knee of a server cell: the highest offered rate the engine
sustains without a growing backlog, in one process on the chip.

    python3 bench/sweep.py --workload <cell> --seed 3 --seconds 16

The cell's mix is an open-loop one (``"arrivals": "gamma"``); its own
``rate`` is not used.  One engine is set up by the configuration's system
module and warmed as ``run.py`` does.  A saturating window first measures
capacity; then open-loop windows of the cell's own mix (its arrival
process and CV) are offered at fractions of it.  The backlog
at a moment is the requests due by then less those answered by then; its
mean over each quarter of the window (read every 10 ms, which evens out
the phase of the batch in flight) is recorded.  A rate is sustained when
the last quarter's mean exceeds the second's by at most one batch (the
backlog does not grow) and no request failed; the knee is the highest
rate below which every rate tried was sustained, and a server cell is
written at 0.8 of it.  Prints one JSON line per window and a last line
with the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import loadgen
import run


def mean_backlog(win: loadgen.WindowResult, a: float, b: float) -> float:
    """Mean over [a, b) of the requests due and not yet answered."""
    due = np.sort([r.due for r in win.requests])
    done = np.sort([r.done for r in win.requests if r.ok])
    t = np.arange(a, b, 0.01)
    return float(np.mean(np.searchsorted(due, t, side="right")
                         - np.searchsorted(done, t, side="right")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=16.0)
    ap.add_argument("--fractions",
                    default="0.6,0.7,0.75,0.8,0.85,0.9,0.95,1.0",
                    help="offered rates as fractions of measured capacity")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        cell, _ = run.open_cell(run.HERE.parent, run.HERE, args.workload)
    except run.SetupError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    eng, payloads, _ = run.start_engine(cell, run.HERE, args.seed, t0)
    max_batch = int(cell.config["serve"]["max_batch"])
    rows = []
    with eng:
        run.warm_up(eng, payloads, max_batch)
        sat = loadgen.run_window(eng, payloads, {"arrivals": "saturate",
                                                 "queued_batches": 3},
                                 max_batch, args.seconds)
        capacity = sat.completed_between(sat.t0, sat.t0 + sat.seconds) \
            / sat.seconds
        print(json.dumps({"capacity_req_s": capacity}), flush=True)
        for f in (float(x) for x in args.fractions.split(",")):
            mix = dict(cell.mix, rate=f * capacity)
            win = loadgen.run_window(eng, payloads, mix, max_batch,
                                     args.seconds)
            due = win.due()
            done = win.completed_between(win.t0, win.t0 + win.seconds)
            lat = [(r.done - r.due) * 1e3 for r in due if r.ok]
            quarter = win.seconds / 4
            quarters = [mean_backlog(win, win.t0 + q * quarter,
                                     win.t0 + (q + 1) * quarter)
                        for q in range(4)]
            row = {"fraction": f, "offered_req_s": len(due) / win.seconds,
                   "completed_req_s": done / win.seconds,
                   "backlog_quarters": quarters,
                   "ttft_p50_ms": run.nearest_rank(lat, 0.5) if lat else None,
                   "ttft_p99_ms": run.nearest_rank(lat, 0.99) if lat else None,
                   "failed": sum(not r.ok for r in due)}
            row["sustained"] = (quarters[3] - quarters[1] <= max_batch
                                and row["failed"] == 0)
            rows.append(row)
            print(json.dumps(row), flush=True)
    knee = None
    for r in sorted(rows, key=lambda r: r["fraction"]):
        if not r["sustained"]:
            break
        knee = r["offered_req_s"]
    print(json.dumps({"knee_req_s": knee,
                      "cell_rate_req_s": 0.8 * knee if knee else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
