"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seconds 30 \\
        --seeds 11 12 ... --control-seeds 11 12 13

In one process (set-up is long): for every seed, one window of the cell's
traffic through the served path and its numbers against the reference (the
lower readings); for each control seed, the same queries answered by the
reference computed in bfloat16 (the control, whose numbers give the upper
readings).  One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--n", type=int, default=None,
                    help="rehearse at this many rows, on any device")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import harness
    import reference
    import spec

    cell = spec.load_cell(args.workload, ROOT)
    if args.n is not None:
        spec.shrink(cell, args.n)
    s = harness.Session(cell, root=ROOT, require_tpu=args.n is None)
    ref = reference.Reference(s.db, cell.config["metric"])
    k = cell.config["k"]
    print(json.dumps({"workload": cell.name, "setup_s":
                      time.perf_counter() - t_start}), flush=True)
    for seed in args.seeds:
        win = s.window(seed, args.seconds)
        end = harness.end_to_end(cell, {"window": win, "numbers": {
            "recall_at_10": 0.0}}, 0.0)
        v = harness.judge_window(win, ref, cell.config)
        print(json.dumps({"seed": seed, "side": "program",
                          "numbers": v["numbers"], "correct": v["correct"],
                          "compiles": s.compiles.count,
                          "requests": len(win.records),
                          "metrics": {m: x["value"] for m, x in end.items()
                                      if m != "recall_at_10"}}), flush=True)
        if seed in args.control_seeds:
            c = harness.judge_window(
                win, ref, cell.config,
                answers=lambda q: reference.control_topk(ref, q, k))
            print(json.dumps({"seed": seed, "side": "control",
                              "numbers": c["numbers"],
                              "correct": c["correct"]}), flush=True)
    s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
