"""The open-loop knee of a cell: the highest rate with no growing backlog.

    python3 bench/sweep.py --workload <cell> --seconds 20 --rates 4 6 8 10

In one process, one window at each rate of the cell's traffic (its rate
replaced), printing per rate the latency median and 95th percentile, the
rate answered, and the backlog's growth: the mean latency of the last
quarter of requests less that of the first quarter.  Run once, when a cell
is made; the cell's traffic file then fixes its rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--n", type=int, default=None,
                    help="rehearse at this many rows, on any device")
    args = ap.parse_args(argv)

    import harness
    import spec

    cell = spec.load_cell(args.workload, ROOT)
    if args.n is not None:
        spec.shrink(cell, args.n)
    if cell.traffic["loop"] != "open":
        raise SystemExit("sweep: the cell's traffic is not an open loop")
    s = harness.Session(cell, root=ROOT, require_tpu=args.n is None)
    for rate in args.rates:
        s.mix = {**cell.traffic, "rate_rps": rate}
        win = s.window(args.seed, args.seconds)
        lat = np.array([r.latency_s for r in win.records if r.answered])
        q = max(len(lat) // 4, 1)
        busy_until = max(r.done for r in win.records if r.answered)
        print(json.dumps({
            "rate_rps": rate, "requests": len(win.records),
            "answered": len(lat),
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "answered_rps": len(lat) / (busy_until - win.start),
            "backlog_growth_ms": 1e3 * float(lat[-q:].mean()
                                             - lat[:q].mean()),
        }), flush=True)
    s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
