"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout with a TPU.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler trace
of the window.  The last line of standard output is the result object; the
last lines of standard error are the numbers that decided ``correct``, each
beside its limit.  Exits 1 without a result when no TPU (or too few chips)
is found, or when the program is not in the checkout.

``--n`` rehearses the cell at a smaller corpus (on the CPU too, with
``JAX_PLATFORMS=cpu``); a rehearsal prints what a run prints and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

REHEARSAL_MAX_N = 50_000


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--n", type=int, default=None,
                    help=f"rehearse at this many rows (<= {REHEARSAL_MAX_N})")
    return ap.parse_args(argv)


def print_checks(result: dict) -> None:
    for r in result["_rows"]:
        rel = "<=" if r["sense"] == "max" else ">="
        print(f"check {r['name']}: {r['value']!r} (limit {rel} "
              f"{r['limit']!r}) {'ok' if r['ok'] else 'FAIL'}",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 1
    import spec

    cell = spec.load_cell(args.workload, ROOT)
    rehearsal = args.n is not None
    if rehearsal:
        if args.n > REHEARSAL_MAX_N:
            print(f"bench: a rehearsal takes --n <= {REHEARSAL_MAX_N}",
                  file=sys.stderr)
            return 2
        spec.shrink(cell, args.n)
    import harness

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, root=ROOT,
                              require_tpu=not rehearsal)
    print_checks(result)
    out = {k: v for k, v in result.items() if not k.startswith("_")}
    print(json.dumps(out), flush=True)
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
