"""Share of the HBM roofline that the search program reaches.

Bytes the hop loop must read, counted from its own counts whatever
implements it: every distance evaluation reads one float32 row (d * 4
bytes) and every hop one neighbour row (R * 4 bytes).  The least time the
chip needs for them is bytes over the peak HBM bandwidth of
``bench/peaks.json``; the share is that over the device time of the
``_batched_search`` module in the trace.  The loop does no matrix work, so
bandwidth, not compute, bounds it."""
import numpy as np

import devtrace


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    done = [r for r in ctx["window"].records if r.answered]
    if red is None or peaks is None or not done:
        return None
    inside, _ = devtrace.module_seconds(red, "_batched_search")
    if inside <= 0:
        return None
    d, R = ctx["config"]["d"], ctx["R"]
    evals = float(np.sum([r.dist_evals.sum() for r in done]))
    hops = float(np.sum([r.hops.sum() for r in done]))
    nbytes = evals * d * 4 + hops * R * 4
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / inside
