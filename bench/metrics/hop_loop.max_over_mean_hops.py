"""Straggler lanes: per request, the most hops any query took over the mean
hops of its queries (``SearchResult.hops``); the vmapped loop runs until its
slowest lane ends.  Averaged over the requests answered in the window."""
import numpy as np


def read(ctx):
    ratios = [float(r.hops.max() / max(r.hops.mean(), 1e-9))
              for r in ctx["window"].records if r.answered]
    return float(np.mean(ratios)) if ratios else None
