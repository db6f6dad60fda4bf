"""Mean hops of the beam search per query (``SearchResult.hops``), over every
query answered in the window: a better entry needs fewer hops."""
import numpy as np


def read(ctx):
    hops = [r.hops for r in ctx["window"].records if r.answered]
    return float(np.concatenate(hops).mean()) if hops else None
