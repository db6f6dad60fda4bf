"""The daemon worker's host time per request while that request's device
work was not running: each ``daemon.serve`` span that starts inside the
window, less its ``gate.search.device_wait`` (the program's ``repro.obs``
spans, matched by request id), averaged over the requests.  The program
records them while the profiler traces; without them, nothing to read."""
from collections import defaultdict

import numpy as np


def read(ctx):
    from repro.obs import get_tracer

    tracer, win = get_tracer(), ctx["window"]
    serve, wait = [], defaultdict(float)
    for e in tracer.events():
        if e["name"] == "gate.search.device_wait":
            wait[e.get("req")] += e["dur"]
        elif (e["name"] == "daemon.serve"
              and win.start <= tracer.t0 + e["ts"] / 1e6 <= win.end):
            serve.append(e)
    if not serve:
        return None
    return float(np.mean([e["dur"] - wait[e["req"]] for e in serve])) / 1e3
