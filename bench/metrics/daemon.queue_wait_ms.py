"""Mean time a request waited in ``ServeDaemon``'s queue, from submit to
dequeue: the program's ``daemon.queue_wait`` spans (``repro.obs``) that
start inside the window, on the host's ``perf_counter`` clock.  The program
records them while the profiler traces; without them, nothing to read."""
import numpy as np


def read(ctx):
    from repro.obs import get_tracer

    tracer, win = get_tracer(), ctx["window"]
    waits = [e["dur"] for e in tracer.events()
             if e["name"] == "daemon.queue_wait"
             and win.start <= tracer.t0 + e["ts"] / 1e6 <= win.end]
    return float(np.mean(waits)) / 1e3 if waits else None
