"""Mean hop at which a query's top-10 last changed (``converged_hop``), over
the queries of every ``gate.search.telemetry`` span (``repro.obs``) that
starts inside the window: the span sums its batch's ``converged_hop`` and
counts its queries.  An entry far from the answer shows here as work, apart
from the hops the beam spends exhausting itself (``entry.hops_per_query``).
The program records the spans while the profiler traces; a program whose
spans do not carry the sum leaves nothing to read."""


def read(ctx):
    from repro.obs import get_tracer

    tracer, win = get_tracer(), ctx["window"]
    hops = queries = 0
    for e in tracer.events():
        args = e.get("args") or {}
        if (e["name"] == "gate.search.telemetry"
                and "converged_hop_sum" in args
                and win.start <= tracer.t0 + e["ts"] / 1e6 <= win.end):
            hops += args["converged_hop_sum"]
            queries += args["queries"]
    return hops / queries if queries else None
