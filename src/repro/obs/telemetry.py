"""Device-side search telemetry: the pytree the jitted search loops return
(one leaf per signal, one host transfer per batch) plus the host-side
consumers — registry recording, ring-overflow warning, summaries.

Field ↔ paper mapping (PAPER.md §5, arXiv:2402.04713, arXiv:2510.22316):
  hops              search path length ℓ (Algorithm-1 expansion count)
  dist_evals        #distance computations (the paper's cost unit)
  ring_evictions    visited-ring slots overwritten while still holding a
                    live id — each one re-opens a node for re-scoring
                    (silent aliasing; satellite fix in ISSUE 6)
  converged_hop     first hop after which the top-k beam prefix never
                    changed again (beam convergence; adaptive-termination
                    signal of Hua et al.)
  nav_hops          navigation-graph greedy-descent length (GATE entry)
  entry_dist        best entry candidate's distance to the query
  entry_rank_proxy  entry_dist / final top-1 distance — 1.0 means the
                    chosen entry already was the answer; large values mean
                    a poor entry (entry-quality proxy without ground truth);
                    under ``ip`` the two distances are taken in the reduced
                    L2 space the index was built in, ‖q‖² + M² − 2 q·x
  bytes_read        estimated HBM bytes this query's search read (vector
                    rows × bytes/row for the active kernel + neighbor-list
                    reads + the q8 rerank's exact rows) — the
                    bandwidth-optimization signal of ISSUE 10; see
                    docs/kernels.md for the traffic model.  float32 on
                    device: an int32 count wraps at ~131k evals of a
                    d=4096 fp32 row, turning registry counters negative
"""
from __future__ import annotations

import inspect
import warnings
from typing import Callable, NamedTuple

import jax
import numpy as np

from repro.obs.registry import MetricsRegistry, POW2_BUCKETS, get_registry


class SearchTelemetry(NamedTuple):
    """Per-query counters accumulated inside the jitted search loops.

    All leaves are shape (B,); a NamedTuple so it crosses jit/vmap as a
    pytree and transfers to host as one batch.
    """

    hops: jax.Array             # int32  — expansions (path length ℓ)
    dist_evals: jax.Array       # int32  — distance computations
    ring_evictions: jax.Array   # int32  — live visited-ring slots overwritten
    converged_hop: jax.Array    # int32  — last hop the top-k prefix changed
    nav_hops: jax.Array         # int32  — nav-graph descent length (0 if n/a)
    entry_dist: jax.Array       # float32 — best entry distance to query
    entry_rank_proxy: jax.Array # float32 — entry_dist / final top-1 dist
    bytes_read: jax.Array       # float32 — est. HBM bytes read (kernel model)


# Ratio buckets for entry_rank_proxy: 1.0 = perfect entry.
RATIO_BUCKETS = (1.0, 1.5, 2.0, 3.0, 5.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                 256.0, 1024.0)


def summarize(tele: SearchTelemetry) -> dict:
    """Host-side scalar summary (means) of a telemetry batch."""
    t = jax.tree.map(np.asarray, tele)
    overflow = int((t.ring_evictions > 0).sum())
    return {
        "queries": int(t.hops.shape[0]),
        "mean_hops": float(t.hops.mean()),
        "mean_dist_evals": float(t.dist_evals.mean()),
        "mean_converged_hop": float(t.converged_hop.mean()),
        "mean_nav_hops": float(t.nav_hops.mean()),
        "mean_entry_dist": float(t.entry_dist.mean()),
        "mean_entry_rank_proxy": float(t.entry_rank_proxy.mean()),
        # tail entry quality within the batch — the rolling window / adaptive
        # controller key off this, not the mean (hard queries are the tail)
        "p95_entry_rank_proxy": float(
            np.quantile(np.atleast_1d(t.entry_rank_proxy), 0.95)
        ),
        "ring_evictions_total": int(t.ring_evictions.sum()),
        "ring_overflow_queries": overflow,
        "mean_bytes_read": float(t.bytes_read.mean()),
    }


def record_search_telemetry(
    tele: SearchTelemetry,
    registry: MetricsRegistry = None,
    prefix: str = "search",
) -> None:
    """Fold a telemetry batch into registry histograms/counters."""
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    t = jax.tree.map(np.asarray, tele)
    reg.counter(f"{prefix}.queries", "queries searched").inc(t.hops.shape[0])
    reg.histogram(
        f"{prefix}.hops", "search path length (hops)", POW2_BUCKETS
    ).observe_many(t.hops)
    reg.histogram(
        f"{prefix}.dist_evals", "distance evaluations per query", POW2_BUCKETS
    ).observe_many(t.dist_evals)
    reg.histogram(
        f"{prefix}.converged_hop", "hop at which top-k prefix stabilized",
        POW2_BUCKETS,
    ).observe_many(t.converged_hop)
    reg.histogram(
        f"{prefix}.nav_hops", "nav-graph descent length", POW2_BUCKETS
    ).observe_many(t.nav_hops)
    reg.histogram(
        f"{prefix}.entry_rank_proxy",
        "entry distance / final top-1 distance", RATIO_BUCKETS,
    ).observe_many(t.entry_rank_proxy)
    reg.counter(
        f"{prefix}.ring_evictions", "visited-ring live-slot evictions"
    ).inc(int(t.ring_evictions.sum()))
    reg.counter(
        f"{prefix}.bytes_read",
        "estimated HBM bytes read by search (kernel traffic model)",
    ).inc(float(t.bytes_read.astype(np.float64).sum()))


def registry_sink(
    tele: SearchTelemetry,
    *,
    params=None,
    where: str = "search",
    prefix: str = "search",
    registry: MetricsRegistry = None,
    **_extra,
) -> None:
    """The default ``telemetry_sink`` (ISSUE 8): fold the batch into the
    metrics registry and warn on visited-ring overflow — exactly the old
    ``GateIndex.search(record=True)`` side effects.

    A *telemetry sink* is any callable ``sink(tele, *, params, where)``;
    ``GateIndex.search(..., telemetry_sink=None)`` is the old
    ``record=False`` (telemetry still returned, no side effects).
    Sinks that additionally declare ``report=`` / ``queries=`` keywords (or
    ``**extra``) receive richer context from routed search — see
    :func:`call_telemetry_sink`; this default one ignores the extras.
    """
    record_search_telemetry(tele, registry, prefix)
    ring = getattr(params, "visited_ring", 0) if params is not None else 0
    warn_on_ring_overflow(tele, ring, where=where, registry=registry)


def call_telemetry_sink(sink, tele, *, params=None, where: str = "search",
                        **extra) -> None:
    """Invoke a telemetry sink, forwarding only the ``extra`` keywords it
    actually accepts.  The sink contract is ``sink(tele, *, params, where)``
    — richer callers (``search_routed`` passing ``report=`` / ``queries=``)
    must not break narrow sinks, and richer sinks (the query log) should
    still receive the extras.  Sinks with ``**kwargs`` get everything; on
    signature-introspection failure the call degrades to the base form."""
    if sink is None:
        return
    if extra:
        try:
            sig = inspect.signature(sink)
            params_ = sig.parameters
            if not any(p.kind is inspect.Parameter.VAR_KEYWORD
                       for p in params_.values()):
                extra = {k: v for k, v in extra.items() if k in params_}
        except (TypeError, ValueError):
            extra = {}
    sink(tele, params=params, where=where, **extra)


def chain_sinks(*sinks) -> Callable:
    """Compose telemetry sinks: each non-None sink runs in order with the
    same payload (extras filtered per sink via :func:`call_telemetry_sink`).
    Lets serving keep ``registry_sink`` metrics *and* query-log capture on
    the one ``telemetry_sink=`` seam."""
    kept = tuple(s for s in sinks if s is not None)

    def chained(tele, *, params=None, where="search", **extra):
        for s in kept:
            call_telemetry_sink(s, tele, params=params, where=where, **extra)

    return chained


def warn_on_ring_overflow(
    tele: SearchTelemetry,
    visited_ring: int,
    where: str = "search",
    registry: MetricsRegistry = None,
) -> int:
    """Host-side warning for the visited-ring aliasing satellite: when total
    expansions exceed the ring capacity, old entries are evicted and their
    nodes can silently be re-scored (wasted dist-evals, inflated recall
    variance).  Returns the number of affected queries.

    Besides the stderr ``RuntimeWarning``, overflow increments the
    ``search.ring_overflow_queries`` counter so it is visible on a
    ``/metrics`` scrape, not just in logs (ISSUE 7 satellite).
    """
    ev = np.asarray(tele.ring_evictions)
    n = int((ev > 0).sum())
    if n:
        reg = registry if registry is not None else get_registry()
        reg.counter(
            "search.ring_overflow_queries",
            "queries whose visited ring overflowed (possible re-scoring)",
        ).inc(n)
        warnings.warn(
            f"[{where}] visited-ring overflow on {n}/{ev.shape[0]} queries "
            f"({int(ev.sum())} evictions, ring={visited_ring}): nodes may be "
            f"re-scored; raise visited_ring or lower max_hops/beam_width",
            RuntimeWarning,
            stacklevel=3,
        )
    return n
