"""Batched greedy beam search over a proximity graph (paper Algorithm 1),
TPU-native formulation.

The CPU pointer-chasing loop becomes a fixed-shape ``lax.while_loop`` per
query, vmapped over the batch:

  state = (beam ids (L,), beam dists (L,), expanded flags (L,),
           visited ring (V,), hops)

Each step expands the best unexpanded beam node: gather its padded neighbor
row (R,), mask already-seen ids (beam + visited ring), compute distances
(the kernels/gather_dist hot spot), merge-and-keep top-L.  Terminates when
every beam slot is expanded (the Algorithm-1 condition) or at max_hops.

Distances are squared L2 (monotone-equivalent to L2), ``1 - cos`` or, for
inner product, ``-q·x``: every metric ascends.

Telemetry (``instrument=True``, a static arg): the loops additionally
accumulate a ``SearchTelemetry`` pytree — visited-ring evictions (silent
aliasing signal), beam-convergence hop, entry quality — on device, so
instrumentation costs one transfer per batch.  ``instrument=False`` (the
default) traces the exact pre-telemetry program: no extra loop state, no
telemetry ops in the HLO.

Every search knob is static: a distinct ``SearchParams`` value is a separate
XLA program.  The adaptive controller (``repro.obs.adaptive``) and the
per-query hardness router (``repro.obs.router``) therefore move along a
small precompiled *ladder* of params — warm every rung once
(``GateIndex.warmup_ladder`` / ``warmup_router``) and adaptation never
recompiles; ``search_jit_cache_size()`` is the assertion hook for that
invariant.

``batched_search`` takes the knobs as one ``params=SearchParams(...)``
object (ISSUE 8); the old per-knob kwargs still work but warn once via the
deprecation shim in ``repro.graphs.params``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.params import SearchParams, resolve_search_params
from repro.obs.telemetry import SearchTelemetry

INF = jnp.float32(3.4e38)


class SearchResult(NamedTuple):
    ids: jax.Array       # (B, k)
    dists: jax.Array     # (B, k)
    hops: jax.Array      # (B,) expansion count (search path length ℓ)
    dist_evals: jax.Array  # (B,) number of distance computations


def _merge_top_l(ids_a, d_a, exp_a, ids_b, d_b):
    """Merge beam (a) with candidates (b), keep L best unique by distance.

    One stable sort keyed on distance carries the ids and flags with it: the
    order of equal keys is ``jnp.argsort``'s.  Applying an argsort instead
    takes three element gathers, which under ``vmap`` the TPU compiler
    flattens into one-element gathers over all B·(L+R) slots, and those cost
    the hop loop far more than the sort."""
    L = ids_a.shape[0]
    d = jnp.concatenate([d_a, d_b])
    ids = jnp.concatenate([ids_a, ids_b])
    expanded = jnp.concatenate([exp_a, jnp.zeros(ids_b.shape, jnp.bool_)])
    d, ids, expanded = jax.lax.sort(
        (d, ids, expanded), num_keys=1, is_stable=True
    )
    return ids[:L], d[:L], expanded[:L]


def _set_at(x, i, v):
    """``x.at[i].set(v)`` for a 1-D loop carry, written as a select.

    Under ``vmap`` the scatter form becomes a batched scatter, which the TPU
    compiler flattens at large batches.  Compiled that way, the beam-64
    search at a batch of 1,024 or more ran nearly every query to its hop
    cap on a v5e, with ids that depended on the batch size.  A compare
    against an iota is one elementwise pass over the carry and needs no
    scatter."""
    return jnp.where(jnp.arange(x.shape[0]) == i, v, x)


def _rerank_exact(beam_ids, beam_d, evals, rerank, exact_dist):
    """q8 epilogue: re-score the first ``rerank`` beam slots (already sorted
    best-first by approximate distance) with the exact fp32 formulation and
    re-order.  Invalid (-1) slots score +inf and sink to the back.  Returns
    the truncated ``(ids, dists)`` plus updated eval count and the number of
    valid rows re-read (for the bytes_read model)."""
    cand = beam_ids[:rerank]
    d_ex = exact_dist(cand)
    order = jnp.argsort(d_ex)
    n_valid = jnp.sum((cand >= 0).astype(jnp.int32))
    return cand[order], d_ex[order], evals + n_valid, n_valid


def _make_dist_fns(
    db, q, *, metric, kernel, kernel_interpret, inv_norms, quant,
    db_lane=None,
):
    """Build ``(dist_to, exact_dist, vec_bytes)`` for one query.

    ``dist_to`` is the per-hop distance function the while-loop uses (the
    approximate q8 one under ``kernel="fused_q8"``); ``exact_dist`` is the
    fp32 formulation used for entry distances' exactness-insensitive twin and
    the rerank epilogue; ``vec_bytes`` is the traffic-model bytes per scored
    row for ``bytes_read`` telemetry.

    Everything query/db-global (query normalization, db inv-norms, TPU lane
    padding, the q8 query widening) happens HERE, once per search — never
    inside the hop loop (ISSUE 10 satellite: no per-hop padding or
    renormalization).

    Kernel dispatch: ``fused`` runs the Pallas in-kernel-gather body on TPU,
    or in interpret mode under ``kernel_interpret=True`` (the CPU test path,
    refused on TPU); elsewhere it is the *matched* XLA formulation — same
    reduction shapes, so fp32 results are bit-identical either way.
    ``fused_q8`` scores its int8 codes with XLA on every platform (no int8
    row DMA pays off on TPU; see ``repro.kernels.gather_dist``).
    """
    from repro.kernels.gather_dist import gather_rows_dist, lane_rows
    from repro.kernels.ops import _on_tpu

    qf = q.astype(jnp.float32)
    D = db.shape[1]
    on_tpu = _on_tpu()
    if kernel_interpret and on_tpu:
        raise ValueError("kernel_interpret is a CPU test mode; on TPU the "
                         "compiled kernel runs")

    if metric == "cosine":
        qx = qf / jnp.maximum(jnp.linalg.norm(qf), 1e-9)
        # precomputed once (or passed in from the index's device cache) —
        # the old path renormalized every gathered row on every hop
        inv = inv_norms if inv_norms is not None else (
            1.0 / jnp.maximum(jnp.linalg.norm(db.astype(jnp.float32), axis=-1),
                              1e-9)
        )

        def exact_dist(ids):
            vecs = db[jnp.maximum(ids, 0)].astype(jnp.float32)
            vn = vecs * inv[jnp.maximum(ids, 0)][:, None]
            d = 1.0 - jnp.sum(vn * qx, axis=-1)
            return jnp.where(ids < 0, INF, d)
    elif metric == "l2":
        qx = qf
        inv = None

        def exact_dist(ids):
            vecs = db[jnp.maximum(ids, 0)].astype(jnp.float32)
            d = jnp.sum((vecs - qx) ** 2, axis=-1)
            return jnp.where(ids < 0, INF, d)
    elif metric == "ip":
        qx = qf
        inv = None

        # an elementwise product and sum in float32, as l2 scores diff²: a
        # default-precision dot runs float32 in bfloat16 passes on the TPU
        def exact_dist(ids):
            vecs = db[jnp.maximum(ids, 0)].astype(jnp.float32)
            d = -jnp.sum(vecs * qx, axis=-1)
            return jnp.where(ids < 0, INF, d)
    else:
        raise ValueError(metric)

    if metric == "ip" and kernel != "xla":
        raise ValueError(f'metric="ip" is served by kernel="xla" only, not '
                         f"kernel={kernel!r}")

    vec_bytes = D * db.dtype.itemsize  # l2 and ip read the row alone
    if metric == "cosine":
        vec_bytes += 4  # the inv-norm read per scored row

    if kernel == "xla" or (kernel == "fused" and not (kernel_interpret
                                                      or on_tpu)):
        return exact_dist, exact_dist, vec_bytes

    if kernel == "fused":
        # interpret mode reads the plain (N, d) rows, so reduction shapes —
        # and therefore bits — match the XLA reference, odd d included.  On
        # TPU the kernel reads the lane-row view; it must come in
        # precomputed (``db_lane``, cached per index by
        # GateIndex._search_kwargs) unless d = 128, where the view is ``db``
        # itself — building it here would trace an O(N·d) HBM copy into
        # every search batch's program.  The inline fallback exists only for
        # direct beam_search_single callers and pays that copy per batch.
        db_k, q_k = db, qx
        if not kernel_interpret:
            db_k = db_lane if db_lane is not None else lane_rows(db)
            q_k = jnp.pad(qx, ((0, (-D) % 128),))

        def dist_to(ids):
            return gather_rows_dist(
                ids, db_k, q_k, inv, interpret=kernel_interpret
            )
        return dist_to, exact_dist, vec_bytes

    # ---- fused_q8: approximate distances from the int8 codebook ----------
    if quant is None:
        raise ValueError(
            'kernel="fused_q8" needs the quantized codebook: pass quant= '
            "(see GateIndex.ensure_quantized / repro.quant.quantize_db)"
        )
    codes, scale, zero, q_inv = quant
    Dp = codes.shape[1]
    nb = scale.shape[1]
    qp = jnp.zeros((Dp,), jnp.float32).at[:D].set(qx)  # widened once
    vec_bytes = Dp + 8 * nb + (4 if metric == "cosine" else 0)

    def dequant_rows(ids):
        safe = jnp.maximum(ids, 0)
        c = codes[safe].astype(jnp.float32)
        c = c.reshape(c.shape[0], nb, Dp // nb)
        v = c * scale[safe][:, :, None] + zero[safe][:, :, None]
        return v.reshape(v.shape[0], Dp)

    if metric == "cosine":
        def dist_to(ids):
            vn = dequant_rows(ids) * q_inv[jnp.maximum(ids, 0)][:, None]
            d = 1.0 - jnp.sum(vn * qp, axis=-1)
            return jnp.where(ids < 0, INF, d)
    else:
        def dist_to(ids):
            d = jnp.sum((dequant_rows(ids) - qp) ** 2, axis=-1)
            return jnp.where(ids < 0, INF, d)
    return dist_to, exact_dist, vec_bytes


def beam_search_single(
    db: jax.Array,          # (N, d)
    neighbors: jax.Array,   # (N, R) int32, -1 padded
    q: jax.Array,           # (d,)
    entry_ids: jax.Array,   # (E,) int32 starting candidates
    *,
    beam_width: int,
    max_hops: int,
    visited_ring: int = 512,
    instrument: bool = False,
    conv_k: int = 10,
    metric: str = "l2",
    kernel: str = "xla",
    kernel_interpret: bool = False,
    rerank: int = 0,
    inv_norms: Optional[jax.Array] = None,
    quant=None,
    db_lane: Optional[jax.Array] = None,
    mips_m2: Optional[jax.Array] = None,
):
    """One query's Algorithm-1 beam search.

    ``metric="l2"`` ranks by squared L2; ``"cosine"`` by 1 − cos(v, q)
    (monotone in angle; vectors need not be pre-normalized); ``"ip"`` by
    −q·v (maximum inner product), on the ``xla`` kernel only.  ``mips_m2``
    is ``M²``, the largest squared row norm, which the instrumented ``ip``
    search needs for ``entry_rank_proxy``: it is refused without it.

    ``kernel`` selects the distance path (see docs/kernels.md): ``"xla"``
    gather+score, ``"fused"`` in-kernel gather via scalar prefetch
    (bit-identical fp32), ``"fused_q8"`` int8 approximate distances from
    ``quant`` (a ``repro.quant.QuantizedDb``) steering the walk, followed —
    when ``rerank > 0`` — by an exact-fp32 re-scoring of the first ``rerank``
    beam slots so returned distances/order are exact over that prefix (the
    beam then truncates to ``rerank`` entries).  ``inv_norms`` is the
    precomputed cosine ``1/‖row‖`` cache; omitted, it is computed once per
    call (still never per hop).  ``db_lane`` is the precomputed lane-row
    view of ``db`` (``repro.kernels.gather_dist.lane_rows``) the TPU
    ``fused`` kernel reads; omitted with ``d != 128``, it is built inline —
    an O(N·d) copy per batch, so serving callers should pass it
    (``GateIndex`` caches one per index).

    Returns ``(beam_ids, beam_d, hops, evals)``; with ``instrument=True`` a
    fifth element — a scalar-leaf ``SearchTelemetry`` — is appended.
    """
    L = beam_width
    R = neighbors.shape[1]
    dist_to, exact_dist, vec_bytes = _make_dist_fns(
        db, q, metric=metric, kernel=kernel,
        kernel_interpret=kernel_interpret, inv_norms=inv_norms, quant=quant,
        db_lane=db_lane,
    )

    e_d = dist_to(entry_ids)
    pad = L - entry_ids.shape[0]
    beam_ids = jnp.concatenate(
        [entry_ids, jnp.full((pad,), -1, jnp.int32)]
    ) if pad > 0 else entry_ids[:L]
    beam_d = jnp.concatenate([e_d, jnp.full((max(pad, 0),), INF)])[:L]
    order = jnp.argsort(beam_d)
    beam_ids, beam_d = beam_ids[order], beam_d[order]
    expanded = jnp.zeros((L,), jnp.bool_)
    ring = jnp.full((visited_ring,), -1, jnp.int32)
    hops = jnp.zeros((), jnp.int32)
    evals = jnp.asarray(entry_ids.shape[0], jnp.int32)

    if not instrument:
        def cond(state):
            beam_ids, beam_d, expanded, ring, hops, evals = state
            frontier = (~expanded) & (beam_ids >= 0)
            return jnp.any(frontier) & (hops < max_hops)

        def step(state):
            beam_ids, beam_d, expanded, ring, hops, evals = state
            masked = jnp.where(expanded | (beam_ids < 0), INF, beam_d)
            j = jnp.argmin(masked)
            p = beam_ids[j]
            expanded = _set_at(expanded, j, True)
            ring = _set_at(ring, hops % visited_ring, p)
            nbrs = neighbors[jnp.maximum(p, 0)]  # (R,)
            # dedup against beam + visited ring
            seen_beam = jnp.any(nbrs[:, None] == beam_ids[None, :], axis=1)
            seen_ring = jnp.any(nbrs[:, None] == ring[None, :], axis=1)
            valid = (nbrs >= 0) & ~seen_beam & ~seen_ring
            d_n = dist_to(jnp.where(valid, nbrs, -1))
            evals = evals + jnp.sum(valid.astype(jnp.int32))
            beam_ids, beam_d, expanded = _merge_top_l(
                beam_ids, beam_d, expanded, jnp.where(valid, nbrs, -1), d_n
            )
            return beam_ids, beam_d, expanded, ring, hops + 1, evals

        state = (beam_ids, beam_d, expanded, ring, hops, evals)
        beam_ids, beam_d, expanded, ring, hops, evals = jax.lax.while_loop(
            cond, step, state
        )
        if rerank > 0:
            beam_ids, beam_d, evals, _ = _rerank_exact(
                beam_ids, beam_d, evals, rerank, exact_dist
            )
        return beam_ids, beam_d, hops, evals

    # ---------------------------------------------------- instrumented loop
    K = min(conv_k, L)
    entry_dist = jnp.min(e_d)
    evictions = jnp.zeros((), jnp.int32)
    conv_hop = jnp.zeros((), jnp.int32)
    prev_topk = beam_ids[:K]

    def cond_i(state):
        frontier = (~state[2]) & (state[0] >= 0)
        return jnp.any(frontier) & (state[4] < max_hops)

    def step_i(state):
        (beam_ids, beam_d, expanded, ring, hops, evals,
         evictions, conv_hop, prev_topk) = state
        masked = jnp.where(expanded | (beam_ids < 0), INF, beam_d)
        j = jnp.argmin(masked)
        p = beam_ids[j]
        expanded = _set_at(expanded, j, True)
        slot = hops % visited_ring
        # a live id overwritten = node can silently be re-scored later
        evictions = evictions + (ring[slot] >= 0).astype(jnp.int32)
        ring = _set_at(ring, slot, p)
        nbrs = neighbors[jnp.maximum(p, 0)]  # (R,)
        seen_beam = jnp.any(nbrs[:, None] == beam_ids[None, :], axis=1)
        seen_ring = jnp.any(nbrs[:, None] == ring[None, :], axis=1)
        valid = (nbrs >= 0) & ~seen_beam & ~seen_ring
        d_n = dist_to(jnp.where(valid, nbrs, -1))
        evals = evals + jnp.sum(valid.astype(jnp.int32))
        beam_ids, beam_d, expanded = _merge_top_l(
            beam_ids, beam_d, expanded, jnp.where(valid, nbrs, -1), d_n
        )
        topk = beam_ids[:K]
        changed = jnp.any(topk != prev_topk)
        conv_hop = jnp.where(changed, hops + 1, conv_hop)
        return (beam_ids, beam_d, expanded, ring, hops + 1, evals,
                evictions, conv_hop, topk)

    state = (beam_ids, beam_d, expanded, ring, hops, evals,
             evictions, conv_hop, prev_topk)
    (beam_ids, beam_d, expanded, ring, hops, evals,
     evictions, conv_hop, prev_topk) = jax.lax.while_loop(
        cond_i, step_i, state
    )
    # traffic model (docs/kernels.md): every scored row reads vec_bytes,
    # every hop reads one (R,) int32 neighbor row; the q8 rerank epilogue
    # re-reads its candidates at full fp32 width.  float32 on device: wide
    # vectors wrap int32 (d=4096 fp32 is 16 KiB/row → overflow at ~131k
    # evals) and the sink can only widen after the damage.
    bytes_read = (
        evals.astype(jnp.float32) * float(vec_bytes)
        + hops.astype(jnp.float32) * float(R * 4)
    )
    if rerank > 0:
        beam_ids, beam_d, evals, rr_valid = _rerank_exact(
            beam_ids, beam_d, evals, rerank, exact_dist
        )
        exact_bytes = db.shape[1] * db.dtype.itemsize + (
            4 if metric == "cosine" else 0
        )
        bytes_read = bytes_read + rr_valid.astype(jnp.float32) * float(
            exact_bytes
        )
    if metric == "ip":
        # -q·x is mostly negative, so its ratio means nothing.  The ratio of
        # the two distances in the reduced L2 space the graph was built in
        # (GateIndex.build), ‖q‖² + M² − 2 q·x, keeps the proxy's meaning:
        # at least 1, and exactly 1 when the entry is the final top-1
        if mips_m2 is None:
            raise ValueError(
                'an instrumented metric="ip" search needs mips_m2 (M², the '
                "largest squared row norm) for entry_rank_proxy"
            )
        qf = q.astype(jnp.float32)
        base = jnp.sum(qf * qf) + mips_m2
        rank_proxy = (jnp.maximum(base + 2.0 * entry_dist, 1e-12)
                      / jnp.maximum(base + 2.0 * beam_d[0], 1e-12))
    else:
        rank_proxy = entry_dist / jnp.maximum(beam_d[0], 1e-12)
    tele = SearchTelemetry(
        hops=hops,
        dist_evals=evals,
        ring_evictions=evictions,
        converged_hop=conv_hop,
        nav_hops=jnp.zeros((), jnp.int32),
        entry_dist=entry_dist,
        entry_rank_proxy=rank_proxy,
        bytes_read=bytes_read,
    )
    return beam_ids, beam_d, hops, evals, tele


@functools.partial(jax.jit, static_argnames=("params",))
def _batched_search(
    db: jax.Array,
    neighbors: jax.Array,
    queries: jax.Array,    # (B, d)
    entry_ids: jax.Array,  # (B, E)
    inv_norms: Optional[jax.Array] = None,  # (N,) cosine 1/‖row‖ cache
    quant=None,                             # repro.quant.QuantizedDb pytree
    db_lane: Optional[jax.Array] = None,    # (N·k, 128) lane-row view of db
    mips_m2: Optional[jax.Array] = None,    # () ip: largest squared row norm
    *,
    params: SearchParams,
):
    """Jitted core: one compiled program per (shapes, ``params``) pair —
    ``SearchParams`` is frozen/hashable, so it is the whole static key.
    ``inv_norms``/``quant``/``db_lane``/``mips_m2`` are ordinary (pytree)
    operands:
    presence vs ``None`` changes the treedef and therefore the cache entry,
    so callers must pass them consistently per params (``GateIndex`` derives
    them from the params deterministically)."""
    if params.kernel == "fused_q8" and quant is None:
        raise ValueError(
            'SearchParams(kernel="fused_q8") requires quant= (the int8 '
            "codebook from repro.quant.quantize_db / "
            "GateIndex.ensure_quantized)"
        )
    k = params.k
    # q8 approximate walk → exact-fp32 rerank of the top k·α beam prefix
    rerank = (
        min(params.beam_width, k * params.rerank_mult)
        if params.kernel == "fused_q8" else 0
    )
    fn = functools.partial(
        beam_search_single,
        db,
        neighbors,
        beam_width=params.beam_width,
        max_hops=params.max_hops,
        visited_ring=params.visited_ring,
        instrument=params.instrument,
        conv_k=params.conv_k,
        metric=params.metric,
        kernel=params.kernel,
        kernel_interpret=params.kernel_interpret,
        rerank=rerank,
        inv_norms=inv_norms,
        quant=quant,
        db_lane=db_lane,
        mips_m2=mips_m2,
    )
    if not params.instrument:
        beam_ids, beam_d, hops, evals = jax.vmap(fn)(queries, entry_ids)
        return SearchResult(beam_ids[:, :k], beam_d[:, :k], hops, evals)
    beam_ids, beam_d, hops, evals, tele = jax.vmap(fn)(queries, entry_ids)
    return SearchResult(beam_ids[:, :k], beam_d[:, :k], hops, evals), tele


def batched_search(
    db: jax.Array,
    neighbors: jax.Array,
    queries: jax.Array,    # (B, d)
    entry_ids: jax.Array,  # (B, E)
    params: Optional[SearchParams] = None,
    *,
    k: Optional[int] = None,
    inv_norms: Optional[jax.Array] = None,
    quant=None,
    db_lane: Optional[jax.Array] = None,
    mips_m2: Optional[jax.Array] = None,
    **legacy,
):
    """Batched Algorithm-1 search.

    Pass the knobs as ``params=SearchParams(...)`` (``k=`` stays as a
    blessed shortcut overriding ``params.k``).  The pre-ISSUE-8 per-knob
    kwargs (``beam_width=``, ``max_hops=``, ...) still work but emit a
    one-shot ``DeprecationWarning`` and count into ``api.deprecated_kwargs``.

    ``params.kernel`` selects the distance path (docs/kernels.md); for
    ``"fused_q8"`` pass ``quant=`` (``repro.quant.quantize_db(db)``), for
    ``metric="cosine"`` optionally ``inv_norms=`` to reuse a precomputed
    ``1/‖row‖`` cache across calls, and for ``"fused"`` on TPU with
    ``d != 128`` optionally ``db_lane=`` (``lane_rows(db)``) so the view
    isn't re-materialized inside every search batch, and for an
    instrumented ``metric="ip"`` ``mips_m2=`` (``M²``, the largest
    squared row norm) for ``entry_rank_proxy``.

    ``params.instrument=False`` (default): returns ``SearchResult`` — the
    HLO is identical to the pre-telemetry program.  ``instrument=True``:
    returns ``(SearchResult, SearchTelemetry)`` with (B,) telemetry leaves.
    """
    params = resolve_search_params("batched_search", params, legacy, k=k)
    return _batched_search(
        db, neighbors, queries, entry_ids, inv_norms, quant, db_lane,
        *_optional(mips_m2), params=params,
    )


def lower_batched_search(
    db: jax.Array,
    neighbors: jax.Array,
    queries: jax.Array,
    entry_ids: jax.Array,
    params: SearchParams,
    *,
    inv_norms: Optional[jax.Array] = None,
    quant=None,
    db_lane: Optional[jax.Array] = None,
    mips_m2: Optional[jax.Array] = None,
):
    """``batched_search``'s program for these arguments, lowered and not
    run: the same jit entry and operands, so ``.compile().as_text()`` is the
    program such a call runs (for example, whether it holds a Pallas
    kernel)."""
    return _batched_search.lower(
        db, neighbors, queries, entry_ids, inv_norms, quant, db_lane,
        *_optional(mips_m2), params=params,
    )


def _optional(mips_m2) -> tuple:
    """``mips_m2`` as a trailing operand where it is given: a search
    without it (every ``l2`` and ``cosine`` search) keeps the call, and the
    compiled program's operands, it had before ``ip``."""
    return () if mips_m2 is None else (mips_m2,)


def search_jit_cache_size() -> int:
    """Number of distinct compiled ``batched_search`` programs (one per
    (shapes, ``SearchParams``) combination).  The adaptive-serving
    invariant — ladder moves and routed sub-batches are jit-cache lookups,
    never recompiles — is asserted by checking this stays flat across
    controller steps / routed batches."""
    return _batched_search._cache_size()


def beam_search_fixed(
    db: jax.Array,          # (N, d)
    neighbors: jax.Array,   # (N, R)
    q: jax.Array,           # (d,)
    entry_ids: jax.Array,   # (E,)
    *,
    beam_width: int,
    num_hops: int,
    visited_ring: int = 256,
    expand_width: int = 1,
    db_norms: Optional[jax.Array] = None,
    instrument: bool = False,
    conv_k: int = 10,
):
    """Fixed-trip-count variant (lax.scan over hops) for batch serving:
    every query runs exactly ``num_hops`` expansions in lockstep — the TPU
    deployment mode (static latency, static HLO trip counts for roofline).
    Already-converged lanes expand their best node idempotently.

    ``expand_width`` E > 1 expands the E best unexpanded beam nodes per hop
    (wavefront expansion): per-hop fixed overhead (argmin/ring/merge) is
    amortized over E·R candidates, cutting the hop count ~E× for the same
    total node expansions.

    Distances use the dot form ‖v‖² − 2 v·q + ‖q‖²: the contraction lands on
    the MXU (kernels/gather_dist fuses it with the mask on real TPU).
    ``db_norms`` (precomputed ‖v‖², the classic ANNS norms-cache) keeps the
    gathered vectors in their storage dtype end-to-end — without it XLA
    hoists a fp32 convert of the ENTIRE db shard out of the hop loop
    (measured +2.1 GiB footprint and +4.3 GB traffic on search_1b).

    Returns ``(beam_ids, beam_d, hops)``; ``instrument=True`` appends a
    scalar-leaf ``SearchTelemetry`` carried through the scan.
    """
    L = beam_width
    E = expand_width
    qf = q.astype(jnp.float32)
    qn = jnp.sum(qf * qf)

    def dist_to(ids):
        vecs = db[jnp.maximum(ids, 0)]       # storage dtype (bf16 ok)
        vq = jax.lax.dot_general(            # MXU, fp32 accumulation
            vecs, q.astype(vecs.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if db_norms is not None:
            vn = db_norms[jnp.maximum(ids, 0)]
        else:
            vf = vecs.astype(jnp.float32)
            vn = jnp.sum(vf * vf, axis=-1)
        d = jnp.maximum(vn - 2.0 * vq + qn, 0.0)
        return jnp.where(ids < 0, INF, d)

    e_d = dist_to(entry_ids)
    pad = L - entry_ids.shape[0]
    beam_ids = jnp.concatenate(
        [entry_ids, jnp.full((max(pad, 0),), -1, jnp.int32)]
    )[:L]
    beam_d = jnp.concatenate([e_d, jnp.full((max(pad, 0),), INF)])[:L]
    order = jnp.argsort(beam_d)
    beam_ids, beam_d = beam_ids[order], beam_d[order]
    expanded0 = jnp.zeros((L,), jnp.bool_)
    ring0 = jnp.full((visited_ring,), -1, jnp.int32)

    def expand(beam_ids, beam_d, expanded, ring, h, count=False):
        """Shared hop body → new beam state + (#valid, #ring evictions).

        ``count=False`` traces no telemetry ops (the eviction slice is only
        read in the instrumented scan)."""
        masked = jnp.where(expanded | (beam_ids < 0), INF, beam_d)
        if E == 1:
            j = jnp.argmin(masked)[None]
        else:
            _, j = jax.lax.top_k(-masked, E)   # E best unexpanded
        p = beam_ids[j]                         # (E,)
        expanded = expanded | jnp.any(
            jnp.arange(L)[:, None] == j[None, :], axis=1
        )
        start = ((h * E) % visited_ring,)
        if count:
            old = jax.lax.dynamic_slice(ring, start, (E,))
        ring = jax.lax.dynamic_update_slice(ring, p, start)
        nbrs = neighbors[jnp.maximum(p, 0)].reshape(-1)  # (E*R,)
        seen_beam = jnp.any(nbrs[:, None] == beam_ids[None, :], axis=1)
        seen_ring = jnp.any(nbrs[:, None] == ring[None, :], axis=1)
        dup = jnp.zeros_like(nbrs, jnp.bool_)
        if E > 1:  # dedup within the expanded batch
            eq = nbrs[:, None] == nbrs[None, :]
            first = jnp.argmax(eq, axis=1)  # first occurrence index
            dup = first != jnp.arange(nbrs.shape[0])
        valid = (
            (nbrs >= 0) & ~seen_beam & ~seen_ring & ~dup
            & (p.repeat(neighbors.shape[1]) >= 0)
        )
        d_n = dist_to(jnp.where(valid, nbrs, -1))
        if count:
            n_valid = jnp.sum(valid.astype(jnp.int32))
            n_evict = jnp.sum((old >= 0).astype(jnp.int32))
        else:
            n_valid = n_evict = jnp.zeros((), jnp.int32)
        beam_ids, beam_d, expanded = _merge_top_l(
            beam_ids, beam_d, expanded, jnp.where(valid, nbrs, -1), d_n
        )
        return beam_ids, beam_d, expanded, ring, n_valid, n_evict

    if not instrument:
        def step(state, h):
            beam_ids, beam_d, expanded, ring = state
            beam_ids, beam_d, expanded, ring, _, _ = expand(
                beam_ids, beam_d, expanded, ring, h
            )
            return (beam_ids, beam_d, expanded, ring), None

        (beam_ids, beam_d, _, _), _ = jax.lax.scan(
            step, (beam_ids, beam_d, expanded0, ring0), jnp.arange(num_hops)
        )
        return beam_ids, beam_d, jnp.asarray(num_hops * E, jnp.int32)

    K = min(conv_k, L)
    entry_dist = jnp.min(e_d)

    def step_i(state, h):
        beam_ids, beam_d, expanded, ring, evals, evictions, conv_hop, prev = state
        beam_ids, beam_d, expanded, ring, n_valid, n_evict = expand(
            beam_ids, beam_d, expanded, ring, h, count=True
        )
        topk = beam_ids[:K]
        changed = jnp.any(topk != prev)
        conv_hop = jnp.where(changed, h + 1, conv_hop)
        return (
            beam_ids, beam_d, expanded, ring,
            evals + n_valid, evictions + n_evict, conv_hop, topk,
        ), None

    state0 = (
        beam_ids, beam_d, expanded0, ring0,
        jnp.asarray(entry_ids.shape[0], jnp.int32),
        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32), beam_ids[:K],
    )
    (beam_ids, beam_d, _, _, evals, evictions, conv_hop, _), _ = jax.lax.scan(
        step_i, state0, jnp.arange(num_hops)
    )
    hops = jnp.asarray(num_hops * E, jnp.int32)
    vec_bytes = db.shape[1] * db.dtype.itemsize + (
        4 if db_norms is not None else 0  # the norms-cache read per row
    )
    tele = SearchTelemetry(
        hops=hops,
        dist_evals=evals,
        ring_evictions=evictions,
        converged_hop=conv_hop,
        nav_hops=jnp.zeros((), jnp.int32),
        entry_dist=entry_dist,
        entry_rank_proxy=entry_dist / jnp.maximum(beam_d[0], 1e-12),
        # float32: wide vectors wrap an int32 byte count (see the
        # while-loop variant above)
        bytes_read=evals.astype(jnp.float32) * float(vec_bytes)
        + hops.astype(jnp.float32) * float(neighbors.shape[1] * 4),
    )
    return beam_ids, beam_d, hops, tele


def greedy_descent(
    vecs: jax.Array,       # (M, d) node vectors (e.g. hub nodes)
    neighbors: jax.Array,  # (M, s) int32
    q: jax.Array,          # (d,)
    start: jax.Array,      # () int32
    max_hops: int = 32,
    metric: str = "l2",
    *,
    instrument: bool = False,
):
    """Pure greedy walk to a local minimum (1-best, no beam). Used for the
    GATE navigation graph where s is tiny. Returns node id; with
    ``instrument=True`` returns ``(node id, hops taken)``."""
    qf = q.astype(jnp.float32)

    if metric == "l2":
        def dist(ids):
            v = vecs[jnp.maximum(ids, 0)].astype(jnp.float32)
            d = jnp.sum((v - qf) ** 2, axis=-1)
            return jnp.where(ids < 0, INF, d)
    elif metric == "cosine":
        qn = qf / jnp.maximum(jnp.linalg.norm(qf), 1e-9)

        def dist(ids):
            v = vecs[jnp.maximum(ids, 0)].astype(jnp.float32)
            v = v / jnp.maximum(
                jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-9
            )
            d = 1.0 - v @ qn
            return jnp.where(ids < 0, INF, d)
    else:
        raise ValueError(metric)

    def cond(state):
        cur, cur_d, done, h = state
        return (~done) & (h < max_hops)

    def step(state):
        cur, cur_d, done, h = state
        nbrs = neighbors[cur]
        d_n = dist(nbrs)
        j = jnp.argmin(d_n)
        better = d_n[j] < cur_d
        return (
            jnp.where(better, nbrs[j], cur),
            jnp.where(better, d_n[j], cur_d),
            ~better,
            h + 1,
        )

    d0 = dist(start[None])[0]
    cur, _, _, h = jax.lax.while_loop(
        cond, step, (start, d0, jnp.zeros((), jnp.bool_), jnp.zeros((), jnp.int32))
    )
    if instrument:
        return cur, h
    return cur
