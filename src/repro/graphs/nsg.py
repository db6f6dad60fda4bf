"""NSG construction (Fu et al., VLDB'19) — the paper's underlying graph index.

Pipeline (vectorized for accelerator-style execution, numpy for glue):
  1. exact KNN graph (graphs/knn.py)
  2. medoid as navigating node
  3. per-node candidate pool: batched beam search of the node itself over the
     KNN graph (vmapped Algorithm 1) ∪ its KNN list
  4. MRNG edge selection: greedy pick nearest unsuppressed candidate; suppress
     any candidate closer to a picked neighbor than to the node (triangle
     pruning) — vectorized per node with a fori loop over the pool
  5. degree cap R; connectivity repair via BFS from the medoid (numpy) +
     nearest-reachable attachment.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.knn import exact_knn, knn_graph, medoid, pairwise_sq_l2
from repro.graphs.params import SearchParams
from repro.graphs.search import batched_search
from repro.obs.trace import span


@dataclass
class NSG:
    neighbors: np.ndarray  # (N, R) int32, -1 padded
    enter_id: int
    R: int

    @property
    def n(self):
        return self.neighbors.shape[0]

    def degree_stats(self):
        deg = (self.neighbors >= 0).sum(axis=1)
        return dict(
            min=int(deg.min()), max=int(deg.max()), mean=float(deg.mean())
        )


def _mrng_prune_batch(node_vecs, cand_ids, cand_vecs, R):
    """Vectorized MRNG selection.

    node_vecs: (B, d); cand_ids: (B, P) sorted by distance to node (-1 pad);
    cand_vecs: (B, P, d).  Returns (B, R) selected ids (-1 pad).
    """
    B, P, d = cand_vecs.shape
    nv = node_vecs.astype(jnp.float32)
    cv = cand_vecs.astype(jnp.float32)
    d_node = jnp.sum((cv - nv[:, None, :]) ** 2, axis=-1)  # (B, P)
    d_node = jnp.where(cand_ids < 0, jnp.inf, d_node)
    # pairwise candidate distances (B, P, P).  HIGHEST precision: at the
    # TPU's default (bf16 passes) the expansion's error is a large share of
    # a neighbour distance, and the rule below compares it with the exact
    # ``d_node``
    sq = jnp.sum(cv * cv, axis=-1)
    cc = jnp.einsum("bpd,bqd->bpq", cv, cv,
                    precision=jax.lax.Precision.HIGHEST)
    d_pair = sq[:, :, None] - 2 * cc + sq[:, None, :]

    def body(i, state):
        suppressed, selected, n_sel = state
        avail = ~suppressed & (cand_ids >= 0)
        dm = jnp.where(avail, d_node, jnp.inf)
        j = jnp.argmin(dm, axis=1)  # (B,)
        ok = jnp.isfinite(jnp.take_along_axis(dm, j[:, None], 1)[:, 0]) & (
            n_sel < R
        )
        picked_id = jnp.take_along_axis(cand_ids, j[:, None], 1)[:, 0]
        selected = jnp.where(
            ok[:, None] & (jnp.arange(R)[None, :] == n_sel[:, None]),
            picked_id[:, None],
            selected,
        )
        # suppress: candidates with d(cand, picked) < d(cand, node)
        d_to_pick = jnp.take_along_axis(
            d_pair, j[:, None, None], 1
        )[:, 0, :]  # (B, P)
        supp_new = d_to_pick < d_node
        suppressed = suppressed | jnp.where(ok[:, None], supp_new, False)
        # a select, not a batched scatter (see graphs.search._set_at)
        suppressed = suppressed | (jnp.arange(P)[None, :] == j[:, None])
        n_sel = n_sel + ok.astype(jnp.int32)
        return suppressed, selected, n_sel

    suppressed = jnp.zeros((B, P), jnp.bool_)
    selected = jnp.full((B, R), -1, jnp.int32)
    n_sel = jnp.zeros((B,), jnp.int32)
    suppressed, selected, n_sel = jax.lax.fori_loop(
        0, P, body, (suppressed, selected, n_sel)
    )

    # fill remaining slots with nearest pruned candidates (keep-pruned fill;
    # pure MRNG pruning leaves the graph too sparse to navigate)
    order = jnp.argsort(d_node, axis=1)

    def fill_body(i, state):
        selected, n_sel = state
        j = order[:, i]
        cid = jnp.take_along_axis(cand_ids, j[:, None], 1)[:, 0]
        dup = jnp.any(selected == cid[:, None], axis=1)
        ok = (~dup) & (cid >= 0) & (n_sel < R)
        selected = jnp.where(
            ok[:, None] & (jnp.arange(R)[None, :] == n_sel[:, None]),
            cid[:, None],
            selected,
        )
        return selected, n_sel + ok.astype(jnp.int32)

    selected, n_sel = jax.lax.fori_loop(0, P, fill_body, (selected, n_sel))
    return selected


def build_nsg(
    db: np.ndarray,
    *,
    R: int = 32,
    knn_k: int = 32,
    search_l: int = 64,
    pool_size: int = 96,
    batch: int = 1024,
    seed: int = 0,
    aug_random: int = 4,
) -> NSG:
    n, d = db.shape
    with span("nsg.knn", n=n, k=knn_k):
        knn = knn_graph(db, knn_k)
        enter = medoid(db)
    dbj = jnp.asarray(db)
    # candidate-generation substrate: KNN rows + a few random long edges per
    # node (efanna-style).  Clustered data yields a cluster-disconnected KNN
    # graph; without long edges the per-node search pools never contain
    # cross-cluster candidates and MRNG pruning can't keep what it never saw.
    rng = np.random.default_rng(seed)
    sub = np.concatenate(
        [knn, rng.integers(0, n, (n, aug_random)).astype(np.int32)], axis=1
    )
    knnj = jnp.asarray(sub)

    with span("nsg.search_prune", n=n, search_l=search_l):
        prune = jax.jit(_mrng_prune_batch, static_argnums=(3,))
        out = np.full((n, R), -1, np.int32)
        entry = jnp.full((batch, 1), enter, jnp.int32)
        for s in range(0, n, batch):
            e = min(s + batch, n)
            qs = dbj[s:e]
            ent = entry[: e - s]
            res = batched_search(
                dbj, knnj, qs, ent,
                SearchParams(k=search_l, beam_width=search_l,
                             max_hops=search_l),
            )
            # pool = search results ∪ own KNN row (dedup; self removed)
            pool = np.concatenate(
                [np.asarray(res.ids), knn[s:e]], axis=1
            )[:, :pool_size + 8]
            node_idx = np.arange(s, e)[:, None]
            pool = np.where(pool == node_idx, -1, pool)
            # dedup within row (keep first occurrence)
            pool_sorted = np.sort(pool, axis=1)
            dup = np.zeros_like(pool, bool)
            srt_idx = np.argsort(pool, axis=1, kind="stable")
            dup_sorted = np.concatenate(
                [np.zeros((pool.shape[0], 1), bool),
                 pool_sorted[:, 1:] == pool_sorted[:, :-1]], axis=1
            )
            np.put_along_axis(dup, srt_idx, dup_sorted, axis=1)
            pool = np.where(dup, -1, pool)[:, :pool_size]
            cand_ids = jnp.asarray(pool)
            cand_vecs = dbj[jnp.maximum(cand_ids, 0)]
            sel = prune(dbj[s:e], cand_ids, cand_vecs, R)
            out[s:e] = np.asarray(sel)

    with span("nsg.reverse_edges", n=n):
        out = _add_reverse_edges(out, R)
    with span("nsg.repair", n=n):
        out = _repair_connectivity(db, out, enter)
    return NSG(neighbors=out, enter_id=enter, R=out.shape[1])


def _add_reverse_edges(neighbors: np.ndarray, R: int) -> np.ndarray:
    """Insert v→u for each u→v where v has a free slot (NSG inter-insert)."""
    n = neighbors.shape[0]
    deg = (neighbors >= 0).sum(axis=1)
    nbr_sets = [set(row[row >= 0].tolist()) for row in neighbors]
    for u in range(n):
        for v in neighbors[u]:
            v = int(v)
            if v < 0:
                continue
            if deg[v] < R and u not in nbr_sets[v]:
                neighbors[v, deg[v]] = u
                nbr_sets[v].add(u)
                deg[v] += 1
    return neighbors


def _mark_reachable(neighbors: np.ndarray, seen: np.ndarray, start) -> None:
    """Mark everything reachable from ``start`` (DFS over unseen nodes)."""
    stack = [int(start)]
    seen[start] = True
    while stack:
        u = stack.pop()
        for v in neighbors[u]:
            if v >= 0 and not seen[v]:
                seen[v] = True
                stack.append(int(v))


def _repair_connectivity(db, neighbors, enter) -> np.ndarray:
    """DFS from the medoid; attach unreachable nodes to their nearest
    reachable node and mark everything the attached node reaches (NSG
    tree_grow), in waves: one exact nearest-reachable search per wave.
    Rows may overflow the degree cap — the adjacency is re-padded to the
    new max degree (matches the reference NSG implementation, which lets
    repair edges exceed R)."""
    n, R = neighbors.shape
    seen = np.zeros(n, bool)
    _mark_reachable(neighbors, seen, enter)
    if seen.all():
        return neighbors
    rows = [list(r[r >= 0]) for r in neighbors]
    extra = np.zeros(n, np.int32)
    cap = 4  # bounded repair fanout: chains spread over waves instead of
    #          piling hundreds of repair edges onto one anchor
    while not seen.all():
        missing = np.where(~seen)[0]
        reach_ids = np.where(seen)[0]
        ids, d = exact_knn(db[missing], db[reach_ids], 1)
        order = np.argsort(d[:, 0])
        attached = 0
        for j in order:
            m = int(missing[j])
            if seen[m]:
                continue  # reached through a node attached this wave
            r = int(reach_ids[ids[j, 0]])
            if extra[r] >= cap:
                continue  # anchor full — m waits for the next wave
            rows[r].append(m)
            extra[r] += 1
            _mark_reachable(neighbors, seen, m)
            attached += 1
        if attached == 0:  # all nearest anchors saturated: relax the cap
            cap *= 2
    new_R = max(R, max(len(r) for r in rows))
    out = np.full((n, new_R), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out
