"""Query-aware sample generation (paper Definition 4).

``H(q, V_i)`` is the hop count of the shortest path from hub ``V_i`` to the
top-1 neighbor of query ``q`` on the proximity graph.  Definition 4 is stated
on *shortest paths*, so the faithful implementation is a reverse BFS from each
query's top-1 target — one O(E) sweep per query instead of |Q|·|V| greedy
searches (the paper's implementation approximates the same quantity by
running Algorithm 1 per (hub, query) pair; ``greedy_hops`` provides that
variant for cross-checking).

A query q is a POSITIVE for hub V_i if  H(q,V_i) ≤ min_q' H(q',V_i) + t_pos,
and a NEGATIVE if                      H(q,V_i) ≥ min_q' H(q',V_i) + t_neg.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.knn import exact_knn


def _reverse_csr(neighbors: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """CSR of the reversed graph (v -> list of u with edge u->v)."""
    n, R = neighbors.shape
    src = np.repeat(np.arange(n, dtype=np.int64), R)
    dst = neighbors.reshape(-1).astype(np.int64)
    m = dst >= 0
    src, dst = src[m], dst[m]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, dst + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, src


def hop_counts(
    neighbors: np.ndarray,   # (N, R) forward adjacency
    targets: np.ndarray,     # (Q,) top-1 node id per query
    hub_ids: np.ndarray,     # (n_c,) hub node ids
    max_hops: int = 64,
) -> np.ndarray:
    """(Q, n_c) hop count from each hub to each query's target (BFS);
    unreachable within max_hops → max_hops."""
    n = neighbors.shape[0]
    indptr, rev = _reverse_csr(neighbors)
    hub_pos = np.full(n, -1, np.int64)
    hub_pos[hub_ids] = np.arange(len(hub_ids))
    out = np.full((len(targets), len(hub_ids)), max_hops, np.int32)

    # dedup targets (many queries share a top-1)
    uniq, inv = np.unique(targets, return_inverse=True)
    dist = np.empty(n, np.int32)
    for ui, t in enumerate(uniq):
        dist.fill(-1)
        dist[t] = 0
        frontier = np.array([t], np.int64)
        hubs_left = len(hub_ids)
        row = np.full(len(hub_ids), max_hops, np.int32)
        if hub_pos[t] >= 0:
            row[hub_pos[t]] = 0
            hubs_left -= 1
        d = 0
        while len(frontier) and d < max_hops and hubs_left > 0:
            d += 1
            # gather all reverse neighbors of the frontier
            segs = [rev[indptr[v] : indptr[v + 1]] for v in frontier]
            if not segs:
                break
            nxt = np.unique(np.concatenate(segs)) if segs else frontier[:0]
            nxt = nxt[dist[nxt] < 0]
            if len(nxt) == 0:
                break
            dist[nxt] = d
            hp = hub_pos[nxt]
            hit = hp >= 0
            if hit.any():
                row[hp[hit]] = d
                hubs_left -= int(hit.sum())
            frontier = nxt
        out[inv == ui] = row[None, :]
    return out


def top1_targets(db: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Exact top-1 base id per query (the search target)."""
    ids, _ = exact_knn(queries, db, 1)
    return ids[:, 0].astype(np.int64)


@functools.partial(jax.jit, static_argnames=("beam_width", "max_hops"))
def _hops_chunk(db, neighbors, queries, hub_ids, targets, *,
                beam_width, max_hops):
    """(Q, n_c) hops of Algorithm 1 from every hub for a chunk of queries.
    ``db`` and the graph are operands: closed over, they would be baked
    into the executable as constants (2.2 GB at 1M x 128)."""
    from repro.graphs.search import beam_search_single

    def one(q, entry, target):
        ids, d, hops, _ = beam_search_single(
            db, neighbors, q, entry[None],
            beam_width=beam_width, max_hops=max_hops,
        )
        return jnp.where(jnp.any(ids == target), hops, max_hops)

    return jax.vmap(jax.vmap(one, (None, 0, None)), (0, None, 0))(
        queries, hub_ids, targets
    )


def greedy_hops(
    db,
    neighbors,
    queries: np.ndarray,
    hub_ids: np.ndarray,
    targets: np.ndarray,
    *,
    beam_width: int = 16,
    max_hops: int = 64,
) -> np.ndarray:
    """Paper-implementation variant: hops of Algorithm 1 from each hub until
    the target enters the beam. (Q, n_c); batched over query-hub pairs."""
    dbj, nbj = jnp.asarray(db), jnp.asarray(neighbors)
    out = np.zeros((len(queries), len(hub_ids)), np.int32)
    qj = jnp.asarray(queries)
    hj = jnp.asarray(hub_ids, jnp.int32)
    tj = jnp.asarray(targets, jnp.int32)
    chunk = 64
    for s in range(0, len(queries), chunk):
        e = min(s + chunk, len(queries))
        out[s:e] = np.asarray(_hops_chunk(
            dbj, nbj, qj[s:e], hj, tj[s:e],
            beam_width=beam_width, max_hops=max_hops,
        ))
    return out


@dataclass
class SampleSet:
    """Per-hub positive / negative query queues (index into the query set)."""

    pos: List[np.ndarray]
    neg: List[np.ndarray]
    hop_matrix: np.ndarray  # (Q, n_c)

    def stats(self):
        return {
            "pos_mean": float(np.mean([len(p) for p in self.pos])),
            "neg_mean": float(np.mean([len(n) for n in self.neg])),
            "hub_with_no_pos": int(sum(len(p) == 0 for p in self.pos)),
        }


def make_samples(
    hop_matrix: np.ndarray,  # (Q, n_c)
    *,
    t_pos: int = 3,
    t_neg: int = 15,
    max_per_queue: int = 256,
    seed: int = 0,
) -> SampleSet:
    rng = np.random.default_rng(seed)
    Q, n_c = hop_matrix.shape
    pos, neg = [], []
    for i in range(n_c):
        col = hop_matrix[:, i]
        m = int(col.min())
        p = np.where(col <= m + t_pos)[0]
        n = np.where(col >= m + t_neg)[0]
        if len(p) > max_per_queue:
            p = rng.choice(p, max_per_queue, replace=False)
        if len(n) > max_per_queue:
            n = rng.choice(n, max_per_queue, replace=False)
        pos.append(np.sort(p))
        neg.append(np.sort(n))
    return SampleSet(pos=pos, neg=neg, hop_matrix=hop_matrix)
