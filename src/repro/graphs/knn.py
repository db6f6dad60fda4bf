"""Exact K-nearest-neighbor graph construction (chunked brute force).

‖q−c‖² = ‖q‖² − 2 q·c + ‖c‖² as chunked matmuls — the TPU-native formulation
(MXU does the q·c term; see kernels/l2dist for the Pallas version).  Used for
index construction (offline) and as ground truth in tests/benchmarks.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def pairwise_sq_l2(q: jax.Array, c: jax.Array) -> jax.Array:
    """(Q,d) x (C,d) -> (Q,C) squared L2, fp32 accumulation.  HIGHEST
    precision: the TPU's default fp32 matmul runs in bf16 passes, which
    would make the exact kNN (the recall reference) approximate there."""
    qf = q.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=1, keepdims=True)
    cn = jnp.sum(cf * cf, axis=1, keepdims=True)
    qc = jnp.matmul(qf, cf.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn - 2.0 * qc + cn.T, 0.0)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_chunk(qc, dbv, *, k):
    d = pairwise_sq_l2(qc, dbv)
    neg_d, idx = jax.lax.top_k(-d, k)
    return idx, -neg_d


def exact_knn(
    queries: np.ndarray,
    db: np.ndarray,
    k: int,
    *,
    exclude_self: bool = False,
    q_chunk: int = 2048,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k nearest db ids/distances per query. Returns (ids, dists)."""
    n = queries.shape[0]
    ids_out = np.empty((n, k), np.int32)
    d_out = np.empty((n, k), np.float32)
    kk = k + (1 if exclude_self else 0)
    dbj = jnp.asarray(db)
    for s in range(0, n, q_chunk):
        e = min(s + q_chunk, n)
        idx, dist = _topk_chunk(jnp.asarray(queries[s:e]), dbj, k=kk)
        idx, dist = np.asarray(idx), np.asarray(dist)
        if exclude_self:
            # drop the self-match (distance ~0 at own index)
            keep = idx != np.arange(s, e)[:, None]
            # ensure exactly k kept per row (self may be absent due to ties)
            rows = []
            rows_d = []
            for r in range(idx.shape[0]):
                sel = np.where(keep[r])[0][:k]
                rows.append(idx[r, sel])
                rows_d.append(dist[r, sel])
            idx, dist = np.stack(rows), np.stack(rows_d)
        ids_out[s:e] = idx[:, :k]
        d_out[s:e] = dist[:, :k]
    return ids_out, d_out


def knn_graph(db: np.ndarray, k: int, q_chunk: int = 2048) -> np.ndarray:
    """(N, k) symmetric-ish KNN adjacency (ids), self excluded."""
    ids, _ = exact_knn(db, db, k, exclude_self=True, q_chunk=q_chunk)
    return ids


def medoid(db: np.ndarray, sample: int = 4096, seed: int = 0) -> int:
    """Approximate medoid: point closest to the dataset mean."""
    mean = db.mean(axis=0, keepdims=True)
    ids, _ = exact_knn(mean.astype(db.dtype), db, 1)
    return int(ids[0, 0])


def recall_at_k(pred_ids: np.ndarray, true_ids: np.ndarray, k: int) -> float:
    """Mean |pred ∩ true| / k over queries."""
    hits = 0
    for p, t in zip(pred_ids[:, :k], true_ids[:, :k]):
        hits += len(set(p.tolist()) & set(t.tolist()))
    return hits / (pred_ids.shape[0] * k)
