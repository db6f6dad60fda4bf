"""The plain reference: exact top-k by brute force on the device.

Independent of the program: it imports nothing from ``repro`` and reads only
the corpus rows.  For each block of queries it scores every row in chunks at
``precision=HIGHEST`` (float32 on the TPU's matrix unit), keeps a shortlist
of ``k + SLACK`` per chunk, and then scores the shortlist again elementwise
in float32, so that the final order does not rest on the matrix form's
cancellation error.  Three metrics, each a distance in ascending order, the
order in which the server returns its answers:

* ``l2``: ``sum((x - q)**2)``;
* ``cosine``: ``1 - x.q`` over unit rows and a unit query;
* ``ip``: ``-sum(x * q)``, the negated inner product.

Any other metric is refused, never scored as one of these.

``control_topk`` is the same search with its distances computed in bfloat16,
the next precision below the configuration's float32: put in the program's
place, it has to come out as not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLACK = 22          # shortlist per chunk beyond k
CHUNK = 65_536      # corpus rows per scoring step
Q_BLOCK = 512       # queries per call
METRICS = ("l2", "cosine", "ip")


def _prep_db(db: np.ndarray, metric: str, chunk: int):
    """Device copy of the corpus padded to whole chunks, with each row's
    squared norm (l2) or as unit rows (cosine); padding rows are masked."""
    n, d = db.shape
    n_pad = -(-n // chunk) * chunk
    x = jnp.zeros((n_pad, d), jnp.float32).at[:n].set(jnp.asarray(db))
    if metric == "cosine":
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    sq = jnp.sum(x * x, axis=1)
    return x, sq, n


@functools.partial(jax.jit, static_argnames=("n", "m", "chunk", "metric"))
def _shortlist(x, sq, q, *, n, m, chunk, metric):
    """(Q, chunks·m) candidate ids: the m best of every chunk by the matrix
    form at HIGHEST precision."""
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)

    def one(c):
        xc = jax.lax.dynamic_slice_in_dim(x, c * chunk, chunk)
        s = jnp.matmul(q, xc.T, precision=jax.lax.Precision.HIGHEST)
        if metric in ("cosine", "ip"):
            d = -s
        else:
            d = jax.lax.dynamic_slice_in_dim(sq, c * chunk, chunk)[None] - 2 * s
        ids = c * chunk + jnp.arange(chunk)
        d = jnp.where(ids[None] < n, d, jnp.inf)
        _, top = jax.lax.top_k(-d, m)
        return top + c * chunk

    tops = jax.lax.map(one, jnp.arange(x.shape[0] // chunk))  # (C, Q, m)
    return jnp.transpose(tops, (1, 0, 2)).reshape(q.shape[0], -1)


def _exact(x, q, ids, metric):
    """Elementwise float32 distances of rows ``ids`` (Q, c) to ``q``."""
    v = x[ids]                                                # (Q, c, d)
    if metric == "cosine":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        return 1.0 - jnp.sum(v * qn[:, None, :], axis=-1)
    if metric == "ip":
        return -jnp.sum(v * q[:, None, :], axis=-1)
    return jnp.sum((v - q[:, None, :]) ** 2, axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _rescore(x, q, cand, *, k, metric):
    d = _exact(x, q, cand, metric)
    neg, j = jax.lax.top_k(-d, k)
    return jnp.take_along_axis(cand, j, axis=1), -neg


@functools.partial(jax.jit, static_argnames=("metric",))
def _distances(x, q, ids, *, metric):
    return _exact(x, q, jnp.maximum(ids, 0), metric)


@jax.jit
def _norms(x, ids):
    return jnp.linalg.norm(x[ids], axis=-1)


class Reference:
    """Exact top-k and exact distances over one corpus, on the device."""

    def __init__(self, db: np.ndarray, metric: str, chunk: int = CHUNK):
        if metric not in METRICS:
            raise ValueError(f"metric {metric!r} is not one of {METRICS}")
        self.metric = metric
        self.chunk = min(chunk, -(-len(db) // 8) * 8)
        self.x, self.sq, self.n = _prep_db(db, metric, self.chunk)

    def _blocks(self, queries: np.ndarray):
        for s in range(0, len(queries), Q_BLOCK):
            q = queries[s:s + Q_BLOCK]
            pad = Q_BLOCK - len(q)
            if pad:
                q = np.concatenate([q, np.repeat(q[-1:], pad, axis=0)])
            yield s, len(q) - pad, jnp.asarray(q, jnp.float32)

    def topk(self, queries: np.ndarray, k: int):
        """(ids, dists) of the exact k nearest rows, host arrays."""
        ids = np.empty((len(queries), k), np.int64)
        dists = np.empty((len(queries), k), np.float32)
        for s, m, q in self._blocks(queries):
            cand = _shortlist(self.x, self.sq, q, n=self.n, m=k + SLACK,
                              chunk=self.chunk, metric=self.metric)
            i, d = _rescore(self.x, q, cand, k=k, metric=self.metric)
            ids[s:s + m] = np.asarray(i)[:m]
            dists[s:s + m] = np.asarray(d)[:m]
        return ids, dists

    def distances(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Exact distances of the given rows (Q, k) to their queries."""
        out = np.empty(ids.shape, np.float32)
        for s, m, q in self._blocks(queries):
            blk = ids[s:s + m]
            if len(blk) < Q_BLOCK:
                blk = np.concatenate(
                    [blk, np.zeros((Q_BLOCK - len(blk), ids.shape[1]),
                                   ids.dtype)])
            out[s:s + m] = np.asarray(_distances(
                self.x, q, jnp.asarray(blk, jnp.int32),
                metric=self.metric))[:m]
        return out

    def row_norms(self, ids: np.ndarray) -> np.ndarray:
        """Float32 norms of rows ``ids`` (Q,) as the reference holds them
        (unit rows under ``cosine``)."""
        ids = np.asarray(ids)
        out = np.empty(len(ids), np.float32)
        for s in range(0, len(ids), Q_BLOCK):
            blk = ids[s:s + Q_BLOCK]
            m = len(blk)
            blk = np.concatenate([blk, np.zeros(Q_BLOCK - m, blk.dtype)])
            out[s:s + m] = np.asarray(_norms(
                self.x, jnp.asarray(blk, jnp.int32)))[:m]
        return out


@functools.partial(jax.jit, static_argnames=("n", "k", "chunk", "metric"))
def _control(x, sq, q, *, n, k, chunk, metric):
    xb = x.astype(jnp.bfloat16)
    qf = q
    if metric == "cosine":
        qf = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    qb = qf.astype(jnp.bfloat16)
    qq = jnp.sum(qb.astype(jnp.float32) ** 2, axis=1)

    def one(c):
        xc = jax.lax.dynamic_slice_in_dim(xb, c * chunk, chunk)
        s = jnp.matmul(qb, xc.T).astype(jnp.float32)
        if metric == "cosine":
            d = 1.0 - s
        elif metric == "ip":
            d = -s
        else:
            xx = jnp.sum(xc.astype(jnp.float32) ** 2, axis=1)
            d = qq[:, None] + xx[None] - 2 * s
        d = d.astype(jnp.bfloat16).astype(jnp.float32)
        ids = c * chunk + jnp.arange(chunk)
        d = jnp.where(ids[None] < n, d, jnp.inf)
        neg, top = jax.lax.top_k(-d, k)
        return top + c * chunk, -neg

    ids, d = jax.lax.map(one, jnp.arange(x.shape[0] // chunk))
    ids = jnp.transpose(ids, (1, 0, 2)).reshape(q.shape[0], -1)
    d = jnp.transpose(d, (1, 0, 2)).reshape(q.shape[0], -1)
    neg, j = jax.lax.top_k(-d, k)
    return jnp.take_along_axis(ids, j, axis=1), -neg


def control_topk(ref: Reference, queries: np.ndarray, k: int):
    """The reference's search with bfloat16 distances: (ids, dists)."""
    ids = np.empty((len(queries), k), np.int64)
    dists = np.empty((len(queries), k), np.float32)
    for s, m, q in ref._blocks(queries):
        i, d = _control(ref.x, ref.sq, q, n=ref.n, k=k, chunk=ref.chunk,
                        metric=ref.metric)
        ids[s:s + m] = np.asarray(i)[:m]
        dists[s:s + m] = np.asarray(d)[:m]
    return ids, dists
