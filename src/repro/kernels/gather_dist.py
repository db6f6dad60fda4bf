"""Fused neighbor-expansion distance kernels — the beam-search hot spot.

Per step the search expands a beam node: gather its R neighbor vectors and
compute masked distances against the query.  Two generations live here:

**Legacy (``gather_dist``)** — takes the rows *already gathered* by XLA as a
(B, R, d) block and fuses mask + distance.  The dominant traffic (the gather
itself, which round-trips the (B, R, d) block through HBM) is untouched, and
the block must be re-padded to lane multiples inside jit on every hop.  Kept
as the pre-ISSUE-10 baseline and for one-shot (non-loop) distance batches.

**In-kernel gather (``gather_rows_dist``)** — the neighbor ids arrive as a
*scalar-prefetch* argument (``pltpu.PrefetchScalarGridSpec``,
``num_scalar_prefetch=1``), so they are in SMEM before the body runs, and the
body DMAs exactly the R needed db rows HBM→VMEM itself.  The gathered block
never exists in HBM; per hop the traffic is R row-reads plus R output
floats.  Invalid slots (id < 0) fetch row 0 and are masked to +inf.  On TPU
the rows come from the lane-row view ``lane_rows(db)`` (the layout Mosaic
can DMA one row from); interpret mode (the CPU test path) reads the plain
(N, d) array and stays bit-identical to the XLA formulation in
``graphs/search.py``.  See docs/kernels.md for the traffic model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = 3.4e38  # python float: jnp scalars would be captured kernel constants
TILE_B = 8


def _gather_dist_kernel(vecs_ref, q_ref, ids_ref, out_ref):
    v = vecs_ref[...].astype(jnp.float32)   # (TB, R, d)
    q = q_ref[...].astype(jnp.float32)      # (TB, d)
    ids = ids_ref[...]                      # (TB, R)
    vq = jax.lax.dot_general(
        v, q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (TB, R)
    vn = jnp.sum(v * v, axis=2)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    d = jnp.maximum(vn - 2.0 * vq + qn, 0.0)
    out_ref[...] = jnp.where(ids >= 0, d, INF)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def gather_dist(
    vecs: jax.Array,  # (B, R, d) gathered neighbor vectors
    q: jax.Array,     # (B, d) queries
    ids: jax.Array,   # (B, R) neighbor ids, -1 = padding
    *,
    tile_b: int = TILE_B,
    interpret: bool = False,
) -> jax.Array:
    """(B, R) masked squared L2; invalid slots → +inf."""
    B, R, D = vecs.shape
    tile_b = min(tile_b, max(B, 1))
    Bp = (B + tile_b - 1) // tile_b * tile_b
    Rp = max((R + 127) // 128 * 128, 128)
    Dp = max((D + 127) // 128 * 128, 128)
    vp = jnp.pad(vecs, ((0, Bp - B), (0, Rp - R), (0, Dp - D)))
    qp = jnp.pad(q, ((0, Bp - B), (0, Dp - D)))
    ip = jnp.pad(ids, ((0, Bp - B), (0, Rp - R)), constant_values=-1)
    out = pl.pallas_call(
        _gather_dist_kernel,
        grid=(Bp // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, Rp, Dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_b, Dp), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, Rp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, Rp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Rp), jnp.float32),
        interpret=interpret,
    )(vp, qp, ip)
    return out[:B, :R]


# ---------------------------------------------------------------------------
# In-kernel gather: one manual DMA per neighbor row.
#
# The ids vector is the scalar-prefetch argument, so it sits in SMEM when the
# body starts.  ``db`` stays in HBM (``memory_space=ANY``); the body issues
# one ``make_async_copy`` per id into an (R, k, W) VMEM scratch, all R in
# flight before the first wait, then scores the block.  Mosaic accepts a
# one-row DMA only from an HBM array whose rows are exactly one 128-lane
# tile row (fp32); wider or odd ``d`` is therefore read through the lane-row
# view built by ``lane_rows`` — k = ⌈d/128⌉ consecutive 128-wide rows per
# base vector, zero-padded — which ``GateIndex`` caches once per index.
# Interpret mode (the CPU test path) reads the plain (N, d) array, k = 1, so
# the per-row reduction ``jnp.sum(..., axis=-1)`` runs over the same ``d``
# elements as the XLA formulation in ``graphs/search.py`` and fp32 results
# are bit-identical (asserted in tests/test_kernel_equiv.py).
#
# There is no int8 twin: int8 HBM rows are packed four to a sublane word, so
# the smallest int8 DMA Mosaic accepts is 8 rows (1 KB at d = 128, twice
# the fp32 row), which cancels the byte cut the codebook exists for.
# ``kernel="fused_q8"`` scores its codes with XLA on every platform.

LANES = 128


def lane_rows(db: jax.Array) -> jax.Array:
    """(N, d) → (N·k, 128) view, k = ⌈d/128⌉, zero-padded: the layout the
    TPU kernel DMAs rows from.  Free for d = 128; an O(N·d) copy otherwise,
    so serving callers build it once (``GateIndex`` caches it)."""
    n, d = db.shape
    if d == LANES:
        return db
    pad = (-d) % LANES
    return jnp.pad(db, ((0, 0), (0, pad))).reshape(n * ((d + pad) // LANES),
                                                     LANES)


def _rows_kernel(ids_ref, db_hbm, q_ref, *rest, cosine):
    if cosine:
        inv_ref, out_ref, buf, sem = rest
    else:
        out_ref, buf, sem = rest
    R, k, _ = buf.shape

    def copy(j):
        row = pl.multiple_of(jnp.maximum(ids_ref[j], 0) * k, k)
        return pltpu.make_async_copy(
            db_hbm.at[pl.ds(row, k)], buf.at[j], sem.at[0]
        )

    # invalid (-1) slots — padding and already-visited neighbors — read
    # nothing; their scratch rows hold stale data the wrapper masks to +inf
    def start(j, c):
        @pl.when(ids_ref[j] >= 0)
        def _():
            copy(j).start()
        return c

    def wait(j, c):
        @pl.when(ids_ref[j] >= 0)
        def _():
            copy(j).wait()
        return c

    jax.lax.fori_loop(0, R, start, 0)
    jax.lax.fori_loop(0, R, wait, 0)
    v = buf[...].astype(jnp.float32)                 # (R, k, W)
    if cosine:
        t = (v * inv_ref[...]) * q_ref[...]          # precomputed 1/‖v‖
    else:
        t = (v - q_ref[...]) ** 2
    # fold the k lane rows first (elementwise), then one lane reduction
    d = jnp.sum(jnp.sum(t, axis=1), axis=-1, keepdims=True)   # (R, 1)
    out_ref[...] = 1.0 - d if cosine else d


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_dist(
    ids: jax.Array,   # (R,) int32 row ids, -1 = invalid
    db: jax.Array,    # (N·k, W) row view: ``lane_rows(db)`` on TPU, or the
                      # plain (N, d) array (k = 1)
    q: jax.Array,     # (k·W,) fp32 query (pre-normalized under cosine)
    inv_norms=None,   # (N,) fp32 1/‖row‖ — presence selects the cosine body
    *,
    interpret: bool = False,
) -> jax.Array:
    """(R,) masked distances with the gather done inside the kernel."""
    R0 = ids.shape[0]
    W = db.shape[1]
    k = q.shape[0] // W
    # whole sublane tiles of slots: Mosaic cannot lay out a (1, k·W) block
    R = -(-R0 // 8) * 8
    ids = jnp.pad(ids.astype(jnp.int32), (0, R - R0), constant_values=-1)
    const = lambda nd: (lambda i, ids: (0,) * nd)  # noqa: E731
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((k, W), const(2)),
    ]
    operands = [ids, db, q.astype(jnp.float32).reshape(k, W)]
    cosine = inv_norms is not None
    if cosine:
        in_specs.append(pl.BlockSpec((R, 1, 1), const(3)))
        operands.append(inv_norms[jnp.maximum(ids, 0)].reshape(R, 1, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((R, 1), const(2)),
        scratch_shapes=[
            pltpu.VMEM((R, k, W), db.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_rows_kernel, cosine=cosine),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
    )(*operands)
    return jnp.where(ids >= 0, out[:, 0], INF)[:R0]
