"""ISSUE 10 kernel-stack equivalence + quantization properties.

The contract the bandwidth-optimized kernels must hold:

- ``kernel="fused"`` is **bit-for-bit** the ``xla`` search in fp32 — same
  ids AND same dists, both metrics, odd R/d, interpret mode on CPU.
- ``kernel="fused_q8"`` steers with approximate int8 distances but reranks
  the top ``k·rerank_mult`` exactly, so recall@10 stays within 0.5pt of the
  fp32 search (the bench gate bound, tested here on a tiny index).
- The quantizer's integer zero-point makes padded dimensions dequantize to
  exactly 0.0 (odd ``d`` needs no masking anywhere downstream).
- ``bytes_read`` telemetry follows the documented traffic model.
- Switching kernels never grows the jit cache after warmup.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI container has no hypothesis; run fixed examples
    from _hypothesis_fallback import given, settings, st

from repro.graphs.knn import exact_knn, recall_at_k
from repro.graphs.params import SearchParams
from repro.graphs.search import batched_search, search_jit_cache_size
from repro.kernels.gather_dist import INF, gather_rows_dist
from repro.quant import QuantizedDb, dequantize, quantize_db


def _problem(n=200, d=24, R=8, n_q=6, seed=0):
    """Random db + random graph with -1 holes (masking must be exercised)."""
    rng = np.random.default_rng(seed)
    db = rng.standard_normal((n, d)).astype(np.float32)
    nbrs = rng.integers(0, n, (n, R)).astype(np.int32)
    nbrs[rng.random((n, R)) < 0.1] = -1
    q = rng.standard_normal((n_q, d)).astype(np.float32)
    entries = rng.integers(0, n, (n_q, 2)).astype(np.int32)
    return (jnp.asarray(db), jnp.asarray(nbrs), jnp.asarray(q),
            jnp.asarray(entries))


def _knn_problem(n=400, d=64, R=10, n_q=32, seed=0, unit=False):
    """KNN-graph problem where beam search actually reaches high recall.
    ``unit=True`` puts db and queries on the unit sphere, where the L2
    graph and ground truth are the cosine ones too."""
    rng = np.random.default_rng(seed)
    db = rng.standard_normal((n, d)).astype(np.float32)
    if unit:
        db /= np.linalg.norm(db, axis=1, keepdims=True)
    ids, _ = exact_knn(db, db, R + 1)
    nbrs = np.asarray(ids)[:, 1:].astype(np.int32)   # drop self-edge
    q = (db[rng.integers(0, n, n_q)]
         + 0.1 * rng.standard_normal((n_q, d))).astype(np.float32)
    if unit:
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    gt, _ = exact_knn(q, db, 10)
    entries = rng.integers(0, n, (n_q, 2)).astype(np.int32)
    return db, nbrs, q, entries, np.asarray(gt)


# ------------------------------------------- fused == xla, bit for bit (fp32)
@settings(deadline=None, max_examples=6)
@given(R=st.integers(min_value=3, max_value=11),
       d=st.integers(min_value=5, max_value=40))
def test_fused_matches_xla_bitwise(R, d):
    """Property: the in-kernel gather search returns identical ids AND
    bitwise-identical dists to the XLA formulation — both metrics, odd
    R and d included (interpret mode runs the kernel body on CPU)."""
    db, nbrs, q, entries = _problem(d=d, R=R, seed=1000 * R + d)
    for metric in ("l2", "cosine"):
        sp = SearchParams(k=5, beam_width=8, max_hops=24, metric=metric)
        a = batched_search(db, nbrs, q, entries, sp)
        b = batched_search(
            db, nbrs, q, entries,
            sp.replace(kernel="fused", kernel_interpret=True),
        )
        np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
        np.testing.assert_array_equal(
            np.asarray(a.dists), np.asarray(b.dists)
        )


@settings(deadline=None, max_examples=6)
@given(R=st.integers(min_value=1, max_value=9),
       d=st.integers(min_value=3, max_value=50))
def test_gather_rows_kernel_bitwise(R, d):
    """Kernel-level property: ``gather_rows_dist`` (interpret) vs the jitted
    matched XLA formulation, invalid ids masked to the same INF constant."""
    rng = np.random.default_rng(10 * R + d)
    db = jnp.asarray(rng.standard_normal((64, d)).astype(np.float32))
    qv = jnp.asarray(rng.standard_normal((d,)).astype(np.float32))
    ids_np = rng.integers(0, 64, R).astype(np.int32)
    ids_np[::3] = -1
    ids = jnp.asarray(ids_np)
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(db, axis=-1), 1e-9)
    qn = qv / jnp.maximum(jnp.linalg.norm(qv), 1e-9)

    @jax.jit
    def ref_l2(ids, db, q):
        v = db[jnp.maximum(ids, 0)].astype(jnp.float32)
        return jnp.where(ids >= 0, jnp.sum((v - q) ** 2, axis=-1), INF)

    @jax.jit
    def ref_cos(ids, db, qn, inv):
        v = db[jnp.maximum(ids, 0)].astype(jnp.float32)
        vn = v * inv[jnp.maximum(ids, 0)][:, None]
        return jnp.where(ids >= 0, 1.0 - jnp.sum(vn * qn, axis=-1), INF)

    np.testing.assert_array_equal(
        np.asarray(gather_rows_dist(ids, db, qv, interpret=True)),
        np.asarray(ref_l2(ids, db, qv)),
    )
    np.testing.assert_array_equal(
        np.asarray(gather_rows_dist(ids, db, qn, inv, interpret=True)),
        np.asarray(ref_cos(ids, db, qn, inv)),
    )


# --------------------------------------------------------- int8 quantization
@settings(deadline=None, max_examples=6)
@given(n=st.integers(min_value=2, max_value=40),
       d=st.integers(min_value=1, max_value=300))
def test_quant_roundtrip_and_exact_zero_pads(n, d):
    rng = np.random.default_rng(n * 1000 + d)
    db = (5.0 * rng.standard_normal((n, d))).astype(np.float32)
    qdb = quantize_db(db)
    deq = dequantize(qdb)                          # (n, nb*block)
    # reconstruction error bounded by half a step per element
    err = np.abs(deq[:, :d] - db)
    nb = qdb.n_blocks
    step = np.repeat(np.asarray(qdb.scale), qdb.block, axis=1)[:, :d]
    assert np.all(err <= 0.5 * step + 1e-6)
    # padded dims reconstruct to EXACTLY 0.0 (integer zero-point property)
    if deq.shape[1] > d:
        assert np.array_equal(deq[:, d:], np.zeros_like(deq[:, d:]))
    # codebook invariants
    assert qdb.codes.shape == (n, nb * qdb.block)
    assert qdb.codes.dtype == np.int8
    assert np.all(np.abs(np.asarray(qdb.codes)) <= 127)


def test_quant_roundtrip_offset_blocks():
    """Regression (REVIEW): blocks that don't span 0 — all-positive /
    offset values, e.g. ReLU-derived features — must still reconstruct
    within half a quantization step.  A clamped zero-point saturates every
    code in such blocks to ±127 and the whole block dequantizes to one
    wrong value (error ≈ the offset, not the half-step bound); the fix
    extends each block's range to include 0 so zp ∈ [-127, 127] by
    construction."""
    rng = np.random.default_rng(42)
    for off in (10.5, -7.25, 200.0):
        db = (off + 0.1 * rng.standard_normal((20, 37))).astype(np.float32)
        qdb = quantize_db(db)
        deq = dequantize(qdb)
        step = np.repeat(np.asarray(qdb.scale), qdb.block, axis=1)[:, :37]
        err = np.abs(deq[:, :37] - db)
        assert np.all(err <= 0.5 * step + 1e-5), (off, err.max())
        # padded dims still reconstruct to EXACTLY 0.0 (every block spans 0)
        assert np.array_equal(deq[:, 37:], np.zeros_like(deq[:, 37:]))


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_q8_rerank_recall_within_bound(metric):
    """fused_q8 + exact rerank holds recall@10 within the bench-gate bound
    (0.5pt) of the fp32 search on a KNN graph, under both metrics."""
    db, nbrs, q, entries, gt = _knn_problem(unit=metric == "cosine")
    qdb = quantize_db(db)
    quant = QuantizedDb(*(jnp.asarray(a) for a in qdb))
    dbj, nbrsj = jnp.asarray(db), jnp.asarray(nbrs)
    qj, ej = jnp.asarray(q), jnp.asarray(entries)
    sp = SearchParams(k=10, beam_width=32, max_hops=64, metric=metric)
    base = batched_search(dbj, nbrsj, qj, ej, sp)
    q8 = batched_search(dbj, nbrsj, qj, ej, sp.replace(kernel="fused_q8"),
                        quant=quant)
    r_base = recall_at_k(np.asarray(base.ids), gt, 10)
    r_q8 = recall_at_k(np.asarray(q8.ids), gt, 10)
    assert r_base > 0.9, f"baseline search too weak ({r_base}) to compare"
    assert r_q8 >= r_base - 0.005, (r_base, r_q8)


def test_q8_requires_codebook():
    db, nbrs, q, entries = _problem()
    sp = SearchParams(k=5, kernel="fused_q8")
    with pytest.raises(ValueError, match="codebook"):
        batched_search(db, nbrs, q, entries, sp)


# --------------------------------------------------- db_lane (fused on TPU)
def test_db_lane_operand_threads_through_search():
    """The precomputed lane-aligned db copy is an ordinary extra operand:
    passing it must not change any result (only the real-TPU fused path
    reads it; here it rides through jit/vmap unused)."""
    db, nbrs, q, entries = _problem(d=20, R=8, seed=5)
    db_lane = jnp.pad(db, ((0, 0), (0, (-db.shape[1]) % 128)))
    for kern, interp in (("xla", False), ("fused", True)):
        sp = SearchParams(k=5, beam_width=8, max_hops=16, kernel=kern,
                          kernel_interpret=interp)
        a = batched_search(db, nbrs, q, entries, sp)
        b = batched_search(db, nbrs, q, entries, sp, db_lane=db_lane)
        np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
        np.testing.assert_array_equal(
            np.asarray(a.dists), np.asarray(b.dists)
        )


def test_fused_tpu_path_uses_precomputed_db_lane(monkeypatch):
    """Regression (REVIEW): on the real-TPU fused path with d % 128 != 0
    the kernel must read the caller's precomputed lane-aligned copy —
    re-padding the (N, d) database inside the jitted per-search program
    traces an O(N·d) HBM allocation + copy into every batch."""
    import importlib

    import repro.kernels.ops as ops
    from repro.graphs import search as S

    # the package re-exports a *function* named gather_dist which shadows
    # the submodule attribute, so resolve the module via importlib
    gd = importlib.import_module("repro.kernels.gather_dist")

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    seen = {}

    def fake_gather(ids, db, q, inv_norms=None, *, interpret=False):
        seen["db"] = db
        return jnp.zeros((ids.shape[0],), jnp.float32)

    monkeypatch.setattr(gd, "gather_rows_dist", fake_gather)
    rng = np.random.default_rng(0)
    db = jnp.asarray(rng.standard_normal((32, 20)).astype(np.float32))
    db_lane = jnp.pad(db, ((0, 0), (0, 108)))
    dist_to, _, _ = S._make_dist_fns(
        db, db[0], metric="l2", kernel="fused", kernel_interpret=False,
        inv_norms=None, quant=None, db_lane=db_lane,
    )
    dist_to(jnp.arange(4, dtype=jnp.int32))
    assert seen["db"] is db_lane


# ------------------------------------------------------- bytes_read telemetry
def test_bytes_read_follows_traffic_model():
    db, nbrs, q, entries = _problem(n=150, d=20, R=8, seed=3)
    R, d = nbrs.shape[1], db.shape[1]
    for metric, vec_bytes in (("l2", d * 4), ("cosine", d * 4 + 4)):
        sp = SearchParams(k=5, beam_width=8, max_hops=16, metric=metric,
                          instrument=True)
        _, tele = batched_search(db, nbrs, q, entries, sp)
        expect = (np.asarray(tele.dist_evals) * vec_bytes
                  + np.asarray(tele.hops) * R * 4)
        got = np.asarray(tele.bytes_read)
        assert got.dtype == np.float32  # int32 wraps for wide vectors
        np.testing.assert_array_equal(got, expect.astype(np.float32))


def test_bytes_read_wide_vectors_no_int32_wrap():
    """Regression (REVIEW): the traffic model is float32 on device — with
    wide rows (d=4096 fp32 = 16 KiB) an int32 count wraps negative at ~131k
    evals/query and poisons the ``search.bytes_read`` registry counter."""
    from repro.obs.registry import MetricsRegistry
    from repro.obs.telemetry import SearchTelemetry, record_search_telemetry

    per_query = 200_000.0 * 16_384.0            # ≈ 3.3e9 ≫ int32 max
    z = np.zeros((2,), np.int32)
    tele = SearchTelemetry(
        hops=np.full((2,), 1000, np.int32),
        dist_evals=np.full((2,), 200_000, np.int32),
        ring_evictions=z, converged_hop=z, nav_hops=z,
        entry_dist=np.zeros((2,), np.float32),
        entry_rank_proxy=np.ones((2,), np.float32),
        bytes_read=np.full((2,), per_query, np.float32),
    )
    reg = MetricsRegistry()
    record_search_telemetry(tele, reg)
    val = reg.get("search.bytes_read").value
    assert val == pytest.approx(2 * per_query)
    assert val > 0


def test_bytes_read_q8_below_fp32_at_wide_d():
    """At d=128 the quantized walk reads ~3-4x fewer bytes than fp32 (the
    whole point of the codebook); rerank adds back a few exact rows."""
    rng = np.random.default_rng(0)
    db = jnp.asarray(rng.standard_normal((200, 128)).astype(np.float32))
    nbrs = jnp.asarray(rng.integers(0, 200, (200, 8)).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((4, 128)).astype(np.float32))
    entries = jnp.asarray(rng.integers(0, 200, (4, 2)).astype(np.int32))
    quant = QuantizedDb(
        *(jnp.asarray(a) for a in quantize_db(np.asarray(db)))
    )
    sp = SearchParams(k=5, beam_width=8, max_hops=16, instrument=True)
    _, t_fp = batched_search(db, nbrs, q, entries, sp)
    _, t_q8 = batched_search(db, nbrs, q, entries,
                             sp.replace(kernel="fused_q8"), quant=quant)
    fp = float(np.asarray(t_fp.bytes_read).mean())
    q8 = float(np.asarray(t_q8.bytes_read).mean())
    assert q8 < fp / 2, (fp, q8)


# ---------------------------------------------------------- jit-cache hygiene
def test_kernel_switch_does_not_grow_jit_cache():
    """After one warmup per kernel, repeated searches with *fresh* (equal)
    SearchParams and fresh QuantizedDb wrappers over the same arrays must be
    pure cache hits."""
    db, nbrs, q, entries = _problem(n=150, d=24, R=8, seed=11)
    qdb = quantize_db(np.asarray(db))
    dev = tuple(jnp.asarray(a) for a in qdb)
    for kern in ("xla", "fused", "fused_q8"):
        sp = SearchParams(k=5, beam_width=8, max_hops=16, kernel=kern)
        kw = {"quant": QuantizedDb(*dev)} if kern == "fused_q8" else {}
        batched_search(db, nbrs, q, entries, sp, **kw)
    cache0 = search_jit_cache_size()
    for _ in range(3):
        for kern in ("xla", "fused", "fused_q8"):
            sp = SearchParams(k=5, beam_width=8, max_hops=16, kernel=kern)
            kw = {"quant": QuantizedDb(*dev)} if kern == "fused_q8" else {}
            batched_search(db, nbrs, q, entries, sp, **kw)
    assert search_jit_cache_size() == cache0


# ------------------------------------------------------ platform dispatch
def test_interpret_mode_refused_on_tpu(monkeypatch):
    """On the chip the compiled kernel runs: interpret mode there would be
    a silent slow fallback on the served path."""
    import repro.kernels.ops as ops
    from repro.graphs import search as S

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    db = jnp.zeros((16, 8), jnp.float32)
    with pytest.raises(ValueError, match="kernel_interpret"):
        S._make_dist_fns(
            db, db[0], metric="l2", kernel="fused", kernel_interpret=True,
            inv_norms=None, quant=None,
        )


def test_platform_check_does_not_swallow_backend_errors(monkeypatch):
    """A backend that fails to start must raise, not read as 'not a TPU'
    and quietly take the XLA path."""
    import repro.kernels.ops as ops

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(ops.jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        ops._on_tpu()
