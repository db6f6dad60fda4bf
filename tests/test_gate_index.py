"""End-to-end GateIndex: build, search, ablations, persistence, speed-up."""
import os

import numpy as np
import pytest

from repro.core import GateConfig, GateIndex
from repro.data.synthetic import make_database, train_eval_query_split
from repro.graphs.knn import exact_knn, recall_at_k

GCFG = GateConfig(n_hubs=48, epochs=60, batch_hubs=48, subgraph_max_nodes=64)


@pytest.fixture(scope="module")
def built_index():
    from repro.graphs.nsg import build_nsg

    db, _ = make_database("sift10m-like", 2000, seed=0)
    nsg = build_nsg(db, R=32, knn_k=32, search_l=64, pool_size=96)
    tq, eq = train_eval_query_split(db, 384, 96)
    idx = GateIndex.from_graph(db, nsg.neighbors, nsg.enter_id, tq, GCFG)
    return idx, eq


def test_build_report_complete(built_index):
    idx, _ = built_index
    rep = idx.build_report
    assert rep["loss_last"] < rep["loss_first"]
    assert rep["samples"]["hub_with_no_pos"] == 0


def test_search_beats_baseline_at_matched_budget(built_index):
    idx, eq = built_index
    true_ids, _ = exact_knn(eq, idx.db, 10)
    res_g = idx.search(eq, k=10, beam_width=32, max_hops=128)
    res_b = idx.search_baseline(eq, k=10, beam_width=32, max_hops=128)
    rec_g = recall_at_k(np.asarray(res_g.ids), true_ids, 10)
    rec_b = recall_at_k(np.asarray(res_b.ids), true_ids, 10)
    assert rec_g >= rec_b - 0.02, (rec_g, rec_b)  # GATE ≥ baseline (margin)


def test_entry_points_are_hubs(built_index):
    idx, eq = built_index
    entries = np.asarray(idx.select_entries(eq[:16]))
    assert np.isin(entries, idx.hubs.ids).all()


def test_save_load_roundtrip(built_index, tmp_path):
    idx, eq = built_index
    path = os.path.join(tmp_path, "gate.pkl")
    idx.save(path)
    idx2 = GateIndex.load(path)
    r1 = idx.search(eq[:8], k=5, beam_width=16, max_hops=64)
    r2 = idx2.search(eq[:8], k=5, beam_width=16, max_hops=64)
    np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))


def test_search_kwargs_caches_lane_aligned_db(monkeypatch):
    """Regression (REVIEW): real-TPU ``fused`` search with d % 128 != 0 gets
    ONE cached lane-aligned db copy from ``_search_kwargs`` — never a
    re-pad inside the jitted search program.  Off TPU (and in interpret
    mode, which runs unpadded) the operand is absent, keeping treedefs
    consistent per SearchParams value."""
    from repro.graphs.nsg import build_nsg
    from repro.graphs.params import SearchParams
    import repro.kernels.ops as ops

    rng = np.random.default_rng(9)
    db = rng.standard_normal((300, 36)).astype(np.float32)
    nsg = build_nsg(db, R=8, knn_k=8, search_l=16, pool_size=24)
    tq, _ = train_eval_query_split(db, 64, 16)
    g = GateConfig(n_hubs=8, epochs=4, batch_hubs=8, subgraph_max_nodes=24)
    idx = GateIndex.from_graph(db, nsg.neighbors, nsg.enter_id, tq, g)
    sp = SearchParams(k=5, kernel="fused")
    assert "db_lane" not in idx._search_kwargs(sp)   # CPU: XLA fallback
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    kw = idx._search_kwargs(sp)
    assert kw["db_lane"].shape == (300, 128)
    np.testing.assert_array_equal(np.asarray(kw["db_lane"][:, :36]), db)
    np.testing.assert_array_equal(
        np.asarray(kw["db_lane"][:, 36:]), 0.0
    )
    assert idx._search_kwargs(sp)["db_lane"] is kw["db_lane"]  # cached once
    assert "db_lane" not in idx._search_kwargs(
        sp.replace(kernel_interpret=True)
    )


@pytest.mark.parametrize("kernel", ["xla", "fused_q8"])
def test_lower_search_is_the_served_program(built_index, kernel):
    """``lower_search`` lowers the call ``search`` makes: its compiled
    program, fed the operands ``search`` passes, returns the same ids."""
    from repro.graphs.params import SearchParams

    idx, eq = built_index
    sp = SearchParams(k=10, beam_width=32, max_hops=128, kernel=kernel)
    compiled = idx.lower_search(eq, params=sp).compile()
    args, kw = idx._search_args(eq, idx.select_entries(eq), sp)
    out = compiled(*args[:4], kw.get("inv_norms"), kw.get("quant"),
                   kw.get("db_lane"))
    np.testing.assert_array_equal(
        np.asarray(out.ids), np.asarray(idx.search(eq, params=sp).ids)
    )


def _eager_entries(idx, queries):
    """Entry selection composed op by op, as ``select_entries`` ran before
    it was one jitted program."""
    import jax
    import jax.numpy as jnp

    from repro.core import navgraph as ng
    from repro.core.twotower import query_tower
    from repro.kernels import ops

    dev = idx._device()
    z_q = query_tower(idx.tower_params, idx.tower_cfg,
                      jnp.asarray(queries, jnp.float32))
    w = idx.gcfg.probe_width
    if idx.hubs.n <= idx.gcfg.flat_score_max:
        scores = ops.twotower_score(z_q, dev["nav"].reps)
        if w == 1:
            hub_local = jnp.argmax(scores, axis=1)[:, None]
        else:
            _, hub_local = jax.lax.top_k(scores, w)
        nav_hops = jnp.zeros((len(queries),), jnp.int32)
    else:
        hub_local, nav_hops = ng.descend(dev["nav"], z_q, probe_width=w,
                                         instrument=True)
    return dev["hub_ids"][hub_local], nav_hops


@pytest.mark.parametrize("path", ["flat", "nav"])
@pytest.mark.parametrize("probe_width", [1, 3])
def test_select_entries_program_matches_eager(built_index, path,
                                              probe_width):
    """The jitted entry-selection program returns the ids (and nav hops)
    of the eager composition, on the flat-score and nav-descent paths, in
    one XLA module whose name holds ``select_entries``."""
    import dataclasses

    from repro.core.gate_index import gate_select_entries

    base, eq = built_index
    gcfg = dataclasses.replace(
        base.gcfg, probe_width=probe_width,
        flat_score_max=base.gcfg.flat_score_max if path == "flat" else 0)
    idx = dataclasses.replace(base, gcfg=gcfg, _dev=None)
    assert (idx.hubs.n <= gcfg.flat_score_max) == (path == "flat")
    want_ids, want_hops = _eager_entries(idx, eq)
    ids = idx.select_entries(eq)
    got_ids, got_hops = idx.select_entries(eq, instrument=True)
    assert ids.shape == (len(eq), probe_width)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(got_hops),
                                  np.asarray(want_hops))
    nav = idx._device()["nav"]
    text = gate_select_entries.lower(
        idx.tower_params, eq, nav.reps, nav.neighbors,
        idx._device()["hub_ids"], tower_cfg=idx.tower_cfg,
        nav_start=nav.start, flat=path == "flat",
        probe_width=probe_width).as_text()
    assert "module @jit_gate_select_entries" in text


def test_ablation_variants_build():
    """GATE w/o H / w/o FE / w/o L all construct and search (Table 4)."""
    from repro.graphs.nsg import build_nsg

    db, _ = make_database("sift10m-like", 800, seed=4)
    nsg = build_nsg(db, R=12, knn_k=12, search_l=16, pool_size=32)
    tq, eq = train_eval_query_split(db, 128, 32)
    for kw in (
        {"use_hbkm": False}, {"use_fusion": False}, {"use_contrastive": False}
    ):
        g = GateConfig(n_hubs=12, epochs=10, batch_hubs=12,
                       subgraph_max_nodes=32, **kw)
        idx = GateIndex.from_graph(db, nsg.neighbors, nsg.enter_id, tq, g)
        res = idx.search(eq, k=5, beam_width=16, max_hops=64)
        assert np.asarray(res.ids).shape == (32, 5)


# ------------------------------------------------------------ inner product
# SHA-256 of an l2 GateIndex.build at a fixed seed, taken on the code before
# the build learned inner product: the l2 build may not move by a bit.
L2_BUILD_PINS = {
    "neighbors":
        "4662a6ab361ee23174c519b77cdfcd9a9524733d7619875d7cc3640663edf6ab",
    "hubs": "c23d08ae24be6f1096ac7487a9335ea4f9d51fb42c0566312c995808c31490df",
    "enter_id": 228,
    "entries":
        "df1f67d9ab4baad184c6b651450ec59550f0ddd19d3446312ae3c70717251567",
}


def _sha(a) -> str:
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def test_l2_build_is_bit_identical_to_before_ip():
    db, _ = make_database("sift10m-like", 1500, seed=5)
    tq, eq = train_eval_query_split(db, 128, 64)
    g = GateConfig(n_hubs=12, epochs=8, batch_hubs=12, subgraph_max_nodes=32)
    idx = GateIndex.build(db, tq, g, R=12, knn_k=12, search_l=24,
                          pool_size=32)
    assert idx.gcfg.metric == "l2"
    got = {"neighbors": _sha(idx.neighbors), "hubs": _sha(idx.hubs.ids),
           "enter_id": int(idx.enter_id),
           "entries": _sha(np.asarray(idx.select_entries(eq)))}
    assert got == L2_BUILD_PINS


def _ip_corpus(n, d=200, seed=0):
    """Clustered rows whose norms differ, training and evaluation queries
    drawn near rows and moved off them by a fixed offset."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((24, d))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    db = (centers[rng.integers(0, 24, n)]
          + 0.04 * rng.standard_normal((n, d))) * rng.uniform(0.8, 1.2,
                                                               (n, 1))
    offset = 0.3 * rng.standard_normal(d) / np.sqrt(d)

    def queries(m):
        base = db[rng.integers(0, n, m)]
        return base + offset + 0.03 * rng.standard_normal((m, d))

    f32 = lambda a: a.astype(np.float32)
    return f32(db), f32(queries(384)), f32(queries(128))


def _ip_truth(db, q, k=10):
    """The plain reference: float64 ``-q·x`` top-k."""
    d = -(q.astype(np.float64) @ db.astype(np.float64).T)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


@pytest.fixture(scope="module")
def ip_index():
    db, tq, eq = _ip_corpus(4000)
    g = GateConfig(n_hubs=24, epochs=40, batch_hubs=24,
                   subgraph_max_nodes=48, metric="ip")
    idx = GateIndex.build(db, tq, g, R=24, knn_k=24, search_l=48,
                          pool_size=64)
    return idx, eq


def _ip_params(**kw):
    from repro.graphs.params import SearchParams

    return SearchParams(**{"k": 10, "beam_width": 64, "max_hops": 256,
                           "metric": "ip", **kw})


def test_ip_build_reduces_and_keeps_the_rows(ip_index):
    """The graph and hubs come from the reduced (d + 1)-wide rows; the index
    keeps and serves the original rows, and its towers see d-wide input."""
    idx, _ = ip_index
    assert idx.db.shape == (4000, 200)
    assert idx.hubs.centroids.shape[1] == 201
    assert idx.tower_cfg.d_p == 200
    np.testing.assert_array_equal(idx.db, _ip_corpus(4000)[0])


def test_ip_index_search_reaches_recall(ip_index):
    """GATE entries, then the ``ip`` walk: recall@10 against the float64
    reference of at least 0.75 (it reads 0.835 at beam 64 on this corpus),
    with every distance the reference's ``-q·x`` of its id."""
    idx, eq = ip_index
    res = idx.search(eq, params=_ip_params())
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    assert recall_at_k(ids, _ip_truth(idx.db, eq), 10) >= 0.75
    want = -np.einsum("qd,qkd->qk", eq.astype(np.float64),
                      idx.db[ids].astype(np.float64))
    np.testing.assert_allclose(dists, want, rtol=0, atol=1e-5)


def test_ip_index_save_load_keeps_the_metric(ip_index, tmp_path):
    idx, eq = ip_index
    path = os.path.join(tmp_path, "ip.pkl")
    idx.save(path)
    idx2 = GateIndex.load(path)
    assert idx2.gcfg.metric == "ip"
    r1 = idx.search(eq[:16], params=_ip_params())
    r2 = idx2.search(eq[:16], params=_ip_params())
    np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
    with pytest.raises(ValueError, match=r"'ip'.*metric='l2'"):
        idx2.search(eq[:16], k=10)


@pytest.mark.parametrize("call", ["search", "search_baseline",
                                  "warmup_ladder", "lower_search"])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_ip_index_refuses_other_metrics(ip_index, call, metric):
    from repro.obs import LadderRung

    idx, eq = ip_index
    sp = _ip_params(metric=metric)
    run = {
        "search": lambda: idx.search(eq, params=sp),
        "search_baseline": lambda: idx.search_baseline(eq, params=sp),
        "warmup_ladder": lambda: idx.warmup_ladder(
            (LadderRung(16, 32),), batch_size=4, params=sp),
        "lower_search": lambda: idx.lower_search(eq, params=sp),
    }[call]
    with pytest.raises(ValueError,
                       match=rf"built under metric='ip'.*metric='{metric}'"):
        run()


@pytest.mark.parametrize("call", ["search", "search_baseline"])
def test_l2_index_refuses_ip(built_index, call):
    idx, eq = built_index
    with pytest.raises(ValueError,
                       match=r"built under metric='l2'.*metric='ip'"):
        getattr(idx, call)(eq, params=_ip_params())


def test_gate_config_refuses_an_unknown_metric():
    with pytest.raises(ValueError, match=r"\('l2', 'ip'\).*'cosine'"):
        GateConfig(metric="cosine")


def test_daemon_serves_an_ip_index_by_its_metric(ip_index):
    """The daemon takes its metric from the index: warmed and asked with no
    params, it serves the ``ip`` search."""
    from repro.obs import LadderRung
    from repro.serve.daemon import ServeDaemon

    idx, eq = ip_index
    rung = LadderRung(64, 256)
    daemon = ServeDaemon(idx, ladder=(rung,), adaptive=False, level=0,
                         batch_size=32, k=10)
    assert daemon.base_params.metric == "ip"
    daemon.start(warmup=True)
    try:
        res, _ = daemon.search(eq[:32])
    finally:
        daemon.stop()
    want = idx.search(eq[:32], params=_ip_params())
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(want.ids))


@pytest.mark.parametrize("fixture", ["built_index", "ip_index"])
def test_telemetry_span_sums_converged_hops(request, fixture, monkeypatch):
    """``gate.search.telemetry`` carries its batch's summed
    ``converged_hop`` and its query count while the tracer records."""
    import repro.obs.trace as trace_mod

    idx, eq = request.getfixturevalue(fixture)
    sp = _ip_params(metric=idx.gcfg.metric, instrument=True)
    t = trace_mod.Tracer()
    monkeypatch.setattr(trace_mod, "_TRACER", t)
    t.start()
    try:
        _, tele = idx.search(eq, params=sp)
    finally:
        t.stop()
    spans = [e for e in t.events() if e["name"] == "gate.search.telemetry"]
    assert len(spans) == 1
    assert spans[0]["args"] == {
        "converged_hop_sum": int(np.asarray(tele.converged_hop).sum()),
        "queries": len(eq)}
