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
