"""Proximity graph substrate: KNN, NSG construction, beam search recall."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import make_database, make_queries_in_dist
from repro.graphs import search
from repro.graphs.knn import exact_knn, knn_graph, medoid, recall_at_k
from repro.graphs.nsg import build_nsg
from repro.graphs.params import SearchParams
from repro.graphs.search import (
    INF,
    batched_search,
    beam_search_fixed,
    greedy_descent,
)


def test_exact_knn_matches_numpy():
    rng = np.random.default_rng(0)
    db = rng.standard_normal((200, 16)).astype(np.float32)
    q = rng.standard_normal((10, 16)).astype(np.float32)
    ids, dists = exact_knn(q, db, 5)
    d_full = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    expect = np.argsort(d_full, axis=1)[:, :5]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(expect, 1))
    np.testing.assert_allclose(
        np.sort(dists, 1), np.sort(np.take_along_axis(d_full, expect, 1), 1),
        rtol=1e-4, atol=1e-3,
    )


def test_knn_graph_excludes_self():
    rng = np.random.default_rng(1)
    db = rng.standard_normal((128, 8)).astype(np.float32)
    g = knn_graph(db, 4)
    assert (g != np.arange(128)[:, None]).all()


def test_nsg_connectivity(small_db, small_nsg):
    db, _ = small_db
    nsg = small_nsg
    n = nsg.n
    seen = np.zeros(n, bool)
    stack = [nsg.enter_id]
    seen[nsg.enter_id] = True
    while stack:
        u = stack.pop()
        for v in nsg.neighbors[u]:
            if v >= 0 and not seen[v]:
                seen[v] = True
                stack.append(int(v))
    assert seen.all(), f"{(~seen).sum()} nodes unreachable from medoid"


def test_repair_attaches_each_component_once():
    """Connectivity repair attaches an unreachable component through one
    edge and then counts everything that node reaches as reachable (NSG
    tree_grow) — not one repair edge per unreachable node."""
    from repro.graphs.nsg import _repair_connectivity

    rng = np.random.default_rng(0)
    db = np.concatenate([
        rng.standard_normal((10, 4)), 50.0 + rng.standard_normal((10, 4)),
    ]).astype(np.float32)
    ring = lambda lo: [[lo + (i + 1) % 10, -1] for i in range(10)]  # noqa: E731
    nbrs = np.asarray(ring(0) + ring(10), np.int32)
    out = _repair_connectivity(db, nbrs.copy(), 0)
    added = int((out >= 0).sum() - (nbrs >= 0).sum())
    assert added == 1
    seen = np.zeros(20, bool)
    stack, seen[0] = [0], True
    while stack:
        for v in out[stack.pop()]:
            if v >= 0 and not seen[v]:
                seen[v] = True
                stack.append(int(v))
    assert seen.all()


def test_nsg_degree_capped(small_nsg):
    assert (small_nsg.neighbors >= -1).all()
    assert small_nsg.neighbors.shape[1] == small_nsg.R


def test_beam_search_high_recall(uniform_db, uniform_nsg):
    """Machinery check on uniform data (clustered-data recall is the paper's
    Limitation I and is covered by the GATE-vs-baseline tests)."""
    db = uniform_db
    queries = make_queries_in_dist(db, 64, seed=7)
    true_ids, _ = exact_knn(queries, db, 10)
    entries = jnp.full((64, 1), uniform_nsg.enter_id, jnp.int32)
    res = batched_search(
        jnp.asarray(db), jnp.asarray(uniform_nsg.neighbors),
        jnp.asarray(queries), entries, beam_width=64, max_hops=256, k=10,
    )
    rec = recall_at_k(np.asarray(res.ids), true_ids, 10)
    assert rec > 0.9, f"recall@10 {rec}"
    assert (np.asarray(res.hops) > 0).all()


def test_beam_search_fixed_matches_while_variant(small_db, small_nsg):
    """The fixed-trip variant must find results at least as good (it never
    stops early)."""
    db, _ = small_db
    queries = make_queries_in_dist(db, 16, seed=9)
    entries = jnp.full((16, 1), small_nsg.enter_id, jnp.int32)
    res_w = batched_search(
        jnp.asarray(db), jnp.asarray(small_nsg.neighbors),
        jnp.asarray(queries), entries, beam_width=32, max_hops=64, k=5,
    )
    fixed = jax.vmap(
        lambda q, e: beam_search_fixed(
            jnp.asarray(db), jnp.asarray(small_nsg.neighbors), q, e,
            beam_width=32, num_hops=64,
        )[:2]
    )
    ids_f, d_f = fixed(jnp.asarray(queries), entries)
    assert float(d_f[:, 0].mean()) <= float(res_w.dists[:, 0].mean()) + 1e-3


def test_greedy_descent_reaches_local_min():
    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((64, 8)).astype(np.float32)
    g = knn_graph(vecs, 4)
    q = jnp.asarray(vecs[17] + 0.01 * rng.standard_normal(8).astype(np.float32))
    out = greedy_descent(
        jnp.asarray(vecs), jnp.asarray(g), q, jnp.asarray(0, jnp.int32),
        max_hops=64,
    )
    # result must be at least as close as every neighbor of the result
    d_out = float(((vecs[int(out)] - np.asarray(q)) ** 2).sum())
    for v in g[int(out)]:
        assert d_out <= ((vecs[v] - np.asarray(q)) ** 2).sum() + 1e-5


def test_greedy_descent_cosine_reaches_local_min():
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((64, 8)).astype(np.float32)
    g = knn_graph(vecs, 4)
    q = jnp.asarray(vecs[23] + 0.01 * rng.standard_normal(8).astype(np.float32))
    out = greedy_descent(
        jnp.asarray(vecs), jnp.asarray(g), q, jnp.asarray(0, jnp.int32),
        max_hops=64, metric="cosine",
    )
    qn = np.asarray(q) / np.linalg.norm(np.asarray(q))

    def cos_d(v):
        return 1.0 - (v / np.linalg.norm(v)) @ qn

    # result must be at least as cosine-close as every neighbor of the result
    d_out = cos_d(vecs[int(out)])
    for v in g[int(out)]:
        assert d_out <= cos_d(vecs[v]) + 1e-5


def test_greedy_descent_cosine_finds_scaled_target():
    """Cosine is scale-invariant: a rescaled db vector must still be found."""
    rng = np.random.default_rng(6)
    vecs = rng.standard_normal((128, 16)).astype(np.float32)
    g = knn_graph(vecs, 6)
    q = jnp.asarray(5.0 * vecs[40])  # same direction, different norm
    out = greedy_descent(
        jnp.asarray(vecs), jnp.asarray(g), q, jnp.asarray(0, jnp.int32),
        max_hops=128, metric="cosine",
    )
    qn = np.asarray(q) / np.linalg.norm(np.asarray(q))
    d_out = 1.0 - (vecs[int(out)] / np.linalg.norm(vecs[int(out)])) @ qn
    for v in g[int(out)]:
        d_v = 1.0 - (vecs[v] / np.linalg.norm(vecs[v])) @ qn
        assert d_out <= d_v + 1e-5


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_greedy_descent_instrument_identical(metric):
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((64, 8)).astype(np.float32)
    g = knn_graph(vecs, 4)
    q = jnp.asarray(rng.standard_normal(8).astype(np.float32))
    start = jnp.asarray(3, jnp.int32)
    out = greedy_descent(
        jnp.asarray(vecs), jnp.asarray(g), q, start, max_hops=64,
        metric=metric,
    )
    out_i, hops = greedy_descent(
        jnp.asarray(vecs), jnp.asarray(g), q, start, max_hops=64,
        metric=metric, instrument=True,
    )
    assert int(out) == int(out_i)
    assert 0 <= int(hops) <= 64


def test_batched_search_instrument_identical_ids_dists(
    uniform_db, uniform_nsg
):
    """instrument=True must not change search results (satellite, ISSUE 6)."""
    db = uniform_db
    queries = make_queries_in_dist(db, 32, seed=11)
    entries = jnp.full((32, 1), uniform_nsg.enter_id, jnp.int32)
    args = (
        jnp.asarray(db), jnp.asarray(uniform_nsg.neighbors),
        jnp.asarray(queries), entries,
    )
    kw = dict(beam_width=32, max_hops=128, k=10)
    res = batched_search(*args, **kw)
    res_i, tele = batched_search(*args, **kw, instrument=True)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_i.ids))
    np.testing.assert_array_equal(
        np.asarray(res.dists), np.asarray(res_i.dists)
    )
    np.testing.assert_array_equal(
        np.asarray(res.hops), np.asarray(tele.hops)
    )


def test_medoid_is_central(small_db):
    db, _ = small_db
    m = medoid(db)
    d_m = ((db[m] - db.mean(0)) ** 2).sum()
    rng = np.random.default_rng(0)
    rand = rng.integers(0, len(db), 50)
    d_r = ((db[rand] - db.mean(0)) ** 2).sum(1).mean()
    assert d_m < d_r


def _merge_top_l_argsort(ids_a, d_a, exp_a, ids_b, d_b):
    """The merge as it was first written: an argsort applied by indexing."""
    L = ids_a.shape[0]
    ids = jnp.concatenate([ids_a, ids_b])
    d = jnp.concatenate([d_a, d_b])
    expanded = jnp.concatenate([exp_a, jnp.zeros(ids_b.shape, jnp.bool_)])
    order = jnp.argsort(d)
    return ids[order][:L], d[order][:L], expanded[order][:L]


def _assert_bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    if a.dtype == np.float32:
        a, b = a.view(np.uint32), b.view(np.uint32)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("R", [32, 38])
@pytest.mark.parametrize("L", [64, 128])
def test_merge_top_l_matches_argsort_form(L, R):
    """The one-sort merge equals the argsort-and-index merge bit for bit,
    with distance ties inside and across the beam and the candidates,
    ``-1`` ids and ``INF`` slots, vmapped over a batch."""
    rng = np.random.default_rng(L * 100 + R)
    B = 64
    d_a = np.sort(rng.integers(0, 6, (B, L)).astype(np.float32), axis=1)
    ids_a = rng.integers(0, 1000, (B, L)).astype(np.int32)
    pad = rng.integers(0, L // 2, B)            # unfilled beam tails
    tail = np.arange(L)[None, :] >= (L - pad)[:, None]
    ids_a[tail], d_a[tail] = -1, INF
    exp_a = (rng.random((B, L)) < 0.5) & (ids_a >= 0)
    ids_b = rng.integers(0, 1000, (B, R)).astype(np.int32)
    d_b = rng.integers(0, 6, (B, R)).astype(np.float32)
    gone = rng.random((B, R)) < 0.3             # seen or absent neighbours
    ids_b[gone], d_b[gone] = -1, INF
    args = [jnp.asarray(x) for x in (ids_a, d_a, exp_a, ids_b, d_b)]
    got = jax.vmap(search._merge_top_l)(*args)
    want = jax.vmap(_merge_top_l_argsort)(*args)
    for g, w in zip(got, want):
        _assert_bits_equal(g, w)


@pytest.mark.parametrize("instrument", [False, True])
@pytest.mark.parametrize("R", [32, 38])
def test_batched_search_matches_argsort_merge(monkeypatch, R, instrument):
    """A search walks exactly as it did with the argsort merge: the same
    ids, distances, hops and distance evaluations, on whole-number vectors
    whose distances tie often and a graph with ``-1`` padding."""
    rng = np.random.default_rng(R)
    n, d, B = 600, 8, 32
    db = jnp.asarray(rng.integers(0, 4, (n, d)).astype(np.float32))
    nbrs = rng.integers(0, n, (n, R)).astype(np.int32)
    nbrs[rng.random((n, R)) < 0.2] = -1
    nbrs = jnp.asarray(nbrs)
    queries = jnp.asarray(rng.integers(0, 4, (B, d)).astype(np.float32))
    entries = jnp.asarray(rng.integers(0, n, (B, 2)).astype(np.int32))
    params = SearchParams(k=10, beam_width=64, max_hops=96,
                          instrument=instrument)
    # jit keeps a trace per function and signature across jit objects:
    # clear it, so each merge is traced, and none is left for other tests
    jax.clear_caches()
    got = batched_search(db, nbrs, queries, entries, params=params)
    with monkeypatch.context() as m:
        m.setattr(search, "_merge_top_l", _merge_top_l_argsort)
        jax.clear_caches()
        want = batched_search(db, nbrs, queries, entries, params=params)
    jax.clear_caches()
    if instrument:
        (got, tele), (want, tele_w) = got, want
        for g, w in zip(jax.tree.leaves(tele), jax.tree.leaves(tele_w)):
            _assert_bits_equal(g, w)
    for g, w in zip(got, want):
        _assert_bits_equal(g, w)
    assert int(np.asarray(got.hops).max()) > 1


# ------------------------------------------------------------ inner product
def _ip_data(n=2000, d=200, n_q=64, seed=0):
    """Clustered rows whose norms differ, and queries near rows of theirs."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((32, d))
    db = (centers[rng.integers(0, 32, n)] + 0.5 * rng.standard_normal((n, d))
          ) * rng.uniform(0.8, 1.2, (n, 1))
    q = db[rng.integers(0, n, n_q)] + 0.5 * rng.standard_normal((n_q, d))
    return db.astype(np.float32), q.astype(np.float32)


def _ip_topk(db, q, k):
    """The plain reference: float64 ``-q·x``, ascending."""
    d = -(q.astype(np.float64) @ db.astype(np.float64).T)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1), d


def test_mips_reduce_l2_order_is_ip_order():
    """Over the reduced rows the exact L2 top-k is the inner-product top-k,
    id for id, on data whose scores have no ties."""
    from repro.core.gate_index import mips_reduce

    db, q = _ip_data()
    rows, qs, m = mips_reduce(db, q)
    assert rows.shape == (2000, 201) and qs.shape == (64, 201)
    np.testing.assert_allclose(m, np.linalg.norm(db, axis=1).max(), rtol=1e-6)
    np.testing.assert_array_equal(rows[:, :200], db)
    np.testing.assert_array_equal(qs[:, 200], 0.0)
    want, _, scores = _ip_topk(db, q, 11)
    # the queries without ties among their first 11: float32 rounding of
    # the reduced rows moves a distance by about M²·2⁻²³, so a gap of 100
    # times that is no tie
    gaps = np.diff(np.take_along_axis(scores, want, axis=1), axis=1)
    keep = gaps.min(axis=1) > 100 * m * m * 2.0 ** -23
    assert keep.sum() >= len(q) // 2
    want, rows_q = want[keep, :10], qs[keep]
    l2 = ((rows_q[:, None, :].astype(np.float64)
           - rows[None].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_array_equal(np.argsort(l2, axis=1)[:, :10], want)
    got, _ = exact_knn(rows_q, rows, 10)
    np.testing.assert_array_equal(got, want)


@pytest.fixture(scope="module")
def ip_graph():
    from repro.core.gate_index import mips_reduce

    db, q = _ip_data(n=3000, seed=1)
    rows, _, _ = mips_reduce(db, q)
    nsg = build_nsg(rows, R=24, knn_k=24, search_l=48, pool_size=64)
    return db, q, nsg


def test_batched_search_ip_distances_match_reference(ip_graph):
    """Under ``ip`` the served distances are the float64 reference's
    ``-q·x`` of the same ids, ascending.  Tolerance: a float32 sum of
    d = 200 products, each at most ‖q‖·‖x‖ in size, is off by at most
    about d·2⁻²⁴·‖q‖·‖x‖ ≈ 1.2e-5·‖q‖·‖x‖."""
    db, q, nsg = ip_graph
    entries = jnp.full((len(q), 1), nsg.enter_id, jnp.int32)
    res = batched_search(
        jnp.asarray(db), jnp.asarray(nsg.neighbors), jnp.asarray(q), entries,
        params=SearchParams(k=10, beam_width=96, max_hops=512, metric="ip"))
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    assert (ids >= 0).all()
    assert np.all(np.diff(dists, axis=1) >= 0)
    want = -np.einsum("qd,qkd->qk", q.astype(np.float64),
                      db[ids].astype(np.float64))
    unit = (np.linalg.norm(q, axis=1)[:, None]
            * np.linalg.norm(db[ids], axis=-1))
    assert np.max(np.abs(dists - want) / unit) < 1.2e-5


def test_ip_search_walks_as_l2_over_the_reduced_rows(ip_graph):
    """The ``ip`` walk over the rows is the ``l2`` walk over the reduced
    rows: the same ids and hops, since ``‖q' − x'‖² = ‖q‖² + M² − 2 q·x``
    orders every candidate as ``-q·x`` does."""
    from repro.core.gate_index import mips_reduce

    db, q, nsg = ip_graph
    rows, qs, _ = mips_reduce(db, q)
    entries = jnp.full((len(q), 1), nsg.enter_id, jnp.int32)
    params = SearchParams(k=10, beam_width=64, max_hops=256, metric="ip")
    nbrs = jnp.asarray(nsg.neighbors)
    got = batched_search(jnp.asarray(db), nbrs, jnp.asarray(q), entries,
                         params=params)
    want = batched_search(jnp.asarray(rows), nbrs, jnp.asarray(qs), entries,
                          params=params.replace(metric="l2"))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.hops),
                                  np.asarray(want.hops))


def test_entry_rank_proxy_under_ip(ip_graph):
    """``entry_rank_proxy`` under ``ip`` is at least 1, and exactly 1 when
    the entry is the final top-1."""
    db, q, nsg = ip_graph
    true, _, _ = _ip_topk(db, q, 1)
    params = SearchParams(k=10, beam_width=64, max_hops=256, metric="ip",
                          instrument=True)
    args = (jnp.asarray(db), jnp.asarray(nsg.neighbors), jnp.asarray(q))
    m2 = jnp.max(jnp.sum(jnp.asarray(db) ** 2, axis=1))
    res, tele = batched_search(*args, jnp.asarray(true, jnp.int32),
                               params=params, mips_m2=m2)
    np.testing.assert_array_equal(np.asarray(res.ids)[:, 0], true[:, 0])
    np.testing.assert_array_equal(np.asarray(tele.entry_rank_proxy), 1.0)
    far = jnp.full((len(q), 1), nsg.enter_id, jnp.int32)
    res, tele = batched_search(*args, far, params=params, mips_m2=m2)
    proxy = np.asarray(tele.entry_rank_proxy)
    assert (proxy >= 1.0).all() and proxy.mean() > 1.0
    # without the operand the instrumented ip search is refused
    with pytest.raises(ValueError, match="mips_m2"):
        batched_search(*args, far, params=params)


@pytest.mark.parametrize("kernel", ["fused", "fused_q8"])
def test_ip_is_refused_off_the_xla_kernel(kernel):
    """The fused kernels score l2 and cosine: under ``ip`` they refuse by
    name, at the params and at the distance function, and never fall back
    to L2."""
    with pytest.raises(ValueError, match=rf'metric="ip".*{kernel}'):
        SearchParams(metric="ip", kernel=kernel)
    db = jnp.zeros((16, 8), jnp.float32)
    with pytest.raises(ValueError, match=rf'metric="ip".*{kernel}'):
        search.beam_search_single(
            db, jnp.zeros((16, 4), jnp.int32), db[0], jnp.zeros((1,),
                                                                jnp.int32),
            beam_width=8, max_hops=4, metric="ip", kernel=kernel)
