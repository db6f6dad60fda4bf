"""Observability layer: metrics registry, spans/trace, search telemetry."""
import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.graphs.knn import knn_graph
from repro.graphs.search import batched_search, beam_search_fixed
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import Tracer


# ---------------------------------------------------------------- registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("g")
    g.set(3.5)
    assert g.value == 3.5

    h = reg.histogram("h", buckets=(1, 2, 4, 8))
    h.observe(0.5)
    h.observe_many([1, 3, 100])
    assert h.count == 4
    assert h.sum == pytest.approx(104.5)
    snap = h.snapshot()
    # le=1 gets {0.5, 1}, le=4 gets {3}, +Inf gets {100}
    assert snap["counts"] == [2, 0, 1, 0, 1]


def test_registry_idempotent_and_type_checked():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    h = reg.histogram("h")
    c.inc()
    h.observe_many(np.arange(100))
    assert c.value == 0 and h.count == 0
    reg.enable()
    c.inc()
    assert c.value == 1


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("c")
    h = reg.histogram("h", buckets=(10, 100))

    def work():
        for i in range(1000):
            c.inc()
            h.observe(i % 7)

    threads = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.value == 8000
    assert h.count == 8000


def test_export_json_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("search.queries", "total queries").inc(7)
    reg.gauge("serve.tokens_per_sec").set(123.0)
    h = reg.histogram("search.hops", buckets=(1, 2, 4))
    h.observe_many([1, 2, 3, 50])

    snap = json.loads(reg.to_json())
    assert snap["search.queries"]["value"] == 7
    assert snap["search.hops"]["count"] == 4

    text = reg.to_prometheus()
    assert "# TYPE search_queries counter" in text
    assert "search_queries 7" in text
    assert '# TYPE search_hops histogram' in text
    assert 'search_hops_bucket{le="+Inf"} 4' in text
    assert "search_hops_count 4" in text
    # cumulative buckets: le=1 → 1, le=2 → 2, le=4 → 3
    assert 'search_hops_bucket{le="1"} 1' in text
    assert 'search_hops_bucket{le="2"} 2' in text
    assert 'search_hops_bucket{le="4"} 3' in text


def test_histogram_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1, 2, 4, 8, 16))
    h.observe_many([1] * 50 + [3] * 40 + [10] * 10)
    assert h.quantile(0.5) == 1   # 50th value sits in the le=1 bucket
    assert h.quantile(0.6) == 4   # 60th value is a 3 → le=4 bucket
    assert h.quantile(0.99) == 16


# ------------------------------------------------- prometheus text format
def parse_prometheus(text: str) -> dict:
    """Parse exposition text back into {name: value} / {name{le}: value}."""
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val) if val != "+Inf" else np.inf
    return out


def test_prometheus_name_sanitization():
    reg = MetricsRegistry()
    reg.counter("9weird.name-x", "leading digit + punctuation").inc(3)
    reg.gauge("search.hops:rate").set(1.0)
    text = reg.to_prometheus()
    sample = parse_prometheus(text)
    # leading digit prefixed, dots/dashes → underscore, colon preserved
    assert sample["_9weird_name_x"] == 3
    assert "9weird" not in text.replace("_9weird", "")
    assert sample["search_hops:rate"] == 1.0
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert __import__("re").fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name)


def test_prometheus_bucket_sum_count_consistency():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 0.5, 1.0, 5.0))
    rng = np.random.default_rng(0)
    vals = rng.uniform(0, 8, 200)
    h.observe_many(vals)
    sample = parse_prometheus(reg.to_prometheus())
    cum = [sample[f'lat_bucket{{le="{e}"}}'] for e in ("0.1", "0.5", "1", "5")]
    cum.append(sample['lat_bucket{le="+Inf"}'])
    # cumulative and monotone, +Inf bucket equals _count
    assert all(a <= b for a, b in zip(cum, cum[1:]))
    assert cum[-1] == sample["lat_count"] == 200
    assert sample["lat_sum"] == pytest.approx(vals.sum(), rel=1e-9)
    # each cumulative bucket matches a direct count of the raw values
    for edge, c in zip((0.1, 0.5, 1.0, 5.0), cum):
        assert c == (vals <= edge).sum()


def test_prometheus_roundtrip_live_exporter():
    """Scrape a live exporter over HTTP and parse the body back (satellite)."""
    import urllib.request

    reg = MetricsRegistry()
    reg.counter("search.queries", "q").inc(42)
    reg.histogram("search.hops", "h", buckets=(2, 8)).observe_many([1, 4, 99])
    with obs.MetricsExporter(reg, port=0) as exp:
        def fetch(path):
            with urllib.request.urlopen(f"{exp.url}{path}", timeout=5) as r:
                return r.status, r.read().decode(), r.headers
        code, body, headers = fetch("/metrics")
        assert code == 200
        assert headers["Content-Type"].startswith("text/plain")
        sample = parse_prometheus(body)
        assert sample["search_queries"] == 42
        assert sample['search_hops_bucket{le="2"}'] == 1
        assert sample['search_hops_bucket{le="+Inf"}'] == 3
        assert sample["search_hops_count"] == 3
        # scrape body == direct export (no transport mangling)
        assert body == reg.to_prometheus()

        code, body, _ = fetch("/metrics.json")
        assert code == 200
        assert json.loads(body)["search.queries"]["value"] == 42

        code, body, _ = fetch("/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

        # no window attached → /debug/telemetry is a 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            fetch("/debug/telemetry")
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            fetch("/nope")
        assert ei.value.code == 404
    assert not exp.running


# ------------------------------------------------------------------ tracer
def test_span_and_trace_file(tmp_path):
    t = Tracer()
    path = str(tmp_path / "trace.json")
    t.start(path)
    # route the module-level helpers at this private tracer
    import repro.obs.trace as trace_mod

    old = trace_mod._TRACER
    trace_mod._TRACER = t
    try:
        with trace_mod.span("phase.a", n=3):
            with trace_mod.span("phase.b"):
                pass
        with trace_mod.span("decorated"):
            pass
    finally:
        trace_mod._TRACER = old
        t.stop()

    events = obs.read_trace(path)
    names = [e["name"] for e in events]
    assert names == ["phase.b", "phase.a", "decorated"]  # inner closes first
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
    assert events[1]["args"] == {"n": 3}
    summary = t.span_summary()
    assert summary["phase.a"]["count"] == 1


def test_span_disabled_is_noop():
    t = obs.get_tracer()
    assert not t.enabled  # module tracer disabled by default in tests
    before = len(t.events())
    with obs.span("nothing"):
        with obs.span("nested", req=1):
            pass
    t.complete_event("nothing.complete", 0.0, 1.0)
    assert len(t.events()) == before


def test_span_records_under_the_profiler(tmp_path, monkeypatch):
    """``jax.profiler.start_trace`` alone turns spans on: each lands in the
    tracer's buffer, with its id, its parent and the request id it
    inherits, and in the profiler's host plane under its own name."""
    import repro.obs.trace as trace_mod

    t = Tracer()
    monkeypatch.setattr(trace_mod, "_TRACER", t)
    jax.profiler.start_trace(str(tmp_path))
    try:
        t_in = time.perf_counter()
        with obs.span("outer", req=7, queries=3):
            with obs.span("inner"):
                pass
            with obs.span("own", req=8):
                pass
        t.complete_event("queued", t_in - 0.5, t_in, req=7)
    finally:
        jax.profiler.stop_trace()
    assert not t.enabled
    ev = {e["name"]: e for e in t.events()}
    assert set(ev) == {"outer", "inner", "own", "queued"}
    assert len({e["id"] for e in ev.values()}) == 4
    assert ev["outer"]["parent"] is None and ev["outer"]["req"] == 7
    assert ev["outer"]["args"] == {"queries": 3}
    assert ev["inner"]["parent"] == ev["outer"]["id"]
    assert ev["inner"]["req"] == 7            # inherited
    assert ev["own"]["parent"] == ev["outer"]["id"] and ev["own"]["req"] == 8
    assert ev["queued"]["parent"] is None and ev["queued"]["req"] == 7
    # the host's perf_counter clock, from the public origin t0
    start = t.t0 + ev["queued"]["ts"] / 1e6
    assert start == pytest.approx(t_in - 0.5, abs=1e-6)
    assert ev["queued"]["dur"] == pytest.approx(5e5, rel=1e-6)
    o, i = ev["outer"], ev["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]

    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    host = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            host |= {e.name for line in plane.lines for e in line.events}
    # spans reach the profiler; the back-dated complete event cannot
    assert {"outer", "inner", "own"} <= host and "queued" not in host

    # both off again: nothing more is recorded
    with obs.span("after"):
        pass
    assert len(t.events()) == 4


# --------------------------------------------------------- search telemetry
@pytest.fixture(scope="module")
def tiny_graph():
    rng = np.random.default_rng(0)
    db = rng.standard_normal((400, 16)).astype(np.float32)
    nbrs = knn_graph(db, 8)
    q = rng.standard_normal((8, 16)).astype(np.float32)
    entries = np.zeros((8, 1), np.int32)
    return (jnp.asarray(db), jnp.asarray(nbrs), jnp.asarray(q),
            jnp.asarray(entries))


def test_batched_search_instrument_identical_results(tiny_graph):
    db, nbrs, q, e = tiny_graph
    res = batched_search(db, nbrs, q, e, beam_width=16, max_hops=64, k=5)
    res_i, tele = batched_search(
        db, nbrs, q, e, beam_width=16, max_hops=64, k=5, instrument=True
    )
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_i.ids))
    np.testing.assert_array_equal(
        np.asarray(res.dists), np.asarray(res_i.dists)
    )
    np.testing.assert_array_equal(np.asarray(res.hops), np.asarray(tele.hops))
    np.testing.assert_array_equal(
        np.asarray(res.dist_evals), np.asarray(tele.dist_evals)
    )


def test_telemetry_fields_sane(tiny_graph):
    db, nbrs, q, e = tiny_graph
    res, tele = batched_search(
        db, nbrs, q, e, beam_width=16, max_hops=64, k=5, instrument=True
    )
    t = jax.tree.map(np.asarray, tele)
    assert (t.converged_hop <= t.hops).all()
    assert (t.ring_evictions >= 0).all()
    assert (t.entry_dist > 0).all()
    # entry 0 is not the true NN for random queries → proxy > 1
    assert (t.entry_rank_proxy >= 1.0).all()
    assert (t.nav_hops == 0).all()  # raw graph search has no nav stage
    s = obs.summarize(tele)
    assert s["queries"] == 8
    assert s["mean_hops"] > 0


def test_ring_overflow_detected_and_warns(tiny_graph):
    db, nbrs, q, e = tiny_graph
    # ring much smaller than the hop count → guaranteed evictions
    _, tele = batched_search(
        db, nbrs, q, e, beam_width=32, max_hops=128, visited_ring=4,
        k=5, instrument=True,
    )
    assert int(np.asarray(tele.ring_evictions).sum()) > 0
    reg = MetricsRegistry()
    with pytest.warns(RuntimeWarning, match="visited-ring overflow"):
        n = obs.warn_on_ring_overflow(tele, 4, registry=reg)
    assert n > 0
    # satellite (ISSUE 7): overflow is a counter on /metrics, not just stderr
    assert reg.get("search.ring_overflow_queries").value == n
    with pytest.warns(RuntimeWarning):
        obs.warn_on_ring_overflow(tele, 4, registry=reg)
    assert reg.get("search.ring_overflow_queries").value == 2 * n


def test_beam_search_fixed_instrument_identical(tiny_graph):
    db, nbrs, q, e = tiny_graph
    ids, d, hops = beam_search_fixed(
        db, nbrs, q[0], e[0], beam_width=16, num_hops=32
    )
    ids2, d2, hops2, tele = beam_search_fixed(
        db, nbrs, q[0], e[0], beam_width=16, num_hops=32, instrument=True
    )
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d2))
    assert int(hops) == int(hops2)
    assert int(tele.dist_evals) > 0
    assert int(tele.converged_hop) <= 32


def test_record_search_telemetry_into_registry(tiny_graph):
    db, nbrs, q, e = tiny_graph
    _, tele = batched_search(
        db, nbrs, q, e, beam_width=16, max_hops=64, k=5, instrument=True
    )
    reg = MetricsRegistry()
    obs.record_search_telemetry(tele, registry=reg, prefix="t")
    snap = reg.snapshot()
    assert snap["t.queries"]["value"] == 8
    assert snap["t.hops"]["count"] == 8
    assert snap["t.dist_evals"]["count"] == 8
    assert snap["t.entry_rank_proxy"]["count"] == 8


# ------------------------------------------------------- gate-level wiring
def test_gate_search_instrumented_end_to_end():
    from repro.core import GateConfig, GateIndex
    from repro.data.synthetic import make_database, make_queries_in_dist
    from repro.graphs.nsg import build_nsg

    db, _ = make_database("sift10m-like", 600, seed=0)
    nsg = build_nsg(db, R=12, knn_k=12, search_l=16, pool_size=32)
    tq = make_queries_in_dist(db, 64, seed=1)
    idx = GateIndex.from_graph(
        db, nsg.neighbors, nsg.enter_id, tq,
        GateConfig(n_hubs=12, epochs=8, batch_hubs=12, subgraph_max_nodes=32),
    )
    eq = make_queries_in_dist(db, 16, seed=2)

    reg = obs.get_registry()
    reg.reset()
    res_plain = idx.search(eq, k=5, beam_width=16)
    res, tele = idx.search(eq, k=5, beam_width=16, instrument=True)
    np.testing.assert_array_equal(
        np.asarray(res_plain.ids), np.asarray(res.ids)
    )
    assert np.asarray(tele.hops).shape == (16,)
    assert np.asarray(tele.nav_hops).shape == (16,)
    snap = reg.snapshot()
    assert snap["search.queries"]["value"] == 16
    assert snap["search.hops"]["count"] == 16
    reg.reset()


def test_serve_generate_records_metrics():
    from repro.configs import get_reduced
    from repro.models.model import build_model
    from repro.serve.engine import ServeEngine

    cfg = get_reduced("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params)
    reg = obs.get_registry()
    reg.reset()
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, cfg.vocab_size, (2, 8)).astype(np.int32)
    out = eng.generate({"tokens": jnp.asarray(prompts)}, 4)
    assert out.tokens.shape == (2, 4)
    snap = reg.snapshot()
    assert snap["serve.requests"]["value"] == 2
    assert snap["serve.tokens"]["value"] == 8
    assert snap["serve.prefill_seconds"]["count"] == 1
    reg.reset()


def test_train_instrument_step():
    from repro.train.loop import instrument_step

    def fake_step(state, batch):
        return state, {"loss": jnp.asarray(1.5), "grad_norm": jnp.asarray(0.3)}

    reg = obs.get_registry()
    reg.reset()
    step = instrument_step(fake_step)
    state, metrics = step({}, {})
    assert float(metrics["loss"]) == 1.5
    snap = reg.snapshot()
    assert snap["train.steps"]["value"] == 1
    assert snap["train.loss"]["value"] == 1.5
    assert snap["train.step_seconds"]["count"] == 1
    reg.reset()
