"""Serving daemon (ISSUE 7): request queue → instrumented search → latency
histograms → rolling window → /metrics scrape, plus ladder warmup."""
import json
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.graphs.search import search_jit_cache_size
from repro.obs.adaptive import LadderRung
from repro.serve.daemon import SearchRequest, ServeDaemon, _build_tiny_index


@pytest.fixture(scope="module")
def tiny_index():
    return _build_tiny_index(400, "sift10m-like", seed=0)


LADDER = (LadderRung(8, 32), LadderRung(16, 64))


def test_daemon_serves_and_exports_metrics(tiny_index):
    obs.get_registry().reset()
    daemon = ServeDaemon(
        tiny_index, ladder=LADDER, level=0, batch_size=8, k=5,
        metrics_port=0, window_size=4,
    )
    port = daemon.start()
    assert port and daemon.exporter.running
    try:
        rng = np.random.default_rng(0)
        for i in range(3):
            q = tiny_index.db[rng.integers(0, 400, 8)] + 0.01 * rng.standard_normal(
                (8, tiny_index.db.shape[1])
            ).astype(np.float32)
            res, tele = daemon.search(q)
            assert np.asarray(res.ids).shape == (8, 5)
            assert np.asarray(tele.hops).shape == (8,)

        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(f"{base}/healthz", timeout=5) as r:
            assert r.status == 200

        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            text = r.read().decode()
        # acceptance: latency histogram + hop/dist-eval counters on /metrics
        assert "search_latency_seconds_bucket" in text
        assert "search_latency_seconds_count 3" in text
        assert "daemon_queue_wait_seconds_count 3" in text
        assert "search_hops_bucket" in text
        assert "search_dist_evals_bucket" in text
        assert "daemon_requests 3" in text
        assert "daemon_queries 24" in text

        with urllib.request.urlopen(f"{base}/debug/telemetry", timeout=5) as r:
            snap = json.loads(r.read().decode())
        assert snap["batches"] == 3
        assert snap["queries"] == 24
        assert snap["latency_p50"] > 0
        assert snap["mean_hops"] > 0
    finally:
        daemon.stop()
    assert not daemon.exporter.running


def test_daemon_warmup_precompiles_ladder(tiny_index):
    daemon = ServeDaemon(
        tiny_index, ladder=LADDER, level=0, batch_size=4, k=5,
        adaptive=True,
    )
    daemon.start(warmup=True)
    try:
        warmed = search_jit_cache_size()
        q = np.asarray(tiny_index.db[:4])
        for level in range(len(LADDER)):  # serve at every rung
            daemon.controller.level = level
            daemon.search(q)
        assert search_jit_cache_size() == warmed  # no recompile at any rung
    finally:
        daemon.stop()


def test_daemon_error_surfaces_to_submitter(tiny_index):
    daemon = ServeDaemon(tiny_index, ladder=LADDER, level=0, batch_size=4)
    daemon.start(warmup=False)
    try:
        bad = SearchRequest(queries=np.zeros((2,)), k=5)  # wrong rank
        with pytest.raises(Exception):
            daemon.submit(bad).get(timeout=30)
        # worker survives a poisoned request
        res, _ = daemon.search(np.asarray(tiny_index.db[:4]))
        assert np.asarray(res.ids).shape[0] == 4
    finally:
        daemon.stop()


def test_daemon_rag_path_shares_window_and_controller(tiny_index):
    import jax

    from repro.configs import get_reduced
    from repro.models.model import build_model
    from repro.serve.engine import ServeEngine
    from repro.serve.retrieval import RagPipeline

    cfg = get_reduced("gemma-2b")
    model = build_model(cfg)
    eng = ServeEngine(cfg, model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    doc_tokens = rng.integers(2, cfg.vocab_size, (400, 4)).astype(np.int32)
    pipe = RagPipeline(tiny_index, eng, doc_tokens, k=2)
    daemon = ServeDaemon(
        tiny_index, pipeline=pipe, ladder=LADDER, level=0, batch_size=2,
    )
    assert pipe.controller is daemon.controller  # daemon wires the loop
    assert pipe.instrument
    daemon.start(warmup=False)
    try:
        q = np.asarray(tiny_index.db[:2])
        prompts = rng.integers(2, cfg.vocab_size, (2, 6)).astype(np.int32)
        res = daemon.submit(SearchRequest(
            queries=q, k=2, prompt_tokens=prompts, max_new_tokens=3,
        )).get(timeout=120)
        assert res.retrieved_ids.shape == (2, 2)
        assert res.generation.tokens.shape == (2, 3)
        # the pipeline (not the bare-search path) fed the daemon's window
        assert len(daemon.window) == 1
        assert "latency_s" in daemon.window._rows()[0]
    finally:
        daemon.stop()


def test_daemon_fixed_mode_never_moves(tiny_index):
    daemon = ServeDaemon(
        tiny_index, ladder=LADDER, level=1, adaptive=False, batch_size=4,
        window_size=2,
        controller_kw=dict(min_batches=1, patience=1, cooldown=0),
    )
    daemon.start(warmup=False)
    try:
        q = np.asarray(tiny_index.db[:4])
        for _ in range(4):
            daemon.search(q)
        assert daemon.controller.level == 1   # adaptive=False → no stepping
        assert len(daemon.window) > 0         # window still fills for SLOs
    finally:
        daemon.stop()


def test_daemon_request_spans_under_the_profiler(tiny_index, tmp_path,
                                                 monkeypatch):
    """One request while ``jax.profiler`` traces: its queue wait, serve,
    entry selection, dispatch, device wait, telemetry and window spans all
    carry its request id and nest in time inside ``daemon.serve``."""
    import jax

    import repro.obs.trace as trace_mod

    tracer = obs.Tracer()
    monkeypatch.setattr(trace_mod, "_TRACER", tracer)
    obs.get_registry().reset()
    daemon = ServeDaemon(tiny_index, ladder=LADDER, level=0, batch_size=4,
                         adaptive=False)
    daemon.start(warmup=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            daemon.search(np.asarray(tiny_index.db[:4]))
        finally:
            jax.profiler.stop_trace()
    finally:
        daemon.stop()
    ev = {e["name"]: e for e in tracer.events()}
    names = ("daemon.queue_wait", "daemon.serve", "gate.select_entries",
             "gate.search.dispatch", "gate.search.device_wait",
             "gate.search.telemetry", "daemon.window")
    assert set(ev) == set(names)
    assert len({ev[n]["req"] for n in names}) == 1
    assert ev["daemon.serve"]["req"] is not None
    assert ev["daemon.queue_wait"]["dur"] >= 0
    serve = ev["daemon.serve"]
    assert serve["args"] == {"queries": 4}
    for n in names[2:]:
        e = ev[n]
        assert e["parent"] == serve["id"], n
        assert serve["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= serve["ts"] + serve["dur"]
    # in the order the worker runs them
    starts = [ev[n]["ts"] for n in names[1:]]
    assert starts == sorted(starts)
    assert ev["daemon.queue_wait"]["ts"] <= serve["ts"]
    hist = obs.get_registry().get("daemon.queue_wait_seconds")
    assert hist.count == 1
