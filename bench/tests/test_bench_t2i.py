"""The ``t2i1m`` deployment: its generator's output pinned bit for bit, its
query kinds, its configuration's metric held to its build's, a whole run of
its cell at 20,000 rows on the CPU, and the reader of converged hops."""
import hashlib
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

CELL = "t2i1m.bulk1024-text"
PIN = {"name": "pin", "generator": "text2image-like", "n": 5000, "d": 200,
       "corpus_seed": 0, "normalize": False}
# SHA-256 of the generator's output as first written, before any recall was
# read; the corpus and the queries may only change with a new generator.
CORPUS = "d89deaea8df12a0846256c06091c749e441992073f4e40aaac56b27f43df9167"
QUERIES = [
    ("text", [7, 0],
     "0baa17b04964345676de35b9aa119dbaa6d917df44073d919e782fee902de8cb"),
    ("text", [7, 1, 0],
     "77fb834d3fb1e6adbf276c5f8974350e5b4d28f7b77952eb4505ea8e6383908a"),
    ("image", [7, 0],
     "b0b5c0cca23fcded90fdcefd39386f7b49132784de00653501ec773032c57da7"),
    ("image", [0, 1],
     "99244fe1d44ed820d17c0aea25e52613a126e3543831859d0bc64ca2a6c74969"),
]


def sha(a: np.ndarray) -> str:
    return hashlib.sha256(a.tobytes()).hexdigest()


@pytest.fixture(scope="module")
def t2i():
    gen = spec.generator("text2image-like")
    return gen, gen.make_corpus(PIN)


def test_t2i_corpus_is_pinned(t2i):
    _, db = t2i
    assert db.shape == (5000, 200) and db.dtype == np.float32
    assert sha(db) == CORPUS


@pytest.mark.parametrize("kind,seed,digest", QUERIES)
def test_t2i_queries_are_pinned(t2i, kind, seed, digest):
    gen, db = t2i
    q = gen.query_maker(db, kind, PIN).make(np.random.default_rng(seed), 64)
    assert q.shape == (64, 200) and q.dtype == np.float32
    assert sha(q) == digest


def test_t2i_unknown_kind_names_both_kinds(t2i):
    gen, db = t2i
    with pytest.raises(ValueError, match=r"'in_dist'.*\['image', 'text'\]"):
        gen.query_maker(db, "in_dist", PIN)


def test_text_queries_are_off_the_rows_and_in_their_concept():
    """The generator's stated properties, at 20,000 rows: a text query
    lies well off the rows (its nearest row at least 1.5 times the rows'
    own nearest-neighbour distance away), an image query on them; a text
    query's exact top-10 lies in its row's concept, shares at most half
    its rows with the cosine top-10, and holds the concept's long rows."""
    gen = spec.generator("text2image-like")
    p = gen.properties({**PIN, "n": 20_000}, n_q=200)
    assert p["ood_ratio.text"] >= 1.5 and p["ood_ratio.image"] < 1.0
    assert p["concept_share.text"] >= 0.8
    assert 0.05 <= p["norm_cv"] <= 0.15
    assert p["ip_cosine_overlap.text"] <= 0.5
    assert p["ip_norm_pct.text"] >= 0.75


def test_metric_is_the_build_metric():
    """A configuration that tells its build a metric (``gate.metric``)
    serves under the same one; t2i1m is such a configuration."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    told = []
    for c in bench["configs"]:
        config = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        if "metric" in config["gate"]:
            assert config["gate"]["metric"] == config["metric"], c["name"]
            told.append(c["name"])
    assert "t2i1m" in told


@pytest.fixture
def t2i_root(tmp_path):
    """A checkout with the benchmark's files, so that the rehearsal keeps
    its index out of the repository's cache."""
    r = str(tmp_path)
    shutil.copytree(spec.BENCH_DIR, os.path.join(r, "bench"),
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), r)
    return r


def test_t2i_cell_rehearsed_at_20k_is_correct(t2i_root, monkeypatch):
    """The cell, cut to 20,000 rows, through the whole run on the CPU: the
    ip build, the served ip search and the reference agree."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    cell = spec.shrink(spec.load_cell(CELL, t2i_root), 20_000)
    assert cell.config["gate"]["metric"] == "ip"
    # the CPU walks the cell's beam of 256 for 1,024 queries in tens of
    # seconds, so a request could outlast the window's grace under load:
    # the rehearsal checks the path, at 128 queries per request
    cell.traffic = {**cell.traffic, "queries_per_request": 128}
    r = harness.run_cell(cell, 2 ** 31 + 16, 1.0, False,
                         t_start=time.perf_counter(), root=t2i_root,
                         require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["checks"]["dist_gap"]["value"] < 1e-5
    assert r["index"]["state"] == "built"


@pytest.fixture
def tracer(monkeypatch):
    import repro.obs.trace as trace_mod

    t = trace_mod.Tracer()
    monkeypatch.setattr(trace_mod, "_TRACER", t)
    t.start()
    yield t
    t.stop()


def test_converged_hops_reader(tracer):
    read = spec.metric_reader("entry.converged_hops_per_query")
    w0 = tracer.t0 + 10.0

    def ctx():
        return {"window": traffic.Window([], w0, w0 + 1.0), "trace": None}

    assert read(ctx()) is None                              # no spans
    tracer.complete_event("gate.search.telemetry", w0 - 1.0, w0 - 0.9,
                          {"converged_hop_sum": 999, "queries": 1})
    assert read(ctx()) is None                              # before it
    # a program whose span carries no sum (the parent's): nothing either
    tracer.complete_event("gate.search.telemetry", w0 + 0.1, w0 + 0.2)
    assert read(ctx()) is None
    tracer.complete_event("gate.search.telemetry", w0 + 0.2, w0 + 0.3,
                          {"converged_hop_sum": 3000, "queries": 1024})
    tracer.complete_event("gate.search.telemetry", w0 + 0.4, w0 + 0.5,
                          {"converged_hop_sum": 1096, "queries": 1024})
    tracer.complete_event("gate.search.telemetry", w0 + 2.0, w0 + 2.1,
                          {"converged_hop_sum": 5000, "queries": 1})
    assert read(ctx()) == pytest.approx(4096 / 2048)
