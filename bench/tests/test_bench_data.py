"""Corpus generators and their query kinds, found by name: the
``sift10m-like`` output pinned bit for bit, a generator and a query kind
dropped in as files reaching a whole run, and the corpus' width held to the
configuration's ``d``."""
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
import index_cache  # noqa: E402
import spec  # noqa: E402

# SHA-256 of the generator's output, taken before it moved into a file of
# its own; the benchmark's corpus and queries may not change with the move.
SIFT_CORPUS = ("efb10d2ddfa8a96cedafc4b07c5c5e82"
               "208d079c58bde0079b3bd59179eaf055")
SIFT_QUERIES = [
    ([7, 0],
     "930742b7a7c969888f3d4529294710230452436808fb7093487c4d0812b6bd53"),
    ([7, 1, 0],
     "f0e415175bb195a142fd2db8db2deb415627d75c0e1a868ce4cf5b4c81cb95c0"),
    ([0, 1],
     "f5a60816320d72c4b3a35a2856032e4718990c6ad1dc735716e3a696bd237893"),
]
SIFT = {"name": "pin", "generator": "sift10m-like", "n": 5000, "d": 128,
        "corpus_seed": 0, "normalize": False}

# A generator of 48-d rows with two query kinds, written as a later change
# would add one: a file and nothing else.
TOY_GENERATOR = '''
import numpy as np


def make_corpus(config):
    rng = np.random.default_rng(config["corpus_seed"])
    n, d = config["n"], config["d"]
    centers = rng.standard_normal((12, d)).astype(np.float32)
    noise = rng.standard_normal((n, d)).astype(np.float32)
    return (centers[rng.integers(0, 12, n)] + 0.3 * noise).astype(np.float32)


class Near:
    """A base row plus a little noise."""

    def __init__(self, db):
        self.db = db

    def make(self, rng, n_q):
        base = self.db[rng.integers(0, len(self.db), n_q)]
        return (base + 0.02 * rng.standard_normal(base.shape)).astype(
            np.float32)


class Between:
    """The midpoint of two base rows: off the corpus' own distribution."""

    def __init__(self, db):
        self.db = db

    def make(self, rng, n_q):
        a = self.db[rng.integers(0, len(self.db), n_q)]
        b = self.db[rng.integers(0, len(self.db), n_q)]
        return (0.5 * (a + b)).astype(np.float32)


KINDS = {"near": Near, "between": Between}


def query_maker(db, kind, config):
    if kind not in KINDS:
        raise ValueError(f"query kind {kind!r} is not one of {sorted(KINDS)}")
    return KINDS[kind](db)
'''
TOY_CONFIG = {
    "name": "toy", "generator": "toy-gen", "n": 1500, "d": 48,
    "metric": "l2", "normalize": False, "k": 10, "corpus_seed": 4,
    "nsg": {"R": 12, "knn_k": 12, "search_l": 16, "pool_size": 32},
    "gate": {"n_hubs": 8, "epochs": 4, "batch_hubs": 8,
             "subgraph_max_nodes": 32},
    "train_queries": 64, "train_query_kind": "between",
    "rung": {"beam_width": 64, "max_hops": 256}, "kernel": "xla",
    "recall_at_10_min": 0.3, "dist_gap_max": 1e-3,
}
TOY_MIX = {"loop": "closed", "clients": 2, "queries_per_request": 8,
           "query_kind": "between"}


def sha(a: np.ndarray) -> str:
    return hashlib.sha256(a.tobytes()).hexdigest()


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


@pytest.fixture(scope="module")
def sift():
    gen = spec.generator("sift10m-like")
    return gen, gen.make_corpus(SIFT)


def test_sift_corpus_is_pinned(sift):
    _, db = sift
    assert db.shape == (5000, 128) and db.dtype == np.float32
    assert sha(db) == SIFT_CORPUS


@pytest.mark.parametrize("seed,digest", SIFT_QUERIES)
def test_sift_in_dist_queries_are_pinned(sift, seed, digest):
    gen, db = sift
    q = gen.query_maker(db, "in_dist", SIFT).make(
        np.random.default_rng(seed), 64)
    assert q.shape == (64, 128) and q.dtype == np.float32
    assert sha(q) == digest


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A checkout that holds the benchmark's files and, dropped in, a toy
    generator, a configuration and a mix that use its second query kind."""
    r = str(tmp_path_factory.mktemp("toy_root"))
    shutil.copytree(spec.BENCH_DIR, os.path.join(r, "bench"),
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    write(os.path.join(r, "bench", "data", "toy-gen.py"), TOY_GENERATOR)
    write(os.path.join(r, "bench", "configs", "toy.json"),
          json.dumps(TOY_CONFIG))
    write(os.path.join(r, "bench", "traffic", "between8.json"),
          json.dumps(TOY_MIX))
    write(os.path.join(r, "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.between8", "config": "toy",
                       "traffic": "between8", "chips": 1}],
        "end_to_end": [{"name": "qps", "unit": "queries/s"},
                       {"name": "recall_at_10", "unit": "fraction"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }))
    return r


def test_dropped_in_generator_and_kind_run_whole(toy_root, monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    cell = spec.load_cell("toy.between8", toy_root)
    r = harness.run_cell(cell, 2 ** 31 + 7, 1.5, False,
                         t_start=time.perf_counter(), root=toy_root,
                         require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["checks"]["dist_gap"]["value"] < 1e-5
    # the index was built from the toy generator's file, at its width
    key = index_cache.cache_key(cell.config, toy_root)
    assert os.path.exists(os.path.join(toy_root, "bench", ".cache",
                                       f"toy-{key}.pkl"))


def test_unknown_generator_names_those_there_are(toy_root):
    with pytest.raises(KeyError,
                       match=r"'no-such-gen'.*sift10m-like.*toy-gen"):
        spec.generator("no-such-gen", toy_root)


@pytest.mark.parametrize("name,kinds", [
    ("toy-gen", r"\['between', 'near'\]"),
    ("sift10m-like", r"\['in_dist'\]"),
])
def test_unknown_query_kind_names_those_there_are(toy_root, name, kinds):
    gen = spec.generator(name, toy_root)
    db = gen.make_corpus({**TOY_CONFIG, "n": 50, "normalize": False})
    with pytest.raises(ValueError, match=r"'ood'.*" + kinds):
        gen.query_maker(db, "ood", TOY_CONFIG)


def test_corpus_of_another_width_is_refused(toy_root):
    config = {**SIFT, "n": 200, "d": 64}
    with pytest.raises(ValueError, match=r"\(200, 128\).*states d=64"):
        index_cache.load_or_build(config, toy_root)
