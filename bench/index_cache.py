"""Built indexes, kept in the checkout so that only a cell's first run builds.

An entry is ``GateIndex.save``'s file at ``bench/.cache/<config>-<key>.pkl``.
The key hashes the configuration's build fields, the configuration's own
corpus generator (``bench/data/<generator>.py``) with the loader that finds
it, and every ``.py`` file under ``src/repro/``, so any change to the program
or to the corpus builds again.  Every run serves an index loaded
from the cache, the run that built it too, so that all runs serve the same
kind of object.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import sys
import time

import spec
from spec import ROOT

BUILD_FIELDS = ("generator", "n", "d", "metric", "normalize", "corpus_seed",
                "nsg", "gate", "train_queries", "train_query_kind")
BUILD_STAGES = ("nsg.knn", "nsg.search_prune", "nsg.reverse_edges",
                "nsg.repair", "gate.build.hubs", "gate.build.subgraphs",
                "gate.build.topo_embed", "gate.build.samples",
                "gate.build.train_towers", "gate.build.nav_graph")


def source_files(root: str = ROOT) -> list:
    return sorted(glob.glob(os.path.join(root, "src", "repro", "**", "*.py"),
                            recursive=True))


def cache_key(config: dict, root: str = ROOT) -> str:
    h = hashlib.sha256()
    build = {f: config.get(f) for f in BUILD_FIELDS}
    h.update(json.dumps(build, sort_keys=True).encode())
    files = [("bench/spec.py", spec.__file__),
             ("generator", spec.generator_path(config["generator"], root))]
    files += [(os.path.relpath(p, root), p) for p in source_files(root)]
    for name, path in files:
        h.update(name.encode())
        with open(path, "rb") as f:
            h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()[:16]


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def make_corpus(config: dict, root: str = ROOT):
    """``(generator, db)``: the configuration's generator and its corpus,
    refused where the corpus' width is not the stated ``d``."""
    gen = spec.generator(config["generator"], root)
    db = gen.make_corpus(config)
    if db.ndim != 2 or db.shape[1] != config["d"]:
        raise ValueError(
            f"generator {config['generator']!r} made a corpus of shape "
            f"{db.shape}; configuration {config['name']!r} states d="
            f"{config['d']}")
    return gen, db


def build_index(config: dict, root: str = ROOT):
    """Corpus and training queries from the configuration, then
    ``GateIndex.build`` with its NSG and GATE settings."""
    import numpy as np
    from repro import obs
    from repro.core import GateConfig, GateIndex

    t0 = time.perf_counter()
    gen, db = make_corpus(config, root)
    tq = gen.query_maker(db, config["train_query_kind"], config).make(
        np.random.default_rng([config["corpus_seed"], 1]),
        config["train_queries"])
    log(f"corpus n={len(db)} d={db.shape[1]} ({time.perf_counter() - t0:.2f}"
        f" s)")
    tracer = obs.get_tracer()
    tracer.start()
    try:
        index = GateIndex.build(
            db, tq, GateConfig(**config["gate"], seed=config["corpus_seed"]),
            **config["nsg"])
        spans = tracer.span_summary()
    finally:
        tracer.stop()
    for name in BUILD_STAGES:
        log(f"build stage {name}: "
            f"{spans.get(name, {}).get('total_s', 0.0):.2f} s")
    return index


def load_or_build(config: dict, root: str = ROOT):
    """``(index, build_s)``: the cached index of ``config``; when the cache
    has none it is built and saved first, and ``build_s`` is the seconds that
    took (``None`` on a hit)."""
    from repro.core import GateIndex

    cache_dir = os.path.join(root, "bench", ".cache")
    path = os.path.join(cache_dir, f"{config['name']}-{cache_key(config, root)}"
                        ".pkl")
    build_s = None
    if not os.path.exists(path):
        log(f"index cache miss: building {config['name']}")
        t0 = time.perf_counter()
        index = build_index(config, root)
        os.makedirs(cache_dir, exist_ok=True)
        for old in glob.glob(os.path.join(cache_dir,
                                          f"{config['name']}-*.pkl")):
            os.remove(old)
        tmp = path + ".part"
        index.save(tmp)
        os.replace(tmp, path)
        del index
        build_s = time.perf_counter() - t0
        log(f"build and save {build_s:.2f} s")
    t0 = time.perf_counter()
    index = GateIndex.load(path)
    how = "built and saved, then loaded" if build_s else "from the cache"
    log(f"index {how}: {os.path.relpath(path, root)} "
        f"({time.perf_counter() - t0:.2f} s)")
    return index, build_s
