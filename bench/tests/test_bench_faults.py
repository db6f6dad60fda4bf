"""A whole run of a tiny cell on the CPU, past the look for a chip: sound, it
comes out correct; with the timed path broken underneath, or with the
bfloat16 reference in the program's place (the control), it does not."""
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
import reference  # noqa: E402
import spec  # noqa: E402

CONFIG = {
    "name": "tiny", "generator": "sift10m-like", "n": 1500, "d": 128,
    "metric": "l2", "normalize": False, "k": 10, "corpus_seed": 3,
    "nsg": {"R": 12, "knn_k": 12, "search_l": 16, "pool_size": 32},
    "gate": {"n_hubs": 8, "epochs": 4, "batch_hubs": 8,
             "subgraph_max_nodes": 32},
    "train_queries": 64, "train_query_kind": "in_dist",
    "rung": {"beam_width": 64, "max_hops": 256}, "kernel": "xla",
    "recall_at_10_min": 0.3, "dist_gap_max": 1e-3,
}
TRAFFIC = {"loop": "closed", "clients": 2, "queries_per_request": 8,
           "query_kind": "in_dist"}
E2E = [{"name": "qps", "unit": "queries/s"},
       {"name": "recall_at_10", "unit": "fraction"},
       {"name": "setup_s", "unit": "s"}]


def cell():
    return spec.Cell("tiny.bulk8", dict(CONFIG), dict(TRAFFIC), 1, E2E, [])


def _wrap(index, change):
    """``index.search`` whose answers pass through ``change(queries, ids,
    dists) -> (ids, dists)``."""
    orig = index.search

    def search(queries, *a, **kw):
        res, tele = orig(queries, *a, **kw)
        ids, dists = change(np.asarray(queries), np.array(res.ids),
                            np.array(res.dists))
        return res._replace(ids=ids, dists=dists), tele
    index.search = search


def state_unchanged(index):
    """The search returns the state it started from: the entry alone."""
    def change(q, ids, dists):
        entries = np.asarray(index.select_entries(q))[:, 0]
        out = np.full_like(ids, -1)
        out[:, 0] = entries
        d = np.full_like(dists, np.inf)
        d[:, 0] = ((index.db[entries] - q) ** 2).sum(1)
        return out, d
    _wrap(index, change)


def half_batch(index):
    """Half of each batch left out; its answers copied from the rest."""
    def change(q, ids, dists):
        h = len(ids) // 2
        ids[h:], dists[h:] = ids[:len(ids) - h], dists[:len(ids) - h]
        return ids, dists
    _wrap(index, change)


def answer_altered(index):
    """One id of each answer changed where it is produced."""
    def change(q, ids, dists):
        ids[0, 0] = (ids[0, 0] + 1) % len(index.db)
        return ids, dists
    _wrap(index, change)


def control(index):
    """The reference in bfloat16 put in the program's place."""
    ref = reference.Reference(index.db, "l2")

    def change(q, ids, dists):
        i, d = reference.control_topk(ref, q, ids.shape[1])
        return i.astype(ids.dtype), d
    _wrap(index, change)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the benchmark's generators, for the index cache."""
    r = str(tmp_path_factory.mktemp("bench_root"))
    shutil.copytree(os.path.join(spec.BENCH_DIR, "data"),
                    os.path.join(r, "bench", "data"))
    return r


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


def run(root, fault=None, seed=2 ** 31 + 11):
    return harness.run_cell(cell(), seed, 1.5, False,
                            t_start=time.perf_counter(), root=root,
                            require_tpu=False, fault=fault)


def test_sound_run_is_correct(root):
    r = run(root)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert set(r["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert r["metrics"]["qps"]["value"] > 0
    assert (r["metrics"]["recall_at_10"]["value"]
            == r["checks"]["recall_at_10"]["value"])
    # the module-scoped root builds the index once, then serves it cached
    assert r["index"]["state"] in ("built", "cached")
    assert r["checks"]["dist_gap"]["value"] < 1e-5
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert [k for k in r if not k.startswith("_")][-1] == "checks"
    assert r["_compiles_in_window"] == 0


@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, "bad_rows"),
    (half_batch, "dist_gap"),
    (answer_altered, "dist_gap"),
    (control, "dist_gap"),
])
def test_broken_path_is_not_correct(root, fault, caught_by):
    r = run(root, fault)
    assert not r["correct"]
    failed = {x["name"] for x in r["_rows"] if not x["ok"]}
    assert caught_by in failed, r["checks"]


def test_no_chip_no_result(root):
    with pytest.raises(SystemExit, match="TPU"):
        harness.run_cell(cell(), 1, 1.0, False, t_start=time.perf_counter(),
                         root=root, require_tpu=True)
