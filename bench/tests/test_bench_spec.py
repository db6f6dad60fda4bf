"""Discovery by name: a configuration, a traffic mix and a per-layer metric
dropped in as files, with no code edit (generators and their query kinds:
``test_bench_data.py``); the peak table; the cache key, which follows the
configuration's own generator."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import index_cache  # noqa: E402
import spec  # noqa: E402


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


@pytest.fixture
def root(tmp_path):
    r = str(tmp_path)
    write(os.path.join(r, "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "new_cfg", "file": "bench/configs/new_cfg.json"}],
        "workloads": [{"name": "new_cfg.mix", "config": "new_cfg",
                       "traffic": "new_mix", "chips": 1}],
        "end_to_end": [
            {"name": "qps", "unit": "queries/s", "workloads": ["other"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "new.metric", "unit": "ms"},
                      {"name": "silent.metric", "unit": "%"}],
    }))
    write(os.path.join(r, "bench", "configs", "new_cfg.json"),
          json.dumps({"name": "new_cfg", "n": 10, "d": 4}))
    write(os.path.join(r, "bench", "traffic", "new_mix.json"),
          json.dumps({"loop": "open", "rate_rps": 3.0}))
    write(os.path.join(r, "bench", "metrics", "new.metric.py"),
          "def read(ctx):\n    return 2 * ctx['x']\n")
    write(os.path.join(r, "bench", "metrics", "silent.metric.py"),
          "def read(ctx):\n    return None\n")
    shutil.copy(os.path.join(spec.BENCH_DIR, "peaks.json"),
                os.path.join(r, "bench", "peaks.json"))
    return r


def test_files_dropped_in_are_found_by_name(root):
    cell = spec.load_cell("new_cfg.mix", root)
    assert cell.config == {"name": "new_cfg", "n": 10, "d": 4}
    assert cell.traffic["rate_rps"] == 3.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    got = spec.read_metrics(cell.per_layer, {"x": 1.5}, root)
    # a reader that finds nothing leaves its metric out
    assert got == {"new.metric": {"value": 3.0, "unit": "ms"}}
    with pytest.raises(KeyError):
        spec.load_cell("missing.cell", root)


def test_peaks_are_keyed_by_device_kind(root):
    assert spec.load_peaks("TPU v5 lite", root)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.load_peaks("TPU v9 imaginary", root)


def test_committed_cells_name_existing_files():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_cache_key_follows_the_program_and_the_build(tmp_path):
    r = str(tmp_path)
    src = os.path.join(r, "src", "repro", "graphs", "search.py")
    write(src, "x = 1\n")
    gen = os.path.join(r, "bench", "data", "sift10m-like.py")
    write(gen, "K = 1\n")
    write(os.path.join(r, "bench", "data", "other-gen.py"), "K = 1\n")
    config = {"name": "c", "n": 100, "generator": "sift10m-like",
              "recall_at_10_min": 0.5}
    key = index_cache.cache_key(config, r)
    assert index_cache.cache_key(config, r) == key
    # a limit of the check is not part of the build
    assert index_cache.cache_key({**config, "recall_at_10_min": 0.4}, r) == key
    assert index_cache.cache_key({**config, "n": 101}, r) != key
    # the configuration's own generator, by name and by its file
    assert index_cache.cache_key({**config, "generator": "other-gen"},
                                 r) != key
    write(gen, "K = 2\n")
    assert index_cache.cache_key(config, r) != key
    write(gen, "K = 1\n")
    assert index_cache.cache_key(config, r) == key
    write(src, "x = 2\n")
    changed = index_cache.cache_key(config, r)
    assert changed != key
    write(os.path.join(r, "src", "repro", "new_module.py"), "")
    assert index_cache.cache_key(config, r) != changed
