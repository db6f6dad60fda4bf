"""Everything the harness knows about a cell, found by name.

``BENCHMARK.json`` at the root names the cells; a cell names a configuration
(``bench/configs/<config>.json``, through the entry in ``configs``) and a
traffic mix (``bench/traffic/<traffic>.json``); a configuration names its
corpus generator (``bench/data/<generator>.py``), which holds the query kinds
that a mix or the training queries name; a per-layer metric is the reader
``bench/metrics/<metric>.py``.  Adding any of them means adding files, never
editing code.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]     # metric entries that this cell reports
    per_layer: List[dict]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration,
    traffic and metric entries."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(
        os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    layer = [m for m in bench["per_layer"] if applies(m, name)]
    return Cell(name, config, traffic, int(w["chips"]), e2e, layer)


def shrink(cell: Cell, n: int) -> Cell:
    """The cell at a corpus of ``n`` rows (a rehearsal), under its own
    cache name."""
    cell.config = {**cell.config, "n": n,
                   "name": f"{cell.config['name']}-n{n}"}
    return cell


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """Peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def _load_module(path: str, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(name: str, root: str = ROOT):
    """The corpus generator ``bench/data/<name>.py``: its
    ``make_corpus(config)`` gives the (n, d) float32 rows, and
    ``query_maker(db, kind, config).make(rng, n_q)`` draws ``n_q`` queries
    of one of its kinds (an unknown kind raises ``ValueError``)."""
    path = generator_path(name, root)
    if not os.path.exists(path):
        have = sorted(os.path.basename(f)[:-3] for f in glob.glob(
            os.path.join(os.path.dirname(path), "*.py")))
        raise KeyError(f"no generator {name!r} in bench/data (have {have})")
    return _load_module(path, "bench_data_", name)


def generator_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "data", name + ".py")


def metric_reader(name: str, root: str = ROOT
                  ) -> Callable[[dict], Optional[float]]:
    """``read(ctx)`` from ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return _load_module(path, "bench_metric_", name).read


def read_metrics(entries: List[dict], ctx: dict, root: str = ROOT
                 ) -> Dict[str, dict]:
    """Each per-layer metric whose reader finds something to read, as
    ``{name: {"value", "unit"}}``; a reader that finds nothing returns
    ``None`` and its metric is left out."""
    out = {}
    for m in entries:
        v = metric_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
