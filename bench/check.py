"""The comparison that decides ``correct``.

Every answer served in the window is compared with the plain reference
(``reference.py``).  Four numbers, each beside its limit:

* ``unanswered``: requests due in the window that never got an answer, or
  got an error.  Limit 0.
* ``bad_rows``: answered queries whose k ids are not k distinct rows of the
  corpus, or whose distances are not in ascending order.  Limit 0.
* ``dist_gap``: the widest gap between a distance the server returned and
  the reference's float32 distance of the same row to the same query, over
  every slot served, in a unit of that query's own.  Under ``l2`` and
  ``cosine`` the unit is the query's exact k-th nearest distance.  Under
  ``ip`` the distance is ``-q.x``, which is mostly negative and crosses 0,
  so the unit is ``|q| * |x_k|``, with ``x_k`` the reference's exact k-th
  row: that bounds ``|q.x|`` near the cut-off, and float32 rounding of a
  dot product scales with it.  Its limit is set from the readings in
  ``PERF.md`` and kept in the configuration's file (``dist_gap_max``).
* ``recall_at_10``: the share of the exact 10 nearest rows that the answers
  hold, over every query served; at least the configuration's stated
  ``recall_at_10_min``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def served(records) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(queries, ids, dists) of every answered request, stacked."""
    done = [r for r in records if r.answered]
    if not done:
        return (np.zeros((0, 0), np.float32), np.zeros((0, 0), np.int64),
                np.zeros((0, 0), np.float32))
    return (np.concatenate([r.queries for r in done]),
            np.concatenate([r.ids for r in done]).astype(np.int64),
            np.concatenate([r.dists for r in done]))


def bad_rows(ids: np.ndarray, dists: np.ndarray, n: int, k: int) -> int:
    if ids.shape[1] != k:
        return len(ids)
    out_of_range = np.any((ids < 0) | (ids >= n), axis=1)
    srt = np.sort(ids, axis=1)
    repeated = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    unordered = np.any(dists[:, 1:] < dists[:, :-1], axis=1)
    return int(np.sum(out_of_range | repeated | unordered))


def gap_unit(metric: str, queries: np.ndarray, kth_dists: np.ndarray,
             kth_norms: np.ndarray) -> np.ndarray:
    """Each query's unit of ``dist_gap``: its exact k-th distance
    (``kth_dists``), or under ``ip`` its norm times the norm of its exact
    k-th row (``kth_norms``)."""
    if metric in ("l2", "cosine"):
        unit = kth_dists
    elif metric == "ip":
        unit = np.linalg.norm(queries, axis=1) * kth_norms
    else:
        raise ValueError(f"metric {metric!r} has no unit of dist_gap")
    return np.maximum(unit, 1e-12)


def compare(queries: np.ndarray, ids: np.ndarray, dists: np.ndarray,
            true_ids: np.ndarray, true_dists: np.ndarray,
            ref_dists_of_ids: np.ndarray, n: int, k: int, metric: str,
            kth_norms: np.ndarray) -> Dict[str, float]:
    """The numbers compared, from the served answers and the reference's;
    ``kth_norms`` are the norms of each query's exact k-th row."""
    if len(ids) == 0:
        return {"bad_rows": 0, "dist_gap": float("inf"), "recall_at_10": 0.0}
    scale = gap_unit(metric, queries, true_dists[:, k - 1], kth_norms)
    valid = (ids >= 0) & (ids < n)
    gap = np.where(valid, np.abs(dists - ref_dists_of_ids), 0.0)
    dist_gap = float(np.max(gap / scale[:, None]))
    hits = [len(np.intersect1d(a[a >= 0], b)) for a, b in zip(ids, true_ids)]
    return {
        "bad_rows": bad_rows(ids, dists, n, k),
        "dist_gap": dist_gap,
        "recall_at_10": float(np.sum(hits) / (k * len(ids))),
    }


def limits(config: dict) -> Dict[str, Tuple[str, float]]:
    """Each number's limit as (sense, value): ``max`` or ``min``."""
    return {
        "unanswered": ("max", 0),
        "bad_rows": ("max", 0),
        "dist_gap": ("max", float(config["dist_gap_max"])),
        "recall_at_10": ("min", float(config["recall_at_10_min"])),
    }


def judge(numbers: Dict[str, float], config: dict) -> Tuple[bool, List[dict]]:
    """(correct, [{"name", "value", "limit", "sense", "ok"}, ...])."""
    rows = []
    for name, (sense, lim) in limits(config).items():
        v = numbers[name]
        ok = v <= lim if sense == "max" else v >= lim
        rows.append({"name": name, "value": v, "limit": lim, "sense": sense,
                     "ok": bool(ok)})
    return all(r["ok"] for r in rows), rows
