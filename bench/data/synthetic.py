"""The benchmark's own copy of the program's corpus and query generator.

Copied from the program's ``repro.data.synthetic`` (its ``sift10m-like``
profile) so that the yardstick does not move when the program does:
Gaussian clusters with Zipf-like sizes and per-cluster anisotropic scales,
full rank in every dimension.  It is not SIFT1M: its local intrinsic
dimensionality and relative contrast differ from the file's (``PERF.md``),
so what a cell over it measures is the program on this generator.

A corpus is fixed by its configuration (``generator``, ``n``,
``corpus_seed``, ``normalize``); the traffic that runs over it is drawn from
the run's ``--seed``: a base row perturbed by noise.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Profile:
    dim: int
    n_clusters: int
    cluster_spread: float = 0.25   # intra-cluster standard deviation scale
    anisotropy: float = 4.0        # per-cluster axis scale ratio


PROFILES = {"sift10m-like": Profile(128, 160)}
IN_DIST_NOISE = 0.05   # query noise per axis, in units of the corpus' std


def make_corpus(config: dict) -> np.ndarray:
    """(n, d) float32 rows: the clustered corpus, rows L2-normalised where
    the configuration states ``normalize``."""
    p = PROFILES[config["generator"]]
    rng = np.random.default_rng(config["corpus_seed"])
    centers = rng.standard_normal((p.n_clusters, p.dim)).astype(np.float32)
    w = 1.0 / np.arange(1, p.n_clusters + 1) ** 0.6
    w /= w.sum()
    n = config["n"]
    assign = rng.choice(p.n_clusters, size=n, p=w)
    scales = rng.uniform(1.0, p.anisotropy,
                         size=(p.n_clusters, p.dim)).astype(np.float32)
    scales *= p.cluster_spread / np.sqrt(p.dim)
    noise = rng.standard_normal((n, p.dim)).astype(np.float32)
    x = centers[assign] + noise * scales[assign]
    if config["normalize"]:
        x /= np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return x.astype(np.float32)


class QueryMaker:
    """Queries over one corpus ``db``: a base row plus noise (``kind``
    ``in_dist``, the only kind there is)."""

    def __init__(self, db: np.ndarray, kind: str):
        if kind != "in_dist":
            raise ValueError(f"query kind {kind!r}")
        self.db = db
        self.std = float(db[: min(len(db), 200_000)].std())

    def make(self, rng: np.random.Generator, n_q: int) -> np.ndarray:
        idx = rng.integers(0, self.db.shape[0], n_q)
        base = self.db[idx]
        noise = rng.standard_normal(base.shape).astype(np.float32)
        return (base + noise * (self.std * IN_DIST_NOISE)).astype(np.float32)
