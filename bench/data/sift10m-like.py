"""The ``sift10m-like`` corpus generator and its query kinds.

Copied from the program's ``repro.data.synthetic`` (its ``sift10m-like``
profile) so that the yardstick does not move when the program does:
Gaussian clusters with Zipf-like sizes and per-cluster anisotropic scales,
full rank in every dimension.  It is not SIFT1M: its local intrinsic
dimensionality and relative contrast differ from the file's (``PERF.md``),
so what a cell over it measures is the program on this generator.

A corpus is fixed by its configuration (``n``, ``corpus_seed``,
``normalize``); the traffic that runs over it is drawn from the run's
``--seed``.  One query kind: ``in_dist``, a base row perturbed by noise.
"""
from __future__ import annotations

import numpy as np

DIM = 128
N_CLUSTERS = 160
CLUSTER_SPREAD = 0.25   # intra-cluster standard deviation scale
ANISOTROPY = 4.0        # per-cluster axis scale ratio
IN_DIST_NOISE = 0.05    # query noise per axis, in units of the corpus' std


def make_corpus(config: dict) -> np.ndarray:
    """(n, 128) float32 rows: the clustered corpus, rows L2-normalised where
    the configuration states ``normalize``."""
    rng = np.random.default_rng(config["corpus_seed"])
    centers = rng.standard_normal((N_CLUSTERS, DIM)).astype(np.float32)
    w = 1.0 / np.arange(1, N_CLUSTERS + 1) ** 0.6
    w /= w.sum()
    n = config["n"]
    assign = rng.choice(N_CLUSTERS, size=n, p=w)
    scales = rng.uniform(1.0, ANISOTROPY,
                         size=(N_CLUSTERS, DIM)).astype(np.float32)
    scales *= CLUSTER_SPREAD / np.sqrt(DIM)
    noise = rng.standard_normal((n, DIM)).astype(np.float32)
    x = centers[assign] + noise * scales[assign]
    if config["normalize"]:
        x /= np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return x.astype(np.float32)


class InDist:
    """A base row plus Gaussian noise of ``IN_DIST_NOISE`` of the corpus'
    standard deviation, taken once over its first 200,000 rows."""

    def __init__(self, db: np.ndarray):
        self.db = db
        self.std = float(db[: min(len(db), 200_000)].std())

    def make(self, rng: np.random.Generator, n_q: int) -> np.ndarray:
        idx = rng.integers(0, self.db.shape[0], n_q)
        base = self.db[idx]
        noise = rng.standard_normal(base.shape).astype(np.float32)
        return (base + noise * (self.std * IN_DIST_NOISE)).astype(np.float32)


KINDS = {"in_dist": InDist}


def query_maker(db: np.ndarray, kind: str, config: dict):
    """The maker of ``kind`` queries over ``db``: ``make(rng, n_q)``."""
    if kind not in KINDS:
        raise ValueError(f"query kind {kind!r} is not one of sift10m-like's "
                         f"{sorted(KINDS)}")
    return KINDS[kind](db)
