"""The ``text2image-like`` corpus generator and its query kinds.

A generated stand-in with the shapes of big-ann-benchmarks' ``text2image-1B``
(Yandex Text-to-Image, the GATE paper's ``Text2Image10M``): 200-d float32
image embeddings, not normalised, ranked by inner product, queried by text
embeddings that a second model maps into the same space.  No file of the
source is read; every number a cell over this corpus reports is the program
on this generator.

Rows.  A row is an image of one concept: the concept's centre plus a
within-concept offset in the concept's own few directions, a little
full-rank residual, and a norm factor of its own,

    x_i = s_i * (c_a + B_a z_i + r_i),

* ``c_a``: one of ``N_CONCEPTS`` unit centres in random directions, with Zipf
  sizes (weight ``1 / rank**ZIPF``), as the program's ``text2image10m-like``
  profile has them (``repro.data.synthetic``);
* ``B_a z_i``: ``B_a`` is a (200, ``LATENT``) orthonormal basis of concept
  ``a``, ``z_i`` Gaussian with per-axis scales drawn once per concept in
  [1, ``ANISOTROPY``], of RMS norm ``CONCEPT_SPREAD``;
* ``r_i``: isotropic Gaussian of RMS norm ``RESIDUAL``;
* ``s_i = exp(NORM_SIGMA * g)``: the per-row norm factor.

Queries.  Two kinds:

* ``text``, the traffic and the training queries: each is drawn for one base
  row, as a caption is drawn for its image, through a fixed text-side map
  (a partial rotation ``T`` and a modality offset ``b``, both fixed by the
  corpus, not by the traffic) plus caption noise ``e``:
  ``q = T x_i + b + e``.  It lies off the rows' distribution, while its
  exact top-10 stays in its row's concept.
* ``image``: a base row plus small noise (in distribution).  Used only for
  the diagnostic readings in ``PERF.md``.

The constants, each with its reason (written before any recall reading):
"""
from __future__ import annotations

import argparse
import json

import numpy as np

DIM = 200             # the source's width
N_CONCEPTS = 128      # the program's text2image10m-like profile
ZIPF = 0.6            # its cluster-size exponent: real concepts are unequal
# Image embeddings vary within a concept along a few directions (pose,
# background, crop); 16 of them gives a local intrinsic dimensionality of
# tens, as real embedding sets have, where full-rank Gaussian clusters read
# far more.
LATENT = 16
ANISOTROPY = 4.0      # per-cluster axis scale ratio, as the program's profiles
# Half the centre's norm: concepts (1.41 apart) stay apart, and a concept's
# rows spread far enough that the rows near one row are a small part of it.
CONCEPT_SPREAD = 0.5
RESIDUAL = 0.1        # no embedding lies exactly in a subspace
# Norm factor: enough spread that the inner-product order differs from the
# cosine order, small enough that the row a query was drawn for and its
# neighbours still weigh as much as the few largest norms of the concept,
# so that queries of one concept do not share one top-10.
NORM_SIGMA = 0.08
# Text side: the partial rotation's strength, the program's OOD generator's
# (``make_queries_ood``); a modality offset half a centre's norm, in a
# random direction, that moves every text query off the image rows; caption
# noise of the same size, full rank, since a caption describes its image in
# part.  Together they put a text query about twice the rows' own
# nearest-neighbour distance from the nearest row.
TEXT_ROTATION = 0.35
MODALITY_OFFSET = 0.5
CAPTION_NOISE = 0.5
IMAGE_NOISE = 0.05    # an image query is its row, barely moved

# Stated properties, which ``properties`` measures on the generator's own
# output (PERF.md compares them with what the literature reports):
#   norm_cv: row norms' std / mean, in [0.05, 0.15];
#   ood_ratio: text queries' median L2 distance to their nearest row over
#     the rows' median distance to their nearest other row, in [1.5, 4];
#     image queries read below 1;
#   concept_share: share of a text query's exact inner-product top-10 in
#     the concept of the row it was drawn for, at least 0.8;
#   ip_cosine_overlap: share of that top-10 also in the cosine top-10, at
#     most 0.5: the inner-product order differs from the cosine order;
#   ip_norm_pct: mean norm percentile, within its concept, of a row of that
#     top-10, at least 0.75: inner-product answers are the long rows of the
#     query's direction, as MIPS answers on real data are (ip-NSW+, Liu et
#     al., AAAI 2020: most queries' MIPS results are items of large norm).
# Measured at 100,000 rows, 1,000 queries: norm_cv 0.094, ood_ratio 2.03
# (text) and 0.135 (image), concept_share 1.0, ip_cosine_overlap 0.14,
# ip_norm_pct 0.955 (the cosine top-10's 0.45).  The cause is the linear
# score, not NORM_SIGMA: with NORM_SIGMA 0 the overlap reads 0.28 and
# ip_norm_pct 0.86, since a concept's rows that reach furthest along the
# query's direction are its longest; and it falls as a concept gains rows
# (0.28 at 20,000 rows, 0.14 at 100,000), since more long rows compete with the query's own
# neighbours.  The constants stay as first written; PERF.md §4.


def _structure(seed: int):
    """Centres, bases and per-axis scales of the concepts."""
    rng = np.random.default_rng([seed, 0])
    centres = rng.standard_normal((N_CONCEPTS, DIM))
    centres /= np.linalg.norm(centres, axis=1, keepdims=True)
    bases = np.stack([np.linalg.qr(rng.standard_normal((DIM, LATENT)))[0]
                      for _ in range(N_CONCEPTS)])
    scales = rng.uniform(1.0, ANISOTROPY, (N_CONCEPTS, LATENT))
    scales *= CONCEPT_SPREAD / np.sqrt((scales ** 2).sum(1, keepdims=True))
    w = 1.0 / np.arange(1, N_CONCEPTS + 1) ** ZIPF
    return centres, bases, scales, w / w.sum()


def concepts(config: dict) -> np.ndarray:
    """(n,) the concept of each row of ``make_corpus(config)``."""
    *_, w = _structure(config["corpus_seed"])
    rng = np.random.default_rng([config["corpus_seed"], 1])
    return rng.choice(N_CONCEPTS, size=config["n"], p=w)


def make_corpus(config: dict) -> np.ndarray:
    """(n, 200) float32 rows; L2-normalised where the configuration states
    ``normalize``."""
    seed, n = config["corpus_seed"], config["n"]
    centres, bases, scales, _ = _structure(seed)
    assign = concepts(config)
    rng = np.random.default_rng([seed, 2])
    x = rng.standard_normal((n, DIM), dtype=np.float32)
    x *= np.float32(RESIDUAL / np.sqrt(DIM))
    z = rng.standard_normal((n, LATENT), dtype=np.float32)
    for a in range(N_CONCEPTS):
        rows = np.flatnonzero(assign == a)
        za = z[rows] * scales[a].astype(np.float32)
        x[rows] += (centres[a] + za @ bases[a].T).astype(np.float32)
    x *= np.exp(NORM_SIGMA * rng.standard_normal(n)).astype(np.float32)[:, None]
    if config["normalize"]:
        x /= np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return x.astype(np.float32)


class Text:
    """``T x_i + b + e`` for a uniformly drawn row ``i``; ``T`` and ``b``
    come from the corpus' seed, ``i`` and ``e`` from the caller's ``rng``."""

    def __init__(self, db: np.ndarray, config: dict):
        self.db = db
        rng = np.random.default_rng([config["corpus_seed"], 3])
        a = rng.standard_normal((DIM, DIM)) / np.sqrt(DIM)
        rot, r = np.linalg.qr(np.eye(DIM) + TEXT_ROTATION * (a - a.T) / 2)
        # QR leaves the signs of R's diagonal free, and LAPACK makes them
        # negative here: unfixed, the "rotation" is nearly -I, a point
        # reflection that sends every query away from its own concept
        rot *= np.sign(np.diag(r))[None, :]
        self.rot_t = rot.T.astype(np.float32)
        b = rng.standard_normal(DIM)
        self.offset = (MODALITY_OFFSET * b / np.linalg.norm(b)).astype(
            np.float32)

    def make_for(self, rng: np.random.Generator, idx: np.ndarray
                 ) -> np.ndarray:
        noise = rng.standard_normal((len(idx), DIM), dtype=np.float32)
        q = self.db[idx] @ self.rot_t + self.offset
        return (q + noise * np.float32(CAPTION_NOISE / np.sqrt(DIM))).astype(
            np.float32)

    def make(self, rng: np.random.Generator, n_q: int) -> np.ndarray:
        return self.make_for(rng, rng.integers(0, len(self.db), n_q))


class Image:
    """A base row plus isotropic noise of RMS norm ``IMAGE_NOISE``."""

    def __init__(self, db: np.ndarray, config: dict):
        self.db = db

    def make_for(self, rng: np.random.Generator, idx: np.ndarray
                 ) -> np.ndarray:
        noise = rng.standard_normal((len(idx), DIM), dtype=np.float32)
        return (self.db[idx] + noise * np.float32(IMAGE_NOISE / np.sqrt(DIM))
                ).astype(np.float32)

    def make(self, rng: np.random.Generator, n_q: int) -> np.ndarray:
        return self.make_for(rng, rng.integers(0, len(self.db), n_q))


KINDS = {"text": Text, "image": Image}


def query_maker(db: np.ndarray, kind: str, config: dict):
    """The maker of ``kind`` queries over ``db``: ``make(rng, n_q)``."""
    if kind not in KINDS:
        raise ValueError(f"query kind {kind!r} is not one of "
                         f"text2image-like's {sorted(KINDS)}")
    return KINDS[kind](db, config)


def _nearest_l2(a: np.ndarray, db: np.ndarray, skip=None) -> np.ndarray:
    """Each row of ``a``'s L2 distance to its nearest row of ``db`` (leaving
    out row ``skip[j]`` for ``a[j]``), in float64."""
    dbn = np.einsum("ij,ij->i", db, db, dtype=np.float64)
    out = np.empty(len(a))
    for s in range(0, len(a), 256):
        qa = a[s:s + 256].astype(np.float64)
        d2 = (np.einsum("ij,ij->i", qa, qa)[:, None] - 2 * qa @ db.T.astype(
            np.float64) + dbn[None, :])
        if skip is not None:
            d2[np.arange(len(qa)), skip[s:s + 256]] = np.inf
        out[s:s + 256] = np.sqrt(np.maximum(d2.min(1), 0.0))
    return out


def _top10(q: np.ndarray, db: np.ndarray, cosine: bool) -> np.ndarray:
    rows = db.astype(np.float64)
    if cosine:
        rows /= np.linalg.norm(rows, axis=1, keepdims=True)
    out = []
    for s in range(0, len(q), 256):
        scores = q[s:s + 256].astype(np.float64) @ rows.T
        top = np.argpartition(-scores, 10, axis=1)[:, :10]
        out.append(top)
    return np.concatenate(out)


def properties(config: dict, n_q: int = 1000, seed: int = 0) -> dict:
    """The stated properties, measured on ``n_q`` queries of each kind over
    ``make_corpus(config)``, with float64 brute force."""
    db = make_corpus(config)
    assign = concepts(config)
    rng = np.random.default_rng([seed, 5])
    norms = np.linalg.norm(db.astype(np.float64), axis=1)
    rows = rng.integers(0, len(db), n_q)
    # each row's norm percentile within its concept
    pct = np.empty(len(db))
    for a in range(N_CONCEPTS):
        r = np.flatnonzero(assign == a)
        pct[r] = np.argsort(np.argsort(norms[r])) / max(len(r) - 1, 1)
    base_nn = np.median(_nearest_l2(db[rows], db, skip=rows))
    out = {"n": len(db), "norm_cv": float(norms.std() / norms.mean()),
           "base_nn_l2": float(base_nn)}
    for kind in KINDS:
        q = KINDS[kind](db, config).make_for(rng, rows)
        out[f"ood_ratio.{kind}"] = float(np.median(_nearest_l2(q, db))
                                         / base_nn)
        top = _top10(q, db, cosine=False)
        out[f"concept_share.{kind}"] = float(
            np.mean(assign[top] == assign[rows][:, None]))
        cos = _top10(q, db, cosine=True)
        out[f"ip_cosine_overlap.{kind}"] = float(np.mean(
            [len(np.intersect1d(a, b)) / 10 for a, b in zip(top, cos)]))
        out[f"ip_norm_pct.{kind}"] = float(pct[top].mean())
        # spread of the top-10: its mean pairwise distance over the rows'
        # nearest-neighbour distance (OOD queries' neighbours lie apart)
        t = db[top].astype(np.float64)
        pair = np.sqrt(np.maximum(((t[:, :, None, :] - t[:, None, :, :]) ** 2
                                   ).sum(-1), 0.0))
        out[f"top10_spread.{kind}"] = float(pair.sum((1, 2)).mean()
                                            / 90 / base_nn)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="measure the generator's stated properties")
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--corpus-seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=1000)
    args = ap.parse_args()
    print(json.dumps(properties({"n": args.n, "corpus_seed": args.corpus_seed,
                                 "normalize": False}, args.queries)))
