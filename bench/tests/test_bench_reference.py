"""The plain reference against NumPy at small N, and its bfloat16 control."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import check  # noqa: E402
import reference  # noqa: E402
from data.synthetic import QueryMaker, make_corpus  # noqa: E402



def corpus_and_queries(corpus_seed, normalize, query_seed, n_q):
    db = make_corpus({"generator": "sift10m-like", "n": 3000,
                      "corpus_seed": corpus_seed, "normalize": normalize})
    q = QueryMaker(db, "in_dist").make(np.random.default_rng(query_seed), n_q)
    return db, q


def numpy_topk(db, q, k, metric):
    db = db.astype(np.float64)
    q = q.astype(np.float64)
    if metric == "cosine":
        db = db / np.linalg.norm(db, axis=1, keepdims=True)
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
        d = 1.0 - q @ db.T
    else:
        d = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


@pytest.mark.parametrize("metric,normalize", [
    ("l2", False),
    ("cosine", True),
])
def test_reference_matches_numpy(metric, normalize):
    db, q = corpus_and_queries(5, normalize, 3, 600)
    ref = reference.Reference(db, metric, chunk=1024)
    ids, d = ref.topk(q, 10)
    want_ids, want_d = numpy_topk(db, q, 10, metric)
    assert np.mean(ids == want_ids) > 0.999
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-5)
    got = ref.distances(q, want_ids)
    np.testing.assert_allclose(got, want_d, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("metric,normalize", [
    ("l2", False),
    ("cosine", True),
])
def test_bfloat16_control_is_caught(metric, normalize):
    """Put in the program's place, the reference in bfloat16 returns
    distances far outside float32 rounding of the exact ones."""
    db, q = corpus_and_queries(6, normalize, 4, 300)
    ref = reference.Reference(db, metric, chunk=1024)
    true_ids, true_d = ref.topk(q, 10)
    exact = check.compare(q, true_ids, true_d, true_ids, true_d,
                          ref.distances(q, true_ids), len(db), 10)
    ids, d = reference.control_topk(ref, q, 10)
    control = check.compare(q, ids, d, true_ids, true_d,
                            ref.distances(q, ids), len(db), 10)
    assert exact["dist_gap"] < 1e-5 and exact["recall_at_10"] == 1.0
    assert control["dist_gap"] > 1e-3
    assert control["recall_at_10"] > 0.5


def test_check_counts_bad_rows():
    ids = np.array([[0, 1, 2], [3, 3, 4], [5, -1, 6], [7, 8, 9]])
    d = np.array([[0, 1, 2], [0, 1, 2], [0, 1, 2], [0, 2, 1]], np.float32)
    assert check.bad_rows(ids, d, n=10, k=3) == 3
    assert check.bad_rows(ids[:, :2], d[:, :2], n=10, k=3) == 4
