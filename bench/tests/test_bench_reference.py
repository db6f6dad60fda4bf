"""The plain reference against NumPy at small N under each metric, and its
bfloat16 control."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import check  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402

METRICS = [("l2", False), ("cosine", True), ("ip", False)]


def corpus_and_queries(corpus_seed, normalize, query_seed, n_q):
    gen = spec.generator("sift10m-like")
    config = {"n": 3000, "corpus_seed": corpus_seed, "normalize": normalize}
    db = gen.make_corpus(config)
    q = gen.query_maker(db, "in_dist", config).make(
        np.random.default_rng(query_seed), n_q)
    return db, q


def numpy_distances(db, q, metric):
    """(Q, N) float64 distances, ascending is nearer."""
    db = db.astype(np.float64)
    q = q.astype(np.float64)
    if metric == "cosine":
        db = db / np.linalg.norm(db, axis=1, keepdims=True)
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
        return 1.0 - q @ db.T
    if metric == "ip":
        return -(q @ db.T)
    return ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)


def numpy_topk(db, q, k, metric):
    d = numpy_distances(db, q, metric)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


@pytest.mark.parametrize("metric,normalize", METRICS)
def test_reference_matches_numpy(metric, normalize):
    db, q = corpus_and_queries(5, normalize, 3, 600)
    ref = reference.Reference(db, metric, chunk=1024)
    ids, d = ref.topk(q, 10)
    want_ids, want_d = numpy_topk(db, q, 10, metric)
    assert np.mean(ids == want_ids) > 0.999
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-5)
    got = ref.distances(q, want_ids)
    np.testing.assert_allclose(got, want_d, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("metric,normalize,limit", [
    ("l2", False, 1e-3),
    ("cosine", True, 1e-2),
    ("ip", False, 1e-3),
])
def test_bfloat16_control_is_caught(metric, normalize, limit):
    """Put in the program's place, the reference in bfloat16 returns
    distances far outside float32 rounding of the exact ones: it fails a
    ``dist_gap`` limit that the float32 reference, against float64 NumPy,
    passes by 100x or more."""
    db, q = corpus_and_queries(6, normalize, 4, 300)
    ref = reference.Reference(db, metric, chunk=1024)
    true_ids, true_d = ref.topk(q, 10)
    kth_norms = ref.row_norms(true_ids[:, 9])
    # the float32 reference's own answers against float64 NumPy
    want_d = np.take_along_axis(numpy_distances(db, q, metric), true_ids,
                                axis=1)
    exact = check.compare(q, true_ids, true_d, true_ids, true_d, want_d,
                          len(db), 10, metric, kth_norms)
    ids, d = reference.control_topk(ref, q, 10)
    control = check.compare(q, ids, d, true_ids, true_d,
                            ref.distances(q, ids), len(db), 10, metric,
                            kth_norms)
    assert 100 * exact["dist_gap"] <= limit < control["dist_gap"]
    assert exact["recall_at_10"] == 1.0
    assert control["recall_at_10"] > 0.5


@pytest.mark.parametrize("metric", ["dot", "L2", "euclidean"])
def test_unknown_metric_is_refused(metric):
    """A metric the reference does not know raises; it is never scored as
    L2."""
    db, q = corpus_and_queries(7, False, 5, 8)
    with pytest.raises(ValueError, match=repr(metric)):
        reference.Reference(db, metric)
    with pytest.raises(ValueError, match=repr(metric)):
        check.gap_unit(metric, q, np.ones(len(q)), np.ones(len(q)))


def test_ip_gap_unit_is_the_norms_at_the_cut_off():
    """Under ``ip`` a query's unit is |q| |x_k|; under ``l2`` and ``cosine``
    it stays the exact k-th distance."""
    q = np.array([[3.0, 4.0], [0.0, 2.0]], np.float32)
    kth_d = np.array([-7.0, 0.5], np.float32)
    kth_norms = np.array([2.0, 3.0], np.float32)
    np.testing.assert_array_equal(check.gap_unit("ip", q, kth_d, kth_norms),
                                  [10.0, 6.0])
    for metric in ("l2", "cosine"):
        np.testing.assert_array_equal(
            check.gap_unit(metric, q, kth_d, kth_norms),
            np.float32([1e-12, 0.5]))


def test_check_counts_bad_rows():
    ids = np.array([[0, 1, 2], [3, 3, 4], [5, -1, 6], [7, 8, 9]])
    d = np.array([[0, 1, 2], [0, 1, 2], [0, 1, 2], [0, 2, 1]], np.float32)
    assert check.bad_rows(ids, d, n=10, k=3) == 3
    assert check.bad_rows(ids[:, :2], d[:, :2], n=10, k=3) == 4
