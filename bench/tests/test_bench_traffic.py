"""The open-loop schedule and its lateness arithmetic."""
import os
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import traffic  # noqa: E402


def test_schedules_share_their_gaps_in_their_own_order():
    a = traffic.open_offsets(10.0, 30.0, np.random.default_rng(1))
    b = traffic.open_offsets(10.0, 30.0, np.random.default_rng(2 ** 33 + 5))
    assert len(a) == len(b) == 300
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    ga = np.sort(np.diff(np.append(a, a[-1])))
    gb = np.sort(np.diff(np.append(b, b[-1])))
    assert not np.array_equal(a, b)
    # the same multiset of gaps, one gap left out by each differencing
    full = -np.log1p(-(np.arange(300) + 0.5) / 300)
    full *= 30.0 / full.sum()
    assert set(np.round(ga[1:], 9)) <= set(np.round(full, 9))
    assert set(np.round(gb[1:], 9)) <= set(np.round(full, 9))
    assert a[-1] < 30.0


class Pending:
    def __init__(self, result, ready_at):
        self.result, self.ready_at = result, ready_at

    def get(self, timeout):
        time.sleep(max(self.ready_at - time.perf_counter(), 0.0))
        return self.result


class Result:
    def __init__(self, n):
        self.ids = np.zeros((n, 1), np.int32)
        self.dists = np.zeros((n, 1), np.float32)
        self.hops = np.ones(n, np.int32)
        self.dist_evals = np.ones(n, np.int32)


def test_latency_counts_from_when_a_request_was_due():
    """A server that stalls on the first request for 0.3 s: the requests
    due meanwhile are sent on time and each is late by what remains of
    the stall when it was due."""
    lock = threading.Lock()
    state = {"free_at": 0.0}

    def submit(q):
        with lock:
            now = time.perf_counter()
            start = max(now, state["free_at"])
            service = 0.3 if state["free_at"] == 0.0 else 0.001
            state["free_at"] = start + service
            return Pending((Result(len(q)), None), state["free_at"])

    offsets = np.array([0.0, 0.1, 0.2, 0.5])
    requests = [np.zeros((2, 4), np.float32) for _ in offsets]
    win = traffic.drive_open(submit, requests, offsets, seconds=0.6)
    lat = np.array([r.latency_s for r in win.records])
    assert all(r.answered for r in win.records)
    assert lat == pytest.approx([0.3, 0.201, 0.102, 0.001], abs=0.03)
    assert len(win.late) == 4 and np.all(win.late >= 0)
    assert np.all(win.late < 0.03)
    assert win.seconds == pytest.approx(0.6)


def test_closed_loop_window_runs_to_the_last_answer():
    def submit(q):
        return Pending((Result(len(q)), None), time.perf_counter() + 0.04)

    win = traffic.drive_closed(submit, lambda c, i: np.zeros((3, 4)),
                               clients=2, seconds=0.2)
    assert 8 <= len(win.records) <= 12
    assert all(r.answered for r in win.records)
    last = max(r.done for r in win.records)
    assert win.end == pytest.approx(last)
    assert win.seconds >= 0.2
