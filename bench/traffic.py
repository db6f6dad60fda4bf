"""The one traffic generator: reads a mix's parameters and drives the server.

A mix (``bench/traffic/<mix>.json``) is either

* ``"loop": "open"``: requests of ``queries_per_request`` queries arrive at
  ``rate_rps``: ``round(rate_rps * seconds)`` requests, with gaps between
  them that are the quantiles of an exponential distribution (so the
  arrivals are Poisson-like), in an order drawn from the mix's
  ``schedule_seed``.  The schedule is the same for every run: the seed
  changes the queries, not the bursts, which would otherwise swing the tail
  from seed to seed far more than the server does.  Each request is timed
  from when it was due, so a stall also counts against the requests it
  delays.
* ``"loop": "closed"``: ``clients`` callers each send a request and wait for
  its reply before sending the next, until the window closes.

The queries of every request come from the run's seed, drawn by the
configuration's corpus generator as the mix's ``query_kind``
(``bench/data/<generator>.py``).
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

GRACE_S = 60.0      # an answer may come this long after the window closes


@dataclass
class Record:
    """One request: when it was due, sent and answered, and its answer."""
    queries: np.ndarray
    due: float
    sent: float = float("nan")
    done: float = float("nan")
    ids: Optional[np.ndarray] = None
    dists: Optional[np.ndarray] = None
    hops: Optional[np.ndarray] = None
    dist_evals: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def answered(self) -> bool:
        return self.ids is not None

    @property
    def latency_s(self) -> float:
        return self.done - self.due


def open_offsets(rate_rps: float, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of an open loop: a fixed count
    and a fixed set of exponential-quantile gaps, in ``rng``'s order."""
    n = max(1, int(round(rate_rps * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def lateness_s(records: List[Record]) -> np.ndarray:
    """How late the generator sent each request after it was due."""
    return np.array([r.sent - r.due for r in records if r.sent == r.sent])


@dataclass
class Window:
    """What a window drove and when: ``start`` and ``end`` on the host's
    ``perf_counter`` clock; ``end`` is the close, or the last answer of a
    closed loop when that comes later."""
    records: List[Record]
    start: float
    end: float
    late: np.ndarray = field(default_factory=lambda: np.zeros(0))

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _fill(rec: Record, result) -> None:
    res, _tele = result
    rec.ids = np.asarray(res.ids)
    rec.dists = np.asarray(res.dists)
    rec.hops = np.asarray(res.hops)
    rec.dist_evals = np.asarray(res.dist_evals)


def drive_open(submit: Callable, requests: List[np.ndarray],
               offsets: np.ndarray, seconds: float) -> Window:
    """Send ``requests[i]`` at ``offsets[i]``; one waiter collects answers
    in order (the server answers in order)."""
    records = [Record(q, 0.0) for q in requests]
    inflight: "queue.Queue" = queue.Queue()
    start = time.perf_counter() + 0.05
    for rec, off in zip(records, offsets):
        rec.due = start + float(off)
    deadline = start + seconds + GRACE_S

    def wait():
        while True:
            item = inflight.get()
            if item is None:
                return
            rec, pending = item
            try:
                result = pending.get(max(deadline - time.perf_counter(), 0.0))
                rec.done = time.perf_counter()
                _fill(rec, result)
            except Exception as e:   # noqa: BLE001 — a failed request
                rec.error = f"{type(e).__name__}: {e}"

    waiter = threading.Thread(target=wait, name="bench-waiter")
    waiter.start()
    try:
        for rec in records:
            delay = rec.due - time.perf_counter()
            if delay > 0:
                with TraceAnnotation("bench.wait_for_arrival"):
                    time.sleep(delay)
            rec.sent = time.perf_counter()
            inflight.put((rec, submit(rec.queries)))
    finally:
        inflight.put(None)
        waiter.join()
    return Window(records, start, start + seconds, lateness_s(records))


def drive_closed(submit: Callable, make_queries: Callable[[int, int], np.ndarray],
                 clients: int, seconds: float) -> Window:
    """``clients`` callers, each sending ``make_queries(client, i)`` and
    waiting for the answer, until ``seconds`` have passed."""
    start = time.perf_counter()
    close = start + seconds
    per_client: List[List[Record]] = [[] for _ in range(clients)]

    def client(c: int):
        i = 0
        while time.perf_counter() < close:
            rec = Record(make_queries(c, i), 0.0)
            rec.due = rec.sent = time.perf_counter()
            per_client[c].append(rec)
            try:
                result = submit(rec.queries).get(close + GRACE_S
                                                 - time.perf_counter())
                rec.done = time.perf_counter()
                _fill(rec, result)
            except Exception as e:   # noqa: BLE001 — a failed request
                rec.error = f"{type(e).__name__}: {e}"
                return
            i += 1

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    records = [r for recs in per_client for r in recs]
    last = max((r.done for r in records if r.answered), default=close)
    return Window(records, start, max(close, last))
