"""The readers of the program's serving spans and of entry selection's
device time, on hand-made spans, windows and trace reductions."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import spec  # noqa: E402
import traffic  # noqa: E402

SPAN_METRICS = ("daemon.queue_wait_ms", "daemon.host_ms_per_request")


def answered(n_queries):
    rec = traffic.Record(np.zeros((n_queries, 4), np.float32), 0.0)
    rec.ids = np.zeros((n_queries, 10), np.int32)
    return rec


@pytest.fixture
def tracer(monkeypatch):
    import repro.obs.trace as trace_mod

    t = trace_mod.Tracer()
    monkeypatch.setattr(trace_mod, "_TRACER", t)
    t.start()
    yield t
    t.stop()


def ctx_at(start, end, records=()):
    return {"window": traffic.Window(list(records), start, end),
            "trace": None}


def test_span_readers(tracer):
    w0 = tracer.t0 + 10.0
    ev = tracer.complete_event
    # before the window: a warm request, left out
    ev("daemon.queue_wait", w0 - 3.0, w0 - 1.0, req=1)
    ev("daemon.serve", w0 - 1.0, w0 - 0.5, req=1)
    # request 2: waited 4 ms; served 30 ms, 20 of them on the device
    ev("daemon.queue_wait", w0 + 0.100, w0 + 0.104, req=2)
    ev("daemon.serve", w0 + 0.104, w0 + 0.134, req=2)
    ev("gate.search.device_wait", w0 + 0.110, w0 + 0.130, req=2)
    # request 3: waited 10 ms; served 14 ms, 2 on the device, in two waits
    ev("daemon.queue_wait", w0 + 0.130, w0 + 0.140, req=3)
    ev("daemon.serve", w0 + 0.140, w0 + 0.154, req=3)
    ev("gate.search.device_wait", w0 + 0.141, w0 + 0.142, req=3)
    ev("gate.search.device_wait", w0 + 0.150, w0 + 0.151, req=3)
    # after the window closed: left out
    ev("daemon.queue_wait", w0 + 5.0, w0 + 6.0, req=4)
    ev("daemon.serve", w0 + 6.0, w0 + 7.0, req=4)
    ctx = ctx_at(w0, w0 + 1.0)
    wait = spec.metric_reader("daemon.queue_wait_ms")(ctx)
    host = spec.metric_reader("daemon.host_ms_per_request")(ctx)
    assert wait == pytest.approx((4.0 + 10.0) / 2, rel=1e-6)
    assert host == pytest.approx((10.0 + 12.0) / 2, rel=1e-6)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_readers_find_nothing(tracer, monkeypatch, name):
    read = spec.metric_reader(name)
    w0 = tracer.t0 + 10.0
    assert read(ctx_at(w0, w0 + 1.0)) is None          # no spans at all
    tracer.complete_event("daemon.queue_wait", w0 - 2.0, w0 - 1.0, req=1)
    tracer.complete_event("daemon.serve", w0 - 1.0, w0 - 0.5, req=1)
    assert read(ctx_at(w0, w0 + 1.0)) is None          # none in the window

    class OlderTracer:
        """A program without these spans or a public origin."""

        def events(self):
            return [{"name": "gate.build.hubs", "ph": "X", "ts": 0.0,
                     "dur": 5.0, "args": {}}]

    import repro.obs.trace as trace_mod

    monkeypatch.setattr(trace_mod, "_TRACER", OlderTracer())
    assert read(ctx_at(w0, w0 + 1.0)) is None


def test_entry_device_time_per_query():
    read = spec.metric_reader("entry.device_us_per_query")
    red = {"module_s": {"jit_gate_select_entries(12)": 0.003,
                        "jit__batched_search(7)": 2.0,
                        "jit_convert_element_type(3)": 0.001}}
    records = [answered(1024), answered(1024), traffic.Record(
        np.zeros((1024, 4), np.float32), 0.0)]   # the last one unanswered
    ctx = {"window": traffic.Window(records, 0.0, 1.0), "trace": red}
    assert read(ctx) == pytest.approx(1e6 * 0.003 / 2048)
    # a program whose entry selection is not one named module: nothing
    ctx["trace"] = {"module_s": {"jit__batched_search(7)": 2.0,
                                 "jit_dot_general(1)": 0.002}}
    assert read(ctx) is None
    ctx["trace"] = None                                  # an untraced run
    assert read(ctx) is None
    ctx = {"window": traffic.Window([], 0.0, 1.0), "trace": red}
    assert read(ctx) is None                             # nothing answered
