"""Host-side spans → chrome://tracing-compatible JSONL and the JAX profiler.

``span(name)`` wraps the host phases (index build stages, the serving path,
prefill/decode, RAG retrieve, train steps).  A span records while the tracer
is started (``Tracer.start``) or while ``jax.profiler`` traces
(``jax.profiler.start_trace``).  When it records it also enters a
``jax.profiler.TraceAnnotation`` of the same name, so that under the profiler
the span sits in the trace's host plane, on the device planes' clock.

Events are Trace Event Format "complete" events (``ph: "X"``), ``ts`` and
``dur`` in µs from the tracer's origin ``Tracer.t0`` (a
``time.perf_counter`` reading), each with its own ``id``, the ``parent`` span
open on the same thread when it began, and the request id ``req``, which a
span inherits from its parent unless it sets one.  They are kept in memory
(``Tracer.events``); after ``start(path)`` they are also written one JSON
object per line to ``path``, which opens with ``[`` so chrome://tracing /
Perfetto load it directly (the trailing ``]`` is optional in the format,
which is what makes line-appending safe for crashing processes).

Off (the default) a span costs a couple of attribute loads and branches —
no clock reads, no allocation of event dicts.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled   # True while jax.profiler traces


class Tracer:
    def __init__(self):
        self.enabled = False
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._file = None
        self._path: Optional[str] = None
        self._ids = itertools.count(1)
        self._local = threading.local()
        # origin of ``ts``: an event spans t0 + ts/1e6 .. + dur/1e6 on the
        # host's perf_counter clock
        self.t0 = time.perf_counter()

    # -------------------------------------------------------------- control
    def start(self, path: Optional[str] = None) -> None:
        """Enable tracing; if ``path`` is given, stream events to it."""
        with self._lock:
            self._events.clear()
            self.t0 = time.perf_counter()
            if self._file is not None:
                self._file.close()
                self._file = None
            self._path = path
            if path:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                self._file = open(path, "w")
                self._file.write("[\n")
            self.enabled = True

    def stop(self) -> None:
        with self._lock:
            self.enabled = False
            if self._file is not None:
                self._file.close()
                self._file = None

    @property
    def path(self) -> Optional[str]:
        return self._path

    @property
    def recording(self) -> bool:
        return self.enabled or _profiling()

    # -------------------------------------------------------------- record
    def _stack(self) -> list:
        """This thread's open spans, as ``(id, req)``."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def complete_event(
        self, name: str, start: float, end: float,
        args: Optional[Dict[str, Any]] = None, *, req: Optional[int] = None,
    ) -> None:
        """Record ``name`` from ``start`` to ``end`` (``time.perf_counter``
        seconds), with no parent, when recording.  Its start may lie on
        another thread, and a profiler event cannot be back-dated, so it
        goes to the tracer alone, never to the profiler's trace."""
        if self.recording:
            self._record(next(self._ids), name, start, end, args, req, None)

    def _record(self, sid: int, name: str, start: float, end: float,
                args: Optional[Dict[str, Any]], req: Optional[int],
                parent: Optional[int]) -> None:
        event = {
            "name": name, "ph": "X", "ts": (start - self.t0) * 1e6,
            "dur": (end - start) * 1e6, "pid": os.getpid(),
            "tid": threading.get_ident(), "id": sid, "parent": parent,
            "req": req, "args": args or {},
        }
        with self._lock:
            self._events.append(event)
            if self._file is not None:
                self._file.write(json.dumps(event) + ",\n")
                self._file.flush()

    # -------------------------------------------------------------- export
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def dump(self, path: str) -> str:
        """Write the in-memory buffer as a chrome trace file."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            f.write("[\n")
            for e in self.events():
                f.write(json.dumps(e) + ",\n")
        return path

    def span_summary(self) -> Dict[str, dict]:
        """name -> {count, total_s, mean_s} over complete events."""
        out: Dict[str, dict] = {}
        for e in self.events():
            if e.get("ph") != "X":
                continue
            s = out.setdefault(e["name"], {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] += e["dur"] / 1e6
        for s in out.values():
            s["mean_s"] = s["total_s"] / s["count"]
        return out


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


@contextmanager
def span(name: str, *, req: Optional[int] = None, **attrs):
    """Time a host-side phase; a no-op when neither the tracer nor the JAX
    profiler records.

    ``req`` tags the span and the spans opened inside it with a request id.
    Attribute values land in the trace event's ``args`` (and the profiler
    event's metadata) and must be JSON-serializable.

    It yields the attribute dict while it records, and ``None`` otherwise:
    attributes known only inside the span go into that dict, and reach the
    trace event's ``args`` (not the profiler event, which began already).
    """
    t = _TRACER
    if not t.enabled and not _profiling():
        yield None
        return
    stack = t._stack()
    parent, parent_req = stack[-1] if stack else (None, None)
    if req is None:
        req = parent_req
    sid = next(t._ids)
    stack.append((sid, req))
    meta = attrs if req is None else {"req": req, **attrs}
    start = time.perf_counter()
    try:
        with TraceAnnotation(name, **meta):
            yield attrs
    finally:
        end = time.perf_counter()
        stack.pop()
        t._record(sid, name, start, end, attrs, req, parent)


def read_trace(path: str) -> List[dict]:
    """Parse a trace file written by this module (or any chrome JSON array)."""
    with open(path) as f:
        text = f.read().strip()
    if text.startswith("["):
        text = text[1:]
    text = text.rstrip().rstrip("]").rstrip().rstrip(",")
    if not text:
        return []
    return json.loads("[" + text + "]")
