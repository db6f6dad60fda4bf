"""Reduction of a JAX profiler trace to device time, idle time and breakdown.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain event tuples; ``reduce`` works on those, so that it can be checked on a
small recorded trace.

* Device busy time is the union of the intervals of the device's XLA module
  executions inside the traced window.  Idle is the rest of the window.
* Device time per module is summed by module name (``_batched_search`` is the
  search program; everything else in a serving window is entry selection and
  transfers).
* ``device_ops`` are the XLA operations that took the most device time.
* ``idle_gaps`` are the longest gaps between device work, each named by the
  host event that overlaps it most (what the host was doing meanwhile).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

WINDOW_ANNOTATION = "bench.window"
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


@dataclass
class TraceEvents:
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per device
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> TraceEvents:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = TraceEvents()
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        is_host = plane.name.startswith("/host:")
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            if is_device and line.name in MODULE_LINES:
                out.modules.setdefault(plane.name, []).extend(evs)
            elif is_device and line.name in OP_LINES:
                out.ops.setdefault(plane.name, []).extend(evs)
            elif is_host:
                out.host.extend(e for e in evs if e[2] > e[1])
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def window_of(ev: TraceEvents) -> Tuple[int, int]:
    """The traced window: the ``bench.window`` annotation on the host."""
    marks = [(s, e) for n, s, e in ev.host if n == WINDOW_ANNOTATION]
    if not marks:
        raise ValueError(f"no {WINDOW_ANNOTATION!r} annotation in the trace")
    return marks[0]


def short_op(name: str) -> str:
    """``%fusion.12 fusion`` from an HLO instruction's text."""
    m = re.match(r"(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _name_gap(host: List[Event], s: int, e: int) -> str:
    """The shortest host event that covers half the gap or more, else the
    one that overlaps it most."""
    cover, most = None, None
    for n, hs, he in host:
        if n == WINDOW_ANNOTATION:
            continue
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        if 2 * ov >= e - s and (cover is None or he - hs < cover[1]):
            cover = (n, he - hs)
        if most is None or ov > most[1]:
            most = (n, ov)
    if cover is not None:
        return cover[0]
    return most[0] if most is not None else "no host event"


def reduce(ev: TraceEvents, top: int = 10) -> dict:
    """Window, busy and idle seconds averaged over the devices, device
    seconds per module, and the breakdown's ``device_ops``/``idle_gaps``."""
    lo, hi = window_of(ev)
    devices = sorted(ev.modules)
    if not devices:
        raise ValueError("no device module events in the trace")
    busy_total, per_module = 0.0, defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for dev in devices:
        mods = _clip(ev.modules[dev], lo, hi)
        busy = _union([(s, e) for _, s, e in mods])
        busy_total += sum(e - s for s, e in busy) / 1e9
        for n, s, e in mods:
            per_module[n] += (e - s) / 1e9 / len(devices)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append(((e - s) / 1e9, (s, e)))
    gaps.sort(key=lambda g: -g[0])
    op_time = defaultdict(float)
    for dev in devices:
        for n, s, e in _clip(ev.ops.get(dev, []), lo, hi):
            op_time[short_op(n)] += (e - s) / 1e9 / len(devices)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / len(devices),
        "devices": len(devices),
        "module_s": dict(per_module),
        "breakdown": {
            "device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[_name_gap(ev.host, s, e), t]
                          for t, (s, e) in gaps[:top]],
        },
    }


def module_seconds(red: dict, needle: str) -> Tuple[float, float]:
    """(seconds in modules whose name holds ``needle``, seconds in the
    others)."""
    inside = sum(t for n, t in red["module_s"].items() if needle in n)
    return inside, sum(red["module_s"].values()) - inside
