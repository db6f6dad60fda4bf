"""Dispatching wrappers: Pallas on TPU, interpret-mode on request, pure-jnp
ref elsewhere (this container is CPU — Mosaic can't lower, so the default
path is the oracle; ``interpret=True`` runs the actual kernel bodies)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import gather_dist as _gd
from repro.kernels import l2dist as _l2
from repro.kernels import ref
from repro.kernels import topk as _tk
from repro.kernels import twotower_score as _tt


def _on_tpu() -> bool:
    # no except: a backend that fails to start must raise, not read as "CPU"
    return jax.default_backend() == "tpu"


def l2dist(q, c, *, mode: str = "auto", **kw):
    """mode: auto | pallas | interpret | ref"""
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return ref.l2dist_ref(q, c)
    if mode == "interpret":
        return _l2.l2dist(q, c, interpret=True, **kw)
    return _l2.l2dist(q, c, **kw)


def topk_min(d, k: int, *, mode: str = "auto", **kw):
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return ref.topk_min_ref(d, k)
    if mode == "interpret":
        return _tk.topk_min(d, k, interpret=True, **kw)
    return _tk.topk_min(d, k, **kw)


def gather_dist(vecs, q, ids, *, mode: str = "auto", **kw):
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return ref.gather_dist_ref(vecs, q, ids)
    if mode == "interpret":
        return _gd.gather_dist(vecs, q, ids, interpret=True, **kw)
    return _gd.gather_dist(vecs, q, ids, **kw)


def twotower_score(q, h, *, mode: str = "auto", **kw):
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return ref.twotower_score_ref(q, h)
    if mode == "interpret":
        return _tt.twotower_score(q, h, interpret=True, **kw)
    return _tt.twotower_score(q, h, **kw)
