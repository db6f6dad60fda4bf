"""``SearchParams`` — the single search-knob object (ISSUE 8 API redesign).

Every search entry point (``batched_search``, ``GateIndex.search`` /
``search_baseline`` / ``search_routed`` / ``warmup_ladder``,
``LadderRung.params``, the daemon's ``SearchRequest``) accepts and carries
one frozen ``SearchParams`` instead of a drift-prone spread of keyword
arguments.  Being frozen (and therefore hashable) it doubles as the *static
jit key* of the compiled search program: two call sites with equal params
share one XLA executable, and the precompiled-ladder invariant ("adaptation
never recompiles") becomes "the set of distinct ``SearchParams`` values is
warmed up front".

Old per-knob kwargs keep working through :func:`resolve_search_params`: each
legacy keyword warns **once** per (call site, keyword) with a
``DeprecationWarning`` attributed to the caller and increments the
``api.deprecated_kwargs`` counter — so migration debt is visible on a
``/metrics`` scrape, not just in logs.  See docs/api.md for the mapping.
"""
from __future__ import annotations

import dataclasses
import sys
import warnings
from dataclasses import dataclass
from typing import Dict, Optional, Set, Tuple

_METRICS = ("l2", "cosine", "ip")

# Distance-kernel variants (ISSUE 10).  "xla" is the plain gather+compute
# formulation; "fused" DMAs rows in-kernel via scalar prefetch (bit-identical
# fp32 distances); "fused_q8" reads the int8 codebook (~4× fewer HBM bytes
# per hop) and exact-reranks the top k·rerank_mult beam slots in fp32.
_KERNELS = ("xla", "fused", "fused_q8")

# Legacy keyword names resolve_search_params understands, in SearchParams
# field order.  ``conv_k`` predates the redesign as a kwarg on
# batched_search; ``k`` is accepted here too for **legacy-dict** resolution
# even though the blessed signatures keep a non-deprecated ``k=`` shortcut.
LEGACY_SEARCH_KWARGS: Tuple[str, ...] = (
    "k", "beam_width", "max_hops", "visited_ring", "metric", "instrument",
    "conv_k",
)

_warned_once: Set[Tuple[str, str]] = set()


@dataclass(frozen=True)
class SearchParams:
    """Frozen bundle of every Algorithm-1 search knob.

    ``beam_width`` / ``max_hops`` / ``visited_ring`` / ``instrument`` /
    ``conv_k`` / ``k`` / ``metric`` are all *static* under jit — a distinct
    ``SearchParams`` value is a distinct compiled program.
    """

    k: int = 10                 # results returned per query
    beam_width: int = 64        # Algorithm-1 beam slots L
    max_hops: int = 256         # expansion budget
    visited_ring: int = 512     # dedup ring capacity
    metric: str = "l2"          # "l2" (squared), "cosine" (1 - cos), "ip" (-q·x)
    instrument: bool = False    # device-side SearchTelemetry on/off
    conv_k: int = 10            # top-k prefix watched for convergence
    kernel: str = "xla"         # distance kernel: "xla" | "fused" | "fused_q8"
    rerank_mult: int = 4        # q8 exact-rerank width α: top k·α beam slots
    kernel_interpret: bool = False  # run Pallas bodies in interpret mode (CPU)

    def __post_init__(self):
        if self.metric not in _METRICS:
            raise ValueError(
                f"metric must be one of {_METRICS}, got {self.metric!r}"
            )
        if self.kernel not in _KERNELS:
            raise ValueError(
                f"kernel must be one of {_KERNELS}, got {self.kernel!r}"
            )
        if self.metric == "ip" and self.kernel != "xla":
            raise ValueError(
                f'metric="ip" is served by kernel="xla" only; kernel='
                f"{self.kernel!r} scores l2 and cosine"
            )
        for name in ("k", "beam_width", "max_hops", "visited_ring", "conv_k",
                     "rerank_mult"):
            v = getattr(self, name)
            if not isinstance(v, (int,)) or isinstance(v, bool) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")

    def replace(self, **changes) -> "SearchParams":
        """Functional update (``dataclasses.replace`` shorthand)."""
        return dataclasses.replace(self, **changes)


def reset_deprecation_state() -> None:
    """Forget which (call site, kwarg) pairs already warned — test hook."""
    _warned_once.clear()


def warn_deprecated_kwarg(
    where: str, kwarg: str, instead: str, *, stacklevel: int = 3
) -> None:
    """Warn once per (where, kwarg); always bump ``api.deprecated_kwargs``.

    The default ``stacklevel=3`` attributes the warning to the *caller of
    the shimmed API* (this helper → the shimmed API → its caller), so an
    ``error::DeprecationWarning`` filter scoped to ``repro.*`` modules
    catches repro-internal misuse without penalizing downstream users.
    The message embeds the caller's ``file:line`` so the one-shot warning
    is actionable from a log even after the warning-dedup machinery has
    swallowed the repeat occurrences (ISSUE 9 satellite).
    """
    # imported lazily: keeps this module dependency-free so it can be the
    # bottom of the repro.graphs / repro.obs import graph
    from repro.obs.registry import get_registry

    get_registry().counter(
        "api.deprecated_kwargs",
        "calls that used pre-SearchParams keyword arguments",
    ).inc()
    key = (where, kwarg)
    if key in _warned_once:
        return
    _warned_once.add(key)
    # the frame `stacklevel` frames up is where warnings.warn attributes
    # the warning: 1 = this helper, so the caller sits at stacklevel - 1
    # hops above us
    caller = ""
    try:
        frame = sys._getframe(max(stacklevel - 1, 1))
        caller = f" (called from {frame.f_code.co_filename}:{frame.f_lineno})"
    except ValueError:
        pass  # fewer frames than stacklevel (e.g. exec'd top level)
    warnings.warn(
        f"{where}({kwarg}=...) is deprecated; pass {instead} instead "
        f"(see docs/api.md){caller}",
        DeprecationWarning,
        stacklevel=stacklevel,
    )


def resolve_search_params(
    where: str,
    params: Optional[SearchParams],
    legacy: Dict,
    *,
    k: Optional[int] = None,
    default: Optional[SearchParams] = None,
) -> SearchParams:
    """Merge ``params`` + deprecated per-knob ``legacy`` kwargs + ``k``.

    Precedence (last wins): ``default`` → ``params`` → legacy kwargs →
    the blessed ``k=`` shortcut.  Unknown legacy keys raise ``TypeError``
    exactly like a normal bad keyword would.
    """
    unknown = set(legacy) - set(LEGACY_SEARCH_KWARGS)
    if unknown:
        raise TypeError(
            f"{where}() got unexpected keyword argument(s) "
            f"{sorted(unknown)}; valid search knobs live on SearchParams"
        )
    out = params if params is not None else (
        default if default is not None else SearchParams()
    )
    if legacy:
        for key in legacy:
            warn_deprecated_kwarg(
                where, key, f"params=SearchParams({key}=...)", stacklevel=4
            )
        out = out.replace(**legacy)
    if k is not None:
        out = out.replace(k=k)
    return out
