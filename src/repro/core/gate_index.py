"""GateIndex — the paper's full pipeline behind one build/search API.

Build (offline):
  1. underlying proximity graph (NSG by default; any padded adjacency works)
  2. hub extraction via HBKM (§4.1)
  3. guided-walk subgraph sampling + WL topology tokens (§4.2)
  4. positive/negative query queues from historical queries (Def. 4)
  5. contrastive two-tower training (§4.3, Eq. 3+4)
  6. navigation graph over learned hub representations

Search (online, fully jit-able):
  query tower MLP → greedy cosine descent on the nav graph → entry hub →
  Algorithm-1 beam search on the base graph.

Inner product (``GateConfig(metric="ip")``): the build reduces maximum
inner-product search to L2 (Bachrach et al., RecSys 2014) and builds the
graph, hubs and samples on the reduced rows; the index keeps and serves the
original rows under ``SearchParams(metric="ip")``.

GATE is a *plug-in*: ``GateIndex.from_graph`` accepts any (neighbors, enter)
pair, leaving the underlying index untouched (paper §1).
"""
from __future__ import annotations

import functools
import pickle
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import navgraph as ng
from repro.core.hubs import HubSet, extract_hubs, kmeans_hubs
from repro.core.samples import SampleSet, hop_counts, make_samples, top1_targets
from repro.core.subgraph import sample_all_subgraphs
from repro.core.topo_embed import embed_all
from repro.core.twotower import (
    TwoTowerConfig,
    hub_tower,
    query_tower,
    train_two_tower,
)
from repro.graphs.nsg import NSG, build_nsg
from repro.graphs.params import (
    SearchParams,
    resolve_search_params,
    warn_deprecated_kwarg,
)
from repro.graphs.search import (
    SearchResult,
    batched_search,
    lower_batched_search,
)
from repro.obs import (
    SearchTelemetry,
    call_telemetry_sink,
    record_search_telemetry,
    registry_sink,
    span,
    summarize,
    warn_on_ring_overflow,
)
from repro import quant as quantlib

# "telemetry_sink not passed" marker: the default sink is registry_sink,
# but an explicit None must mean "no side effects" (old record=False)
_UNSET = object()


@functools.partial(
    jax.jit,
    static_argnames=("tower_cfg", "nav_start", "flat", "probe_width"),
)
def gate_select_entries(tower_params, queries, nav_reps, nav_neighbors,
                        hub_ids, *, tower_cfg: TwoTowerConfig, nav_start: int,
                        flat: bool, probe_width: int):
    """Entry selection as one program: query tower, then either one fused
    ``twotower_score`` over every hub and its argmax / top-``probe_width``
    (``flat``: small hub sets), or the greedy cosine descent on the
    navigation graph (large hub sets: no |V| scores per query); then the
    hubs' base-graph ids, with each query's nav-graph descent length
    (zeros on the flat path, which takes no hops).  Its XLA module is named
    after this function, so a profiler trace finds it by
    ``select_entries``."""
    z_q = query_tower(tower_params, tower_cfg,
                      jnp.asarray(queries, jnp.float32))
    if flat:
        from repro.kernels import ops

        scores = ops.twotower_score(z_q, nav_reps)
        if probe_width == 1:
            hub_local = jnp.argmax(scores, axis=1)[:, None]
        else:
            _, hub_local = jax.lax.top_k(scores, probe_width)
        nav_hops = jnp.zeros((hub_local.shape[0],), jnp.int32)
    else:
        hub_local, nav_hops = ng.descend(
            ng.NavGraphDevice(nav_reps, nav_neighbors, nav_start), z_q,
            probe_width=probe_width, instrument=True,
        )
    return hub_ids[hub_local], nav_hops


# the search metrics an index built under each GateConfig.metric serves
SERVES = {"l2": ("l2", "cosine"), "ip": ("ip",)}


def mips_reduce(db: np.ndarray, queries: np.ndarray):
    """``(rows, queries, M)`` one column wider, in which the L2 order is the
    inner-product order (Bachrach et al., RecSys 2014): each row gains
    ``sqrt(M² − ‖x‖²)`` with ``M = max ‖x‖``, each query 0, so that
    ``‖q' − x'‖² = ‖q‖² + M² − 2 q·x``."""
    sq = np.einsum("ij,ij->i", db, db)
    m2 = sq.max()
    rows = np.concatenate(
        [db, np.sqrt(np.maximum(m2 - sq, 0.0))[:, None]], axis=1
    ).astype(np.float32)
    qs = np.concatenate(
        [queries, np.zeros((len(queries), 1), queries.dtype)], axis=1
    ).astype(np.float32)
    return rows, qs, float(np.sqrt(m2))


@dataclass(frozen=True)
class GateConfig:
    n_hubs: int = 64            # |V| (paper: 512 at 10M scale)
    h: int = 5                  # subgraph max hop
    t_pos: int = 3
    t_neg: int = 15
    s_edges: int = 8            # nav-graph out-degree
    d_u: int = 64
    wl_iters: int = 3
    subgraph_max_nodes: int = 256
    epochs: int = 300
    batch_hubs: int = 64
    lr: float = 1e-3
    probe_width: int = 1
    hbkm_branch: int = 8
    hbkm_lam: float = 1.0
    # H(q, V_i) measurement (Def. 4): "greedy" = Algorithm-1 path length
    # (the paper's implementation — long for bad entries, short for good
    # ones, highly discriminative); "bfs" = literal shortest-path hops
    # (small-world diameters make it nearly constant — kept for ablation).
    hop_mode: str = "greedy"
    hop_beam: int = 8
    hop_max: int = 48
    # entry selection: hub sets up to this size score every hub with one
    # twotower_score matmul; larger sets use the nav-graph cosine descent
    flat_score_max: int = 128
    # ablations (§5.2 Exp-2)
    use_hbkm: bool = True        # False → GATE w/o H (plain k-means hubs)
    use_fusion: bool = True      # False → GATE w/o FE
    use_contrastive: bool = True # False → GATE w/o L (untrained towers)
    seed: int = 0
    # the metric the index is built for: "l2" (serves l2 and cosine) or
    # "ip" (serves ip alone; the build runs on mips_reduce's rows)
    metric: str = "l2"

    def __post_init__(self):
        if self.metric not in SERVES:
            raise ValueError(f"GateConfig.metric must be one of "
                             f"{tuple(SERVES)}, got {self.metric!r}")


def _build_space(db: np.ndarray, train_queries: np.ndarray,
                 gcfg: GateConfig):
    """The rows and training queries the graph, hubs and samples are built
    on: under ``metric="ip"`` ``mips_reduce``'s, else the inputs."""
    if gcfg.metric != "ip":
        return db, train_queries
    with span("gate.build.mips_reduce", n=len(db), d=db.shape[1]) as ev:
        rows, qs, m = mips_reduce(db, train_queries)
        if ev is not None:
            ev["M"] = m
    return rows, qs


@dataclass
class GateIndex:
    db: np.ndarray
    neighbors: np.ndarray          # base-graph padded adjacency
    enter_id: int                  # base-graph default entry (for baselines)
    hubs: HubSet
    tower_params: Dict
    tower_cfg: TwoTowerConfig
    nav: ng.NavGraph
    gcfg: GateConfig
    build_report: Dict = field(default_factory=dict)
    # int8 codebook for SearchParams(kernel="fused_q8") — built lazily by
    # ensure_quantized() or eagerly at build time; persisted by save()
    quant: Optional[quantlib.QuantizedDb] = None

    # device-side caches
    _dev: Optional[dict] = None

    # ------------------------------------------------------------------ build
    @classmethod
    def from_graph(
        cls,
        db: np.ndarray,
        neighbors: np.ndarray,
        enter_id: int,
        train_queries: np.ndarray,
        gcfg: GateConfig = GateConfig(),
    ) -> "GateIndex":
        """GATE over a given graph; under ``gcfg.metric="ip"`` the graph is
        one built on ``mips_reduce``'s rows."""
        return cls._from_graph(db, neighbors, enter_id, train_queries, gcfg,
                               _build_space(db, train_queries, gcfg))

    @classmethod
    def _from_graph(cls, db, neighbors, enter_id, train_queries, gcfg,
                    space) -> "GateIndex":
        """``from_graph`` with the build space (rows, training queries)
        already made: hubs, subgraphs and samples are built in it, the
        towers and the index on ``db`` and ``train_queries``."""
        space_db, space_tq = space
        report = {}
        t0 = time.time()
        with span("gate.build.hubs", n_hubs=gcfg.n_hubs,
                  method="hbkm" if gcfg.use_hbkm else "kmeans"):
            if gcfg.use_hbkm:
                hubs = extract_hubs(
                    space_db, gcfg.n_hubs, branch_k=gcfg.hbkm_branch,
                    lam=gcfg.hbkm_lam, seed=gcfg.seed,
                )
            else:
                hubs = kmeans_hubs(space_db, gcfg.n_hubs, seed=gcfg.seed)
        report["t_hubs"] = time.time() - t0

        t0 = time.time()
        with span("gate.build.subgraphs", h=gcfg.h,
                  max_nodes=gcfg.subgraph_max_nodes):
            sgs = sample_all_subgraphs(
                space_db, neighbors, hubs.ids, h=gcfg.h,
                max_nodes=gcfg.subgraph_max_nodes, seed=gcfg.seed,
            )
        with span("gate.build.topo_embed", d_u=gcfg.d_u,
                  wl_iters=gcfg.wl_iters):
            u_toks = embed_all(
                sgs, gcfg.d_u, wl_iters=gcfg.wl_iters, seed=gcfg.seed
            )
        report["t_topo"] = time.time() - t0
        report["subgraph_nodes_mean"] = float(
            np.mean([len(s.nodes) for s in sgs])
        )

        t0 = time.time()
        with span("gate.build.samples", hop_mode=gcfg.hop_mode,
                  n_queries=len(train_queries)):
            targets = top1_targets(space_db, space_tq)
            if gcfg.hop_mode == "greedy":
                from repro.core.samples import greedy_hops

                hops = greedy_hops(
                    space_db, neighbors, space_tq, hubs.ids, targets,
                    beam_width=gcfg.hop_beam, max_hops=gcfg.hop_max,
                )
            else:
                hops = hop_counts(neighbors, targets, hubs.ids)
            samples = make_samples(
                hops, t_pos=gcfg.t_pos, t_neg=gcfg.t_neg, seed=gcfg.seed
            )
        report["t_samples"] = time.time() - t0
        report["samples"] = samples.stats()

        tcfg = TwoTowerConfig(
            d_p=db.shape[1], d_u=gcfg.d_u, use_fusion=gcfg.use_fusion,
            lr=gcfg.lr,
        )
        t0 = time.time()
        with span("gate.build.train_towers", epochs=gcfg.epochs,
                  contrastive=gcfg.use_contrastive):
            if gcfg.use_contrastive:
                params, train_rep = train_two_tower(
                    tcfg, db[hubs.ids], u_toks, train_queries, samples,
                    epochs=gcfg.epochs, batch_hubs=gcfg.batch_hubs,
                    seed=gcfg.seed,
                )
                report["loss_first"] = train_rep.losses[0]
                report["loss_last"] = train_rep.losses[-1]
            else:  # ablation GATE w/o L: random-init towers, no training
                from repro.core.twotower import init_params

                params = init_params(tcfg, jax.random.PRNGKey(gcfg.seed))
        report["t_train"] = time.time() - t0

        with span("gate.build.nav_graph", s=gcfg.s_edges):
            reps = np.asarray(
                hub_tower(params, tcfg, jnp.asarray(db[hubs.ids], jnp.float32),
                          jnp.asarray(u_toks, jnp.float32))
            )
            nav = ng.build_nav_graph(reps, s=gcfg.s_edges)
        return cls(
            db=db, neighbors=neighbors, enter_id=enter_id, hubs=hubs,
            tower_params=params, tower_cfg=tcfg, nav=nav, gcfg=gcfg,
            build_report=report,
        )

    @classmethod
    def build(
        cls,
        db: np.ndarray,
        train_queries: np.ndarray,
        gcfg: GateConfig = GateConfig(),
        nsg: Optional[NSG] = None,
        **nsg_kw,
    ) -> "GateIndex":
        """NSG, then GATE over it.  Under ``gcfg.metric="ip"`` everything
        built with L2 (the graph, hubs, samples) is built on
        ``mips_reduce``'s rows, which are freed once it is built."""
        space = _build_space(db, train_queries, gcfg)
        if nsg is None:
            with span("gate.build.nsg", n=len(db)):
                nsg = build_nsg(space[0], **nsg_kw)
        return cls._from_graph(
            db, nsg.neighbors, nsg.enter_id, train_queries, gcfg, space
        )

    # ----------------------------------------------------------------- search
    def _device(self):
        if self._dev is None:
            self._dev = {
                "db": jnp.asarray(self.db),
                "neighbors": jnp.asarray(self.neighbors),
                "hub_ids": jnp.asarray(self.hubs.ids, jnp.int32),
                "nav": ng.NavGraphDevice.from_host(self.nav),
            }
        return self._dev

    def ensure_quantized(self, block: int = quantlib.BLOCK) -> quantlib.QuantizedDb:
        """Build (once) and return the int8 codebook for ``fused_q8`` search.

        Deterministic host-side quantization of ``db`` (per-(row, block)
        affine int8 — ``repro.quant``); the result is cached on the instance
        and included by ``save()``.  Registers the codebook size as the
        ``gate.quant_bytes`` gauge so the ~4× footprint win is visible on a
        ``/metrics`` scrape.
        """
        if self.quant is None or self.quant.block != block:
            with span("gate.quantize_db", n=len(self.db), block=block):
                self.quant = quantlib.quantize_db(self.db, block=block)
            if self._dev is not None:
                self._dev.pop("quant", None)
            from repro.obs.registry import get_registry

            get_registry().gauge(
                "gate.quant_bytes", "int8 codebook resident bytes"
            ).set(quantlib.memory_bytes(self.quant))
        return self.quant

    def memory_bytes(self) -> Dict[str, int]:
        """Resident bytes per index component (host copies; the device
        mirrors in ``_dev`` are the same sizes).  ``quant`` appears once the
        codebook is built; ``total`` sums what a ``fused_q8`` deployment
        keeps in HBM (db stays resident for the exact rerank)."""
        out = {
            "db": int(self.db.nbytes),
            "neighbors": int(self.neighbors.nbytes),
            "nav_reps": int(np.asarray(self.nav.reps).nbytes),
            "nav_neighbors": int(np.asarray(self.nav.neighbors).nbytes),
        }
        if self.quant is not None:
            out["quant"] = quantlib.memory_bytes(self.quant)
        out["total"] = sum(out.values())
        return out

    def _search_kwargs(self, params: SearchParams) -> Dict:
        """Device operands ``batched_search`` needs for these params, derived
        deterministically so every call site (direct, routed, warmup) passes
        the same treedef per ``SearchParams`` value — the jit cache stays
        warm.  Cosine always gets the precomputed ``1/‖row‖`` cache
        (ISSUE 10 satellite: never renormalize rows per hop); ``fused_q8``
        gets the device codebook, quantizing on first use; real-TPU
        ``fused`` with ``d != 128`` gets the cached lane-row view the kernel
        DMAs from (``repro.kernels.gather_dist.lane_rows``) — building it
        inside the jitted search would re-materialize an O(N·d) copy per
        batch."""
        dev = self._device()
        kw: Dict = {}
        if params.metric == "cosine":
            if "inv_norms" not in dev:
                dev["inv_norms"] = 1.0 / jnp.maximum(
                    jnp.linalg.norm(
                        dev["db"].astype(jnp.float32), axis=-1
                    ),
                    1e-9,
                )
            kw["inv_norms"] = dev["inv_norms"]
        if params.kernel == "fused_q8":
            if "quant" not in dev:
                q = self.ensure_quantized()
                dev["quant"] = quantlib.QuantizedDb(
                    *(jnp.asarray(a) for a in q)
                )
            kw["quant"] = dev["quant"]
        if params.metric == "ip" and params.instrument:
            if "mips_m2" not in dev:
                db32 = dev["db"].astype(jnp.float32)
                dev["mips_m2"] = jnp.max(jnp.sum(db32 * db32, axis=-1))
            kw["mips_m2"] = dev["mips_m2"]
        if (params.kernel == "fused" and not params.kernel_interpret
                and dev["db"].shape[1] != 128):
            from repro.kernels.gather_dist import lane_rows
            from repro.kernels.ops import _on_tpu

            if _on_tpu():
                if "db_lane" not in dev:
                    dev["db_lane"] = lane_rows(dev["db"])
                kw["db_lane"] = dev["db_lane"]
        return kw

    def check_metric(self, params: SearchParams, where: str) -> None:
        """Refuse a search metric this index was not built for: an ``ip``
        index serves ``ip`` alone, an ``l2`` index ``l2`` and ``cosine``."""
        built = self.gcfg.metric
        if params.metric not in SERVES[built]:
            raise ValueError(
                f"{where}: an index built under metric={built!r} serves "
                f"{SERVES[built]}, not a search under metric="
                f"{params.metric!r}"
            )

    def _search_args(self, queries, entries, params: SearchParams):
        """``(args, kwargs)`` of the ``batched_search`` call ``search``
        makes; ``lower_search`` lowers the same call."""
        dev = self._device()
        args = (dev["db"], dev["neighbors"], jnp.asarray(queries), entries,
                params)
        return args, self._search_kwargs(params)

    def lower_search(self, queries: np.ndarray, *, params: SearchParams):
        """The program ``search`` runs for these queries and params,
        lowered (``jax.stages.Lowered``) and not run."""
        self.check_metric(params, "GateIndex.lower_search")
        args, kw = self._search_args(
            queries, self.select_entries(queries), params
        )
        return lower_batched_search(*args, **kw)

    def select_entries(self, queries: jax.Array, *, instrument: bool = False):
        """(B, probe_width) base-graph entry ids chosen by the model, from
        one jitted program (``gate_select_entries``).

        ``instrument=True`` additionally returns the per-query nav-graph
        descent length (zeros on the flat-score path, which takes no hops).
        """
        dev = self._device()
        nav = dev["nav"]
        entries, nav_hops = gate_select_entries(
            self.tower_params, queries, nav.reps, nav.neighbors,
            dev["hub_ids"], tower_cfg=self.tower_cfg, nav_start=nav.start,
            flat=self.hubs.n <= self.gcfg.flat_score_max,
            probe_width=self.gcfg.probe_width,
        )
        return (entries, nav_hops) if instrument else entries

    def route_signals(self, queries: jax.Array, *, with_features: bool = False):
        """Per-query entry ids + hardness, from signals GATE computes anyway.

        Returns ``(entries (B, w), nav_hops (B,), hardness (B,))``, higher
        hardness = harder.  With ``with_features=True``, additionally returns
        a ``(B, 3)`` float32 feature matrix ``[-s1, s2-s1, nav_hops]`` (see
        ``repro.feedback.fit.FEATURE_NAMES``) — the raw signals a learned
        hardness predictor scores instead of the hand-mixed formula;
        whichever path didn't run contributes zero columns.  Flat-score path: hardness combines the negated
        best two-tower score ``-s1`` (low affinity to *every* hub is the
        modality-gap / OOD tell) with the top-2 margin ``s2 − s1`` (an
        ambiguous entry choice marks a query likely to wander,
        arXiv:2402.04713): ``-s1 + 0.5·(s2 − s1)``.  The score term
        separates queries that actually need a bigger beam markedly better
        than the margin alone (AUC 0.70 vs 0.65 against a
        needs-wide-beam label on mixed in-dist/OOD traffic).  Nav-descent
        path: the descent length (long walks correlate with poor entries).
        The scale is irrelevant — the router thresholds on an empirical
        quantile of recent values.

        Entry ids are identical to ``select_entries`` (``lax.top_k`` and
        ``argmax`` share first-occurrence tie-breaking), which is what makes
        routed results bit-identical to unrouted ones at the same rung.
        """
        dev = self._device()
        z_q = query_tower(
            self.tower_params, self.tower_cfg,
            jnp.asarray(queries, jnp.float32),
        )
        w = self.gcfg.probe_width
        B = z_q.shape[0]
        if self.hubs.n <= self.gcfg.flat_score_max:
            from repro.kernels import ops

            scores = ops.twotower_score(z_q, dev["nav"].reps)
            m = min(max(w, 2), self.hubs.n)
            top_s, top_i = jax.lax.top_k(scores, m)
            hub_local = top_i[:, :w]
            if m >= 2:
                hardness = 0.5 * top_s[:, 1] - 1.5 * top_s[:, 0]
                margin = top_s[:, 1] - top_s[:, 0]
            else:  # single hub: no margin term, only the affinity tell
                hardness = -top_s[:, 0]
                margin = jnp.zeros((B,), jnp.float32)
            nav_hops = jnp.zeros((B,), jnp.int32)
            features = jnp.stack(
                [-top_s[:, 0], margin, jnp.zeros((B,), jnp.float32)], axis=1
            )
        else:
            hub_local, nav_hops = ng.descend(
                dev["nav"], z_q, probe_width=w, instrument=True
            )
            hardness = nav_hops.astype(jnp.float32)
            features = jnp.stack(
                [jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.float32),
                 nav_hops.astype(jnp.float32)], axis=1
            )
        if with_features:
            return dev["hub_ids"][hub_local], nav_hops, hardness, features
        return dev["hub_ids"][hub_local], nav_hops, hardness

    def warmup_ladder(
        self,
        ladder,
        *,
        batch_size: int,
        params: Optional[SearchParams] = None,
        **legacy,
    ) -> int:
        """Precompile one search program per ladder rung (ISSUE 7).

        Every ``SearchParams`` field is a static jit argument, so the
        adaptive controller's ladder moves would otherwise recompile on
        first use of each rung — at serving time, under traffic.  One dummy
        batch per rung here moves every compile to startup; afterwards
        adaptation is a jit cache lookup
        (``graphs.search.search_jit_cache_size()`` stays flat).

        ``params`` is the base config each rung is applied onto (defaults
        to ``SearchParams(instrument=True)`` — serving runs instrumented).
        Returns the number of rungs warmed.  ``batch_size`` must match the
        serving batch shape (shape changes also recompile).
        """
        base = resolve_search_params(
            "GateIndex.warmup_ladder", params, legacy,
            default=SearchParams(instrument=True),
        )
        self.check_metric(base, "GateIndex.warmup_ladder")
        d = self.db.shape[1]
        dummy = np.zeros((batch_size, d), self.db.dtype)
        with span("gate.warmup_ladder", rungs=len(ladder),
                  batch_size=batch_size):
            for rung in ladder:
                rp = rung.params(base)
                out = self.search(dummy, params=rp, telemetry_sink=None)
                res = out[0] if rp.instrument else out
                jax.block_until_ready(res.ids)
        return len(ladder)

    def warmup_router(
        self,
        router,
        *,
        params: Optional[SearchParams] = None,
    ) -> int:
        """Precompile every (rung, bucket) program the router can dispatch
        (ISSUE 8): both rungs at every static sub-batch size.  After this,
        ``search_routed`` never misses the jit cache regardless of how a
        batch splits.  Returns the number of programs warmed.
        """
        base = params if params is not None else SearchParams()
        rungs = (
            (router.easy_rung,)
            if router.easy_rung == router.hard_rung
            else (router.easy_rung, router.hard_rung)
        )
        d = self.db.shape[1]
        warmed = 0
        with span("gate.warmup_router", rungs=len(rungs),
                  buckets=len(router.buckets)):
            for rung in rungs:
                sp = router.rung_params(rung, base)
                for m in router.buckets:
                    dummy = np.zeros((m, d), self.db.dtype)
                    res, _ = self.search(dummy, params=sp,
                                         telemetry_sink=None)
                    jax.block_until_ready(res.ids)
                    warmed += 1
        return warmed

    def search(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        *,
        params: Optional[SearchParams] = None,
        telemetry_sink=_UNSET,
        **legacy,
    ):
        """GATE search at one ``SearchParams`` config (ISSUE 8 API).

        Returns ``SearchResult``; with ``params.instrument=True`` returns
        ``(SearchResult, SearchTelemetry)`` and hands the telemetry to
        ``telemetry_sink`` — default :func:`repro.obs.registry_sink`
        (registry ``search.*`` instruments + ring-overflow warning), or any
        callable ``sink(tele, *, params, where)``; ``telemetry_sink=None``
        skips the side effects (used by warmup — dummy batches must not
        pollute metrics — and by callers folding telemetry into their own
        window/registry).

        ``k=`` stays as a blessed shortcut overriding ``params.k``.  The
        pre-ISSUE-8 kwargs (``beam_width=``, ..., ``record=``) keep working
        through the one-shot deprecation shim (docs/api.md).
        """
        if "record" in legacy:
            record = legacy.pop("record")
            warn_deprecated_kwarg(
                "GateIndex.search", "record",
                "telemetry_sink=None (or leave the default registry sink)",
            )
            if telemetry_sink is not _UNSET:
                raise TypeError(
                    "pass either telemetry_sink= or the deprecated record=, "
                    "not both"
                )
            telemetry_sink = _UNSET if record else None
        params = resolve_search_params("GateIndex.search", params, legacy, k=k)
        self.check_metric(params, "GateIndex.search")
        sink = registry_sink if telemetry_sink is _UNSET else telemetry_sink
        with span("gate.select_entries"):
            entries, nav_hops = self.select_entries(queries, instrument=True)
        with span("gate.search.dispatch", queries=len(queries),
                  beam_width=params.beam_width):
            args, kw = self._search_args(queries, entries, params)
            out = batched_search(*args, **kw)
        if not params.instrument:
            return out
        res, tele = out
        tele = tele._replace(nav_hops=nav_hops)
        if sink is not None:
            # the sink reads the telemetry on the host, so it waits for the
            # device anyway; the wait gets a span of its own
            with span("gate.search.device_wait"):
                jax.block_until_ready((res, tele))
            with span("gate.search.telemetry") as ev:
                sink(tele, params=params, where="GateIndex.search")
                if ev is not None:
                    # free where the sink read the field to the host (the
                    # registry's sink does while the registry is enabled):
                    # the array keeps that copy; else this is the one read
                    conv = np.asarray(tele.converged_hop)
                    ev["converged_hop_sum"] = int(conv.sum())
                    ev["queries"] = int(conv.size)
        return res, tele

    def search_routed(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        *,
        router,
        params: Optional[SearchParams] = None,
        telemetry_sink=_UNSET,
    ):
        """Per-query hardness-routed search (ISSUE 8 tentpole).

        One entry-selection pass computes entries *and* hardness for the
        whole batch (``route_signals``); the router splits the batch, each
        sub-batch is padded to a precompiled bucket size and searched at its
        side's ladder rung, and results are scatter-merged back into the
        original query order (host arrays, bit-identical per query to an
        unrouted search at the same rung).

        Always instruments — per-rung telemetry is what the router learns
        from.  Returns ``(SearchResult, RouteReport)``; the report carries
        the merged telemetry, split indices/threshold and per-rung
        summaries, and has already been fed to ``router.observe`` (routed
        counters + per-rung windows).  Call ``router.step()`` once per batch
        to let the split fraction adapt.
        """
        from repro.obs.router import RouteReport

        base = resolve_search_params(
            "GateIndex.search_routed", params, {}, k=k
        )
        self.check_metric(base, "GateIndex.search_routed")
        sink = registry_sink if telemetry_sink is _UNSET else telemetry_sink
        dev = self._device()
        qd = jnp.asarray(queries)
        B = int(qd.shape[0])
        entries, nav_hops_d, hardness_d, features_d = self.route_signals(
            queries, with_features=True
        )
        nav_hops = np.asarray(nav_hops_d)
        hardness = np.asarray(hardness_d)
        features = np.asarray(features_d)
        easy_idx, hard_idx, thr = router.split(hardness, features=features)
        kk = base.k
        ids = np.full((B, kk), -1, np.int32)
        dists = np.full((B, kk), np.inf, np.float32)
        hops = np.zeros((B,), np.int32)
        evals = np.zeros((B,), np.int32)
        leaves = {
            f: np.zeros((B,), np.float32 if f in ("entry_dist",
                                                  "entry_rank_proxy",
                                                  "bytes_read")
               else np.int32)
            for f in SearchTelemetry._fields
        }
        summaries = {}
        padded = {}
        with span("gate.search_routed", queries=B,
                  easy=int(easy_idx.size), hard=int(hard_idx.size)):
            for side, idx, rung in (
                ("easy", easy_idx, router.easy_rung),
                ("hard", hard_idx, router.hard_rung),
            ):
                n = int(idx.size)
                if n == 0:
                    continue
                m = router.bucket(n)
                padded[side] = m
                take = idx if m == n else np.concatenate(
                    [idx, np.full(m - n, idx[0], idx.dtype)]
                )
                tj = jnp.asarray(take, jnp.int32)
                rp = router.rung_params(rung, base)
                sub_res, sub_tele = batched_search(
                    dev["db"], dev["neighbors"], qd[tj], entries[tj],
                    params=rp, **self._search_kwargs(rp),
                )
                # a rung narrower than k returns min(beam_width, k) columns;
                # the remaining merged columns keep the -1 / inf padding
                w = min(int(sub_res.ids.shape[1]), kk)
                ids[idx[:, None], np.arange(w)] = np.asarray(
                    sub_res.ids)[:n, :w]
                dists[idx[:, None], np.arange(w)] = np.asarray(
                    sub_res.dists)[:n, :w]
                hops[idx] = np.asarray(sub_res.hops)[:n]
                evals[idx] = np.asarray(sub_res.dist_evals)[:n]
                sub_t = jax.tree.map(lambda a: np.asarray(a)[:n], sub_tele)
                sub_t = sub_t._replace(nav_hops=nav_hops[idx])
                for f in SearchTelemetry._fields:
                    leaves[f][idx] = getattr(sub_t, f)
                summaries[side] = summarize(sub_t)
        tele = SearchTelemetry(**leaves)
        res = SearchResult(ids=ids, dists=dists, hops=hops, dist_evals=evals)
        report = RouteReport(
            telemetry=tele, easy_idx=easy_idx, hard_idx=hard_idx,
            threshold=thr, easy_rung=router.easy_rung,
            hard_rung=router.hard_rung,
            easy_summary=summaries.get("easy"),
            hard_summary=summaries.get("hard"),
            easy_padded=padded.get("easy", 0),
            hard_padded=padded.get("hard", 0),
            hardness=hardness,
            features=features,
            scores=getattr(router, "last_scores", None),
            predictor_version=getattr(router, "predictor_version", None),
            hard_frac=getattr(router, "hard_frac", None),
        )
        router.observe(report)
        if sink is not None:
            # extras (report/queries) reach only sinks that declare them —
            # narrow sink(tele, *, params, where) callables keep working
            call_telemetry_sink(
                sink, tele, params=base, where="GateIndex.search_routed",
                report=report, queries=queries,
            )
        return res, report

    def search_baseline(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        *,
        params: Optional[SearchParams] = None,
        entry: str = "medoid",
        telemetry_sink=_UNSET,
        **legacy,
    ):
        """Underlying-index search without GATE (entry ∈ {medoid, random});
        same ``SearchParams`` / ``telemetry_sink`` contract as ``search``
        (baseline telemetry lands under ``search_baseline.<entry>.*``)."""
        params = resolve_search_params(
            "GateIndex.search_baseline", params, legacy, k=k
        )
        self.check_metric(params, "GateIndex.search_baseline")
        dev = self._device()
        B = len(queries)
        if entry == "medoid":
            entries = jnp.full((B, 1), self.enter_id, jnp.int32)
        elif entry == "random":
            rng = np.random.default_rng(0)
            entries = jnp.asarray(
                rng.integers(0, len(self.db), (B, 1)), jnp.int32
            )
        else:
            raise ValueError(entry)
        out = batched_search(
            dev["db"], dev["neighbors"], jnp.asarray(queries), entries,
            params=params, **self._search_kwargs(params),
        )
        if params.instrument:
            res, tele = out
            if telemetry_sink is _UNSET:
                record_search_telemetry(
                    tele, prefix=f"search_baseline.{entry}"
                )
                warn_on_ring_overflow(
                    tele, params.visited_ring,
                    where=f"search_baseline({entry})",
                )
            elif telemetry_sink is not None:
                telemetry_sink(
                    tele, params=params, where=f"search_baseline({entry})"
                )
            return res, tele
        return out

    # ------------------------------------------------------------ persistence
    def save(self, path: str):
        state = {
            "db": self.db, "neighbors": self.neighbors,
            "enter_id": self.enter_id,
            "hubs": (self.hubs.ids, self.hubs.assign, self.hubs.centroids),
            "tower_params": jax.tree.map(np.asarray, self.tower_params),
            "tower_cfg": self.tower_cfg, "gcfg": self.gcfg,
            "nav": (self.nav.neighbors, self.nav.reps, self.nav.start),
            "build_report": self.build_report,
            "quant": tuple(self.quant) if self.quant is not None else None,
        }
        with open(path, "wb") as f:
            pickle.dump(state, f)

    @classmethod
    def load(cls, path: str) -> "GateIndex":
        with open(path, "rb") as f:
            s = pickle.load(f)
        q = s.get("quant")  # absent in pre-ISSUE-10 pickles
        return cls(
            db=s["db"], neighbors=s["neighbors"], enter_id=s["enter_id"],
            hubs=HubSet(*s["hubs"]),
            tower_params=s["tower_params"], tower_cfg=s["tower_cfg"],
            nav=ng.NavGraph(*s["nav"]), gcfg=s["gcfg"],
            build_report=s["build_report"],
            quant=quantlib.QuantizedDb(*q) if q is not None else None,
        )
