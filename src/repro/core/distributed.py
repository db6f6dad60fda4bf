"""Distributed GATE search: partitioned ANNS over the production mesh.

Layout (DiskANN-style partitioned index, TPU-native):
  * the vector DB is row-sharded into P partitions over ALL mesh axes
    (a flat "shards" view of the (data, model) / (pod, data, model) mesh);
    each device owns (N/P, d) vectors + its own (N/P, R) LOCAL subgraph
    (neighbor ids are shard-local — graphs never cross shards);
  * GATE hub representations are sharded with their partition: each shard
    selects its own entry point with one two-tower scores matmul (query
    tower output × local hub reps);
  * every query searches all partitions (vmapped fixed-hop beam search under
    ``shard_map``), then per-shard top-k candidates are merged with one
    ``all_gather`` (k·B ids+dists per shard — tiny) and a top-k over P·k.

This mirrors how a 1000+-node deployment serves ANNS: queries broadcast,
partitions search concurrently, results reduce.  The only cross-device
traffic is the final k-merge — collective bytes per query = P·k·8B.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.twotower import TwoTowerConfig, query_tower
from repro.graphs.search import beam_search_fixed, beam_search_single


class ShardedGate(NamedTuple):
    """Device arrays for the sharded index (all leaves already placed)."""

    db: jax.Array          # (N, d) row-sharded
    db_norms: jax.Array    # (N,) precomputed ‖v‖² fp32, row-sharded
    neighbors: jax.Array   # (N, R) row-sharded, shard-LOCAL ids
    hub_reps: jax.Array    # (n_hubs_total, d_out) row-sharded per partition
    hub_local_ids: jax.Array  # (n_hubs_total,) local entry id per hub
    tower_params: dict     # replicated
    offsets: jax.Array     # (P,) global row offset of each shard


def _shard_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def make_search_step(
    mesh: Mesh,
    tcfg: TwoTowerConfig,
    *,
    beam_width: int = 64,
    max_hops: int = 128,
    k: int = 10,
    visited_ring: int = 256,
    expand_width: int = 1,
):
    """Returns search_step(sharded_gate, queries) -> (ids, dists) global top-k.

    Fixed-hop beam search per shard (bounded loop → static HLO), one
    all_gather merge. jit/lower-able with ShapeDtypeStructs for the dry-run.
    Its kernel, ``beam_search_fixed``, scores squared L2 alone.
    """
    axes = _shard_axes(mesh)
    # ring only needs to hold every node this search can expand — sizing it
    # exactly removes dead membership-test traffic (§Perf G-P4)
    visited_ring = min(visited_ring, max(max_hops * expand_width, 8))

    def local_search(db_s, norms_s, nbr_s, hubs_s, hub_ids_s, params, offset,
                     queries):
        # entry selection: two-tower scores against LOCAL hubs (one matmul)
        z_q = query_tower(params, tcfg, queries.astype(jnp.float32))
        scores = z_q @ hubs_s.T             # (B, H_local)
        entry_local = hub_ids_s[jnp.argmax(scores, axis=1)]  # (B,)

        def one(q, e):
            # fixed-trip scan: lockstep batch serving (static latency + HLO)
            ids, d, hops = beam_search_fixed(
                db_s, nbr_s, q, e[None],
                beam_width=beam_width, num_hops=max_hops,
                visited_ring=visited_ring, expand_width=expand_width,
                db_norms=norms_s,
            )
            return ids[:k], d[:k], hops

        ids, dists, hops = jax.vmap(one)(queries, entry_local)
        ids = jnp.where(ids >= 0, ids + offset[0], -1)  # globalize
        # merge across shards: gather per-shard candidates, take global top-k
        all_ids = jax.lax.all_gather(ids, axes, tiled=False)     # (P,B,k)
        all_d = jax.lax.all_gather(dists, axes, tiled=False)
        Pn = all_ids.shape[0] if all_ids.ndim == 3 else 1
        all_ids = all_ids.reshape(-1, queries.shape[0], k)
        all_d = all_d.reshape(-1, queries.shape[0], k)
        merged_ids = jnp.swapaxes(all_ids, 0, 1).reshape(queries.shape[0], -1)
        merged_d = jnp.swapaxes(all_d, 0, 1).reshape(queries.shape[0], -1)
        neg_top, top_i = jax.lax.top_k(-merged_d, k)
        out_ids = jnp.take_along_axis(merged_ids, top_i, axis=1)
        return out_ids, -neg_top, hops

    shard = P(axes if len(axes) > 1 else axes[0])
    rep = P()

    search = jax.shard_map(
        local_search,
        mesh=mesh,
        in_specs=(shard, shard, shard, shard, shard, rep, shard, rep),
        out_specs=(rep, rep, shard),
        check_vma=False,
    )

    def search_step(sg: ShardedGate, queries: jax.Array):
        return search(
            sg.db, sg.db_norms, sg.neighbors, sg.hub_reps, sg.hub_local_ids,
            sg.tower_params, sg.offsets, queries,
        )

    return search_step


def sharded_gate_specs(
    mesh: Mesh,
    tcfg: TwoTowerConfig,
    *,
    n_total: int,
    d: int,
    R: int = 32,
    hubs_per_shard: int = 64,
    dtype=jnp.bfloat16,
) -> ShardedGate:
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    from repro.core.twotower import init_params

    Pn = mesh.size
    n_hubs = hubs_per_shard * Pn
    params = jax.eval_shape(
        lambda: init_params(tcfg, jax.random.PRNGKey(0))
    )
    return ShardedGate(
        db=jax.ShapeDtypeStruct((n_total, d), dtype),
        db_norms=jax.ShapeDtypeStruct((n_total,), jnp.float32),
        neighbors=jax.ShapeDtypeStruct((n_total, R), jnp.int32),
        hub_reps=jax.ShapeDtypeStruct((n_hubs, tcfg.d_out), jnp.float32),
        hub_local_ids=jax.ShapeDtypeStruct((n_hubs,), jnp.int32),
        tower_params=params,
        offsets=jax.ShapeDtypeStruct((Pn,), jnp.int32),
    )


def gate_shardings(mesh: Mesh) -> ShardedGate:
    axes = _shard_axes(mesh)
    row = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
    rep = NamedSharding(mesh, P())
    return ShardedGate(
        db=row, db_norms=row, neighbors=row, hub_reps=row, hub_local_ids=row,
        tower_params=rep, offsets=row,
    )


# --------------------------------------------------------------------- host
def build_sharded_gate(
    mesh: Mesh,
    db: np.ndarray,
    tcfg_and_params: Tuple[TwoTowerConfig, dict],
    hub_reps: np.ndarray,
    hub_global_ids: np.ndarray,
    neighbors_builder,
    *,
    R: int = 16,
) -> ShardedGate:
    """Concrete small-scale sharded index (tests/examples): partition rows
    contiguously, build a LOCAL subgraph per shard via ``neighbors_builder``
    (e.g. knn_graph), spread hubs round-robin to their owning shard."""
    tcfg, params = tcfg_and_params
    Pn = mesh.size
    n = len(db) // Pn * Pn
    db = db[:n]
    per = n // Pn
    nbrs = np.zeros((n, R), np.int32)
    offsets = np.arange(Pn, dtype=np.int32) * per
    hub_reps_s = []
    hub_loc_s = []
    per_hub = None
    for p in range(Pn):
        lo, hi = p * per, (p + 1) * per
        nbrs[lo:hi] = neighbors_builder(db[lo:hi], R)
        mine = (hub_global_ids >= lo) & (hub_global_ids < hi)
        reps_p, loc_p = hub_reps[mine], hub_global_ids[mine] - lo
        if per_hub is None:
            per_hub = max(1, int(mine.sum()))
        # pad/truncate to a uniform per-shard hub count (shard_map needs
        # equal shapes); pad with the first local hub
        if len(loc_p) == 0:
            reps_p = np.zeros((per_hub, hub_reps.shape[1]), np.float32)
            loc_p = np.zeros((per_hub,), np.int64)
        while len(loc_p) < per_hub:
            reps_p = np.concatenate([reps_p, reps_p[:1]])
            loc_p = np.concatenate([loc_p, loc_p[:1]])
        hub_reps_s.append(reps_p[:per_hub])
        hub_loc_s.append(loc_p[:per_hub])

    sh = gate_shardings(mesh)
    put = lambda x, s: jax.device_put(x, s)
    norms = np.sum(db.astype(np.float32) ** 2, axis=1)
    return ShardedGate(
        db=put(jnp.asarray(db), sh.db),
        db_norms=put(jnp.asarray(norms, jnp.float32), sh.db_norms),
        neighbors=put(jnp.asarray(nbrs), sh.neighbors),
        hub_reps=put(jnp.asarray(np.concatenate(hub_reps_s), jnp.float32),
                     sh.hub_reps),
        hub_local_ids=put(
            jnp.asarray(np.concatenate(hub_loc_s), jnp.int32),
            sh.hub_local_ids),
        tower_params=put(jax.tree.map(jnp.asarray, params), sh.tower_params),
        offsets=put(jnp.asarray(offsets), sh.offsets),
    )
