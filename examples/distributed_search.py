"""Distributed (multi-device) GATE search: the production shard_map path on
fake host devices.

    PYTHONPATH=src python examples/distributed_search.py [--devices 8]

Row-shards the DB over a (data, model) mesh, builds a LOCAL subgraph per
partition, selects per-shard entries with the two-tower model, runs the
fixed-hop beam search under ``shard_map``, and merges per-shard top-k with
one all-gather — the identical program the multi-pod dry-run lowers for
512 chips.  Runtime: ~1 min.
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=64)
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}"
    )
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import build_sharded_gate, make_search_step
    from repro.core.twotower import TwoTowerConfig, init_params, query_tower
    from repro.data.synthetic import make_database, make_queries_in_dist
    from repro.graphs.knn import exact_knn, knn_graph, recall_at_k
    from repro.launch.mesh import make_host_mesh

    shape = (args.devices // 2, 2)
    mesh = make_host_mesh(shape, ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} over {mesh.size} devices")

    db, _ = make_database("sift10m-like", args.n, seed=0)
    tcfg = TwoTowerConfig(d_p=db.shape[1])
    params = init_params(tcfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    hub_ids = rng.choice(args.n, 16 * mesh.size, replace=False)
    hub_reps = np.asarray(
        query_tower(params, tcfg, jnp.asarray(db[hub_ids], jnp.float32))
    )
    print("building per-shard local subgraphs ...")
    sg = build_sharded_gate(
        mesh, db, (tcfg, params), hub_reps, hub_ids,
        lambda x, R: knn_graph(x, R), R=16,
    )
    step = jax.jit(make_search_step(mesh, tcfg, beam_width=32, max_hops=64,
                                    k=10))
    queries = make_queries_in_dist(db, args.queries, seed=5)
    with mesh:
        ids, dists, hops = step(sg, jnp.asarray(queries))
    true_ids, _ = exact_knn(queries, db, 10)
    rec = recall_at_k(np.asarray(ids), true_ids, 10)
    print(f"sharded recall@10 = {rec:.3f} over {mesh.size} partitions")
    print(f"per-query result ids[0] = {np.asarray(ids)[0]}")


if __name__ == "__main__":
    main()
