"""Beam-search hop probe: does a query's search depend on its batch?

    python -m benchmarks.probe_hops                    # 200,000 x 128 L2
    JAX_PLATFORMS=cpu python -m benchmarks.probe_hops --n 20000

Builds the NSG graph of ``chip_smoke.py``'s configuration (``sift10m-like``
data from ``--seed``, the benchmark's ``NSG_KW``) and searches the first B
of its 2,048 eval queries from the medoid entry, at each rung in ``RUNGS``
and each batch size in ``BATCHES``.  One line per (rung, B): mean and max
hops, the share of queries at the hop cap, recall@10 against ``exact_knn``,
and the shares of id slots and hop counts equal to the largest batch's on
the same rows.  A query's result must not depend on the batch it rides in,
and at these rungs a search ends well before its cap, so the probe exits 1
when ids agree on less than ``MIN_ID_MATCH`` of slots or more than
``MAX_AT_CAP`` of queries reach the cap.  The last line is a JSON summary.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax.numpy as jnp
import numpy as np

from benchmarks.common import NSG_KW
from repro.data.synthetic import make_database, train_eval_query_split
from repro.graphs.knn import exact_knn, recall_at_k
from repro.graphs.nsg import build_nsg
from repro.graphs.params import SearchParams
from repro.graphs.search import batched_search

RUNGS = ((64, 256), (128, 512))      # (beam width, hop cap)
BATCHES = (2048, 1024, 256, 64)      # largest first: the others compare to it
MIN_ID_MATCH = 0.99
MAX_AT_CAP = 0.01
K = 10


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200_000,
                    help="database rows (d = 128)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    db, _ = make_database("sift10m-like", args.n, seed=args.seed)
    _, eq = train_eval_query_split(db, 768, BATCHES[0], seed=args.seed + 1)
    nsg = build_nsg(db, **NSG_KW)
    truth, _ = exact_knn(eq, db, K)
    dbj, nbrs = jnp.asarray(db), jnp.asarray(nsg.neighbors)

    rows, ok = [], True
    for beam, cap in RUNGS:
        sp = SearchParams(k=K, beam_width=beam, max_hops=cap)
        ref = None
        for b in BATCHES:
            entries = jnp.full((b, 1), nsg.enter_id, jnp.int32)
            res = batched_search(dbj, nbrs, jnp.asarray(eq[:b]), entries, sp)
            ids, hops = np.asarray(res.ids), np.asarray(res.hops)
            if ref is None:
                ref = (ids, hops)
            row = {"beam": beam, "cap": cap, "batch": b,
                   "hops_mean": float(hops.mean()), "hops_max": int(hops.max()),
                   "at_cap": float(np.mean(hops >= cap)),
                   "recall": float(recall_at_k(ids, truth[:b], K)),
                   "ids_equal": float(np.mean(ids == ref[0][:b])),
                   "hops_equal": float(np.mean(hops == ref[1][:b]))}
            rows.append(row)
            print(f"probe_hops: beam {beam} cap {cap} batch {b}: hops mean "
                  f"{row['hops_mean']:.2f} max {row['hops_max']} at-cap "
                  f"{row['at_cap']:.4f} recall@{K} {row['recall']:.4f}; vs "
                  f"batch {BATCHES[0]} on the same rows: ids equal "
                  f"{row['ids_equal']:.4f} hops equal "
                  f"{row['hops_equal']:.4f}", flush=True)
            ok &= row["ids_equal"] >= MIN_ID_MATCH
            ok &= row["at_cap"] <= MAX_AT_CAP
    print(json.dumps({"ok": bool(ok), "n": args.n, "rows": rows}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
