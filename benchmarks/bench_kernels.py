"""Kernel benchmarks: interpret-mode correctness sweep + CPU-path timing +
TPU roofline estimates per kernel (from tile shapes and the v5e model —
197 TFLOP/s bf16, 819 GB/s HBM).

It also covers the bandwidth-optimized search path (``gather_rows_dist``,
the in-kernel gather) plus an end-to-end xla/fused/fused_q8 serving gate
(imported from bench_qps); ``fused_q8`` scores with XLA, so it has no
micro section.  Their combined results are written to
``BENCH_kernels.json`` — the artifact CI uploads.  ``--smoke`` shrinks
every shape for the CI lane.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_json, save_kernels_json
from repro.kernels import ref
from repro.kernels.gather_dist import gather_dist, gather_rows_dist
from repro.kernels.l2dist import l2dist
from repro.kernels.topk import topk_min
from repro.kernels.twotower_score import twotower_score

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _time(fn, *args, repeats=5):
    out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    t0 = time.time()
    for _ in range(repeats):
        out = fn(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.time() - t0) / repeats


def run(mode: str = "quick", e2e: bool = True):
    rng = np.random.default_rng(0)
    results = {}
    small = mode in ("quick", "smoke")

    # l2dist: Q=1024 C=8192 d=128 (one beam-expansion batch at search scale)
    Q, C, D = (256, 2048, 128) if small else (1024, 8192, 128)
    q = jnp.asarray(rng.standard_normal((Q, D)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((C, D)).astype(np.float32))
    t_ref = _time(lambda a, b: ref.l2dist_ref(a, b), q, c)
    ok = np.allclose(
        l2dist(q[:64], c[:256], interpret=True),
        ref.l2dist_ref(q[:64], c[:256]), rtol=2e-5, atol=2e-4,
    )
    flops = 2.0 * Q * C * D
    bytes_ = 4.0 * (Q * D + C * D + Q * C)
    results["l2dist"] = {
        "interpret_ok": bool(ok),
        "cpu_ref_s": t_ref,
        "flops": flops,
        "bytes": bytes_,
        "tpu_compute_s": flops / PEAK_FLOPS,
        "tpu_memory_s": bytes_ / HBM_BW,
        "tpu_bound": "memory" if bytes_ / HBM_BW > flops / PEAK_FLOPS
        else "compute",
    }

    # topk over the merged candidate rows
    B, Cc, K = (256, 1024, 32)
    d = jnp.asarray(rng.standard_normal((B, Cc)).astype(np.float32))
    t_ref = _time(lambda x: ref.topk_min_ref(x, K), d)
    v_i, i_i = topk_min(d[:32], K, interpret=True)
    v_r, i_r = ref.topk_min_ref(d[:32], K)
    results["topk"] = {
        "interpret_ok": bool(
            np.allclose(v_i, v_r) and np.array_equal(i_i, i_r)
        ),
        "cpu_ref_s": t_ref,
        "bytes": 4.0 * B * Cc,
        "tpu_memory_s": 4.0 * B * Cc / HBM_BW,
        "tpu_bound": "memory",
    }

    # gather_dist at beam-search shapes
    Bb, R, Dd = 128, 32, 128
    vecs = jnp.asarray(rng.standard_normal((Bb, R, Dd)).astype(np.float32))
    qq = jnp.asarray(rng.standard_normal((Bb, Dd)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, 999, (Bb, R)).astype(np.int32))
    t_ref = _time(ref.gather_dist_ref, vecs, qq, ids)
    ok = np.allclose(
        gather_dist(vecs[:16], qq[:16], ids[:16], interpret=True),
        ref.gather_dist_ref(vecs[:16], qq[:16], ids[:16]),
        rtol=2e-5, atol=2e-4,
    )
    flops = 3.0 * Bb * R * Dd
    bytes_ = 4.0 * (Bb * R * Dd + Bb * Dd + Bb * R)
    results["gather_dist"] = {
        "interpret_ok": bool(ok),
        "cpu_ref_s": t_ref,
        "flops": flops, "bytes": bytes_,
        "tpu_compute_s": flops / PEAK_FLOPS,
        "tpu_memory_s": bytes_ / HBM_BW,
        "tpu_bound": "memory",
    }

    # in-kernel gather (ISSUE 10 tentpole): neighbor ids scalar-prefetched
    # into SMEM steer a per-row HBM→VMEM DMA; distances come out without the
    # XLA gather's round trip of the gathered block through HBM.
    N, R, Dg = (2048, 32, 128) if small else (8192, 32, 128)
    gdb = jnp.asarray(rng.standard_normal((N, Dg)).astype(np.float32))
    gq = jnp.asarray(rng.standard_normal((Dg,)).astype(np.float32))
    gids_np = rng.integers(0, N, R).astype(np.int32)
    gids_np[::7] = -1                   # invalid slots must mask to inf
    gids = jnp.asarray(gids_np)

    from repro.kernels.gather_dist import INF

    @jax.jit
    def xla_rows(ids, db, q):           # the matched off-TPU fallback
        v = db[jnp.maximum(ids, 0)].astype(jnp.float32)
        d = jnp.sum((v - q) ** 2, axis=-1)
        return jnp.where(ids >= 0, d, INF)

    t_ref = _time(xla_rows, gids, gdb, gq)
    got = np.asarray(gather_rows_dist(gids, gdb, gq, interpret=True))
    want = np.asarray(xla_rows(gids, gdb, gq))
    # bytes per hop (docs/kernels.md): xla round-trips the gathered (R,d)
    # block through HBM (read rows + write block + re-read block); fused
    # reads each row once.  + R*4 for the neighbor-id row either way.
    bytes_fused = 4.0 * R * Dg + 4.0 * R
    bytes_xla = 3 * 4.0 * R * Dg + 4.0 * R
    results["gather_rows_dist"] = {
        "interpret_ok": bool(np.array_equal(got, want)),  # bitwise, incl. inf
        "cpu_ref_s": t_ref,
        "flops": 3.0 * R * Dg,
        "bytes": bytes_fused,
        "bytes_xla_formulation": bytes_xla,
        "hbm_traffic_ratio_vs_xla": bytes_xla / bytes_fused,
        "tpu_memory_s": bytes_fused / HBM_BW,
        "tpu_bound": "memory",
    }

    # twotower_score at entry-selection shapes (B queries x 512 hubs)
    Bq, H, Do = 4096, 512, 128
    zq = jnp.asarray(rng.standard_normal((Bq, Do)).astype(np.float32))
    zh = jnp.asarray(rng.standard_normal((H, Do)).astype(np.float32))
    t_ref = _time(ref.twotower_score_ref, zq, zh)
    ok = np.allclose(
        twotower_score(zq[:64], zh[:64], interpret=True),
        ref.twotower_score_ref(zq[:64], zh[:64]), rtol=2e-5, atol=2e-5,
    )
    flops = 2.0 * Bq * H * Do
    bytes_ = 4.0 * (Bq * Do + H * Do + Bq * H)
    results["twotower_score"] = {
        "interpret_ok": bool(ok),
        "cpu_ref_s": t_ref,
        "flops": flops, "bytes": bytes_,
        "tpu_compute_s": flops / PEAK_FLOPS,
        "tpu_memory_s": bytes_ / HBM_BW,
        "tpu_bound": "memory" if bytes_ / HBM_BW > flops / PEAK_FLOPS
        else "compute",
    }

    for k, v in results.items():
        print(f"[bench_kernels] {k}: interpret_ok={v['interpret_ok']} "
              f"cpu_ref={v['cpu_ref_s'] * 1e3:.2f}ms "
              f"tpu_bound={v.get('tpu_bound')}")
    path = save_json("kernels", results)
    print(f"[bench_kernels] -> {path}")

    # BENCH_kernels.json: the ISSUE 10 acceptance artifact CI uploads —
    # micro sections for the new kernels + the end-to-end serving gate
    doc = {
        "benchmark": "kernels",
        "source": "bench_kernels",
        "mode": mode,
        "micro": {
            "gather_rows_dist": results["gather_rows_dist"],
        },
    }
    if e2e:
        from benchmarks.bench_qps import _kernels_headline, measure_kernels
        from benchmarks.common import load_workload

        if mode == "smoke":
            w = load_workload("sift10m-like", 1500, n_train_q=256,
                              n_eval_q=64, gate_kw={"epochs": 60})
            doc["e2e"] = measure_kernels(w, batch=32, rounds=4)
        else:
            w = load_workload("sift10m-like", 8000)
            doc["e2e"] = measure_kernels(w)
        print(f"[bench_kernels] e2e: {_kernels_headline(doc['e2e'])}")
    kpath = save_kernels_json(doc)
    print(f"[bench_kernels] -> {kpath}")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="quick",
                    choices=["smoke", "quick", "full"])
    ap.add_argument("--smoke", action="store_const", dest="mode",
                    const="smoke",
                    help="tiny shapes + small workload for the CI lane")
    ap.add_argument("--no-e2e", dest="e2e", action="store_false",
                    help="skip the end-to-end xla/fused/fused_q8 gate "
                         "(micro sections only)")
    args = ap.parse_args()
    run(args.mode, e2e=args.e2e)
