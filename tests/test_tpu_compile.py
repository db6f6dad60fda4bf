"""Compile the served path's kernels for a described TPU v5e chip.

Interpret mode (the rest of the suite) accepts block shapes and DMAs that
Mosaic refuses; these tests lower and compile for a ``v5e:2x2`` topology
that is described, not attached, so they run on a CPU-only host in a couple
of seconds each.  Nothing is executed.  The topology is described inside a
fixture — never at import time — because only one process may load the TPU
library and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.graphs.params import SearchParams
from repro.graphs.search import _batched_search
from repro.kernels.gather_dist import gather_rows_dist
from repro.kernels.twotower_score import twotower_score


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the trace-time platform check to the chip being compiled for
    (the host still reports CPU)."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("d", [128, 960])
def test_gather_rows_dist_compiles(one_chip, d, metric):
    n, r = 100_000, 32
    k = -(-d // 128)
    args = [
        _sds((r,), jnp.int32, one_chip),
        _sds((n * k, 128), jnp.float32, one_chip),   # lane_rows(db) view
        _sds((k * 128,), jnp.float32, one_chip),
    ]
    if metric == "cosine":
        args.append(_sds((n,), jnp.float32, one_chip))
    compiled = jax.jit(gather_rows_dist).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_twotower_score_compiles(one_chip):
    compiled = jax.jit(twotower_score).lower(
        _sds((64, 128), jnp.float32, one_chip),
        _sds((64, 128), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("flat", [True, False])
def test_select_entries_compiles_at_serving_batch(one_chip, on_tpu, flat):
    """Entry selection at the served batch (1,024 queries, d = 128, 64
    hubs) is one program; on the flat path it runs the Pallas
    ``twotower_score``."""
    from repro.core.gate_index import gate_select_entries
    from repro.core.twotower import TwoTowerConfig, init_params

    cfg = TwoTowerConfig(d_p=128)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    lowered = gate_select_entries.lower(
        params, _sds((1024, 128), jnp.float32, one_chip),
        _sds((64, cfg.d_out), jnp.float32, one_chip),
        _sds((64, 8), jnp.int32, one_chip),
        _sds((64,), jnp.int32, one_chip),
        tower_cfg=cfg, nav_start=0, flat=flat, probe_width=1)
    assert "module @jit_gate_select_entries" in lowered.as_text()
    compiled = lowered.compile()
    assert ("tpu_custom_call" in compiled.as_text()) == flat


def _search_args(one_chip, n, d, b=64, r=32):
    return (
        _sds((n, d), jnp.float32, one_chip),
        _sds((n, r), jnp.int32, one_chip),
        _sds((b, d), jnp.float32, one_chip),
        _sds((b, 1), jnp.int32, one_chip),
    )


def test_batched_search_fused_compiles_at_1m(one_chip, on_tpu):
    """The served search program at N = 1M, d = 128 runs the Pallas gather
    (no XLA fallback) and fits the chip."""
    params = SearchParams(k=10, beam_width=64, max_hops=256,
                          instrument=True, kernel="fused")
    compiled = _batched_search.lower(
        *_search_args(one_chip, 1_000_000, 128), params=params
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_batched_search_fused_wide_reads_lane_rows(one_chip, on_tpu):
    """d = 960 reads the precomputed lane-row view: the program holds no
    padded copy of the database."""
    n, d = 100_000, 960
    params = SearchParams(k=10, beam_width=64, max_hops=64, kernel="fused")
    args = _search_args(one_chip, n, d)
    lane = _sds((n * 8, 128), jnp.float32, one_chip)
    compiled = _batched_search.lower(
        *args, None, None, lane, params=params
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < n * 1024 * 4


@pytest.mark.parametrize("d", [128, 200])
def test_batched_search_q8_compiles(one_chip, on_tpu, d):
    """fused_q8 scores its codebook with XLA on the chip too."""
    from repro.quant import BLOCK, QuantizedDb

    n = 100_000
    nb = -(-d // BLOCK)
    quant = QuantizedDb(
        _sds((n, nb * BLOCK), jnp.int8, one_chip),
        _sds((n, nb), jnp.float32, one_chip),
        _sds((n, nb), jnp.float32, one_chip),
        _sds((n,), jnp.float32, one_chip),
    )
    params = SearchParams(k=10, beam_width=64, max_hops=64,
                          kernel="fused_q8")
    compiled = _batched_search.lower(
        *_search_args(one_chip, n, d), None, quant, params=params
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("beam", [64, 128])
def test_batched_search_loop_has_no_scatter(one_chip, beam):
    """At a batch of 2,048 the compiler flattens a vmapped scatter.  The
    beam-64 search compiled that way ran nearly every query to its hop cap
    on a v5e.  The loop writes its carries with selects."""
    params = SearchParams(k=10, beam_width=beam, max_hops=4 * beam)
    compiled = _batched_search.lower(
        *_search_args(one_chip, 100_000, 128, b=2048), params=params
    ).compile()
    assert " scatter(" not in compiled.as_text()


def test_batched_search_merge_has_no_element_gather(one_chip, on_tpu):
    """The served program (B = 1,024, beam 128, R = 38) merges each hop with
    one sort that carries its operands.  Applying an argsort instead puts
    one-element gathers over all B·(L+R) merge slots in the loop, which cost
    the hop loop most of its time on a v5e."""
    b, beam, r = 1024, 128, 38
    params = SearchParams(k=10, beam_width=beam, max_hops=4 * beam,
                          instrument=True, kernel="xla")
    text = _batched_search.lower(
        *_search_args(one_chip, 1_000_000, 128, b=b, r=r), params=params
    ).compile().as_text()
    shape = rf"\[({b},{beam + r}|{b * (beam + r)})\]"
    assert re.search(r"\) sort\(", text)
    assert not re.search(rf"= \w+{shape}\S* gather\(", text)


def test_nsg_prune_has_no_scatter(one_chip):
    """The NSG prune's suppression update runs at the build's batch of
    1,024 rows; it too is a select, not a batched scatter."""
    from repro.graphs.nsg import _mrng_prune_batch

    b, p, d, r = 1024, 96, 128, 32
    compiled = jax.jit(_mrng_prune_batch, static_argnums=(3,)).lower(
        _sds((b, d), jnp.float32, one_chip),
        _sds((b, p), jnp.int32, one_chip),
        _sds((b, p, d), jnp.float32, one_chip),
        r,
    ).compile()
    assert " scatter(" not in compiled.as_text()


def test_batched_search_ip_compiles_at_1m(one_chip, on_tpu):
    """The served ``ip`` program at N = 1M, d = 200 (B = 1,024, beam 128,
    instrumented, with ``M²``) fits the chip and scores ``-q·x`` as a
    float32 multiply and sum: no dot, which would run float32 in bfloat16
    passes at the default precision."""
    b, beam, r = 1024, 128, 38
    params = SearchParams(k=10, beam_width=beam, max_hops=4 * beam,
                          instrument=True, metric="ip")
    compiled = _batched_search.lower(
        *_search_args(one_chip, 1_000_000, 200, b=b, r=r), None, None, None,
        _sds((), jnp.float32, one_chip), params=params
    ).compile()
    text = compiled.as_text()
    assert not re.search(r" (dot|convolution)\(", text)
    assert re.search(rf"f32\[{b},{r}\]\S* reduce\(%mul", text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
