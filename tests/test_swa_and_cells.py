"""SWA rolling-buffer prefill→decode consistency + cell lowering on a tiny
mesh (the dry-run contract at test scale)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models.model import build_model
from tests._subproc import run_with_devices


def test_swa_prefill_rolls_window():
    """Prompt longer than the window: prefill returns a C=window ring whose
    decode continuation matches the full forward pass."""
    cfg = get_reduced("mixtral-8x22b").with_(remat=False)  # window=64
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    B, S = 2, 96  # S > window=64
    rng = np.random.default_rng(1)
    toks = rng.integers(2, cfg.vocab_size, (B, S + 1)).astype(np.int32)
    logits_p, cache = jax.jit(
        lambda p, b: model.prefill(p, b, capacity=S + 1)
    )(params, {"tokens": jnp.asarray(toks[:, :S])})
    assert cache["k"].shape[2] == cfg.window  # ring, not S
    logits_d, _ = jax.jit(model.decode)(
        params, jnp.asarray(toks[:, S : S + 1]), cache,
        jnp.full((B,), S, jnp.int32),
    )
    logits_f, _ = jax.jit(
        lambda p, b: model.prefill(p, b)
    )(params, {"tokens": jnp.asarray(toks)})
    np.testing.assert_allclose(
        np.asarray(logits_d), np.asarray(logits_f), rtol=3e-3, atol=3e-3
    )


def test_q_chunked_attention_matches_unchunked():
    from repro.models.common import blockwise_attention

    rng = np.random.default_rng(0)
    B, Sq, H, D = 2, 100, 4, 16
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, Sq, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, Sq, H, D)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    ref = blockwise_attention(q, k, v, pos, pos, chunk=32, q_chunk=None)
    out = blockwise_attention(q, k, v, pos, pos, chunk=32, q_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_cell_lowering_tiny_mesh(shape_name):
    """build_cell + lower + compile on a 2x2 host mesh with a reduced arch —
    the same assembly path the 256-chip dry-run uses."""
    run_with_devices(
        f"""
import jax
from repro.configs import get_reduced
from repro.configs.base import ShapeSpec
from repro.launch.cells import build_cell, lower_cell
from repro.launch.mesh import make_host_mesh
cfg = get_reduced("llama3-8b")
kind = "train" if "{shape_name}" == "train_4k" else "decode"
shape = ShapeSpec("{shape_name}", kind, 128, 8)
mesh = make_host_mesh((2, 2), ("data", "model"))
cell = build_cell(cfg, shape, mesh, num_microbatches=2)
with mesh:
    compiled = lower_cell(cell).compile()
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes >= 0
print("ok", mem.temp_size_in_bytes)
""",
        n_devices=4,
        timeout=600,
    )


def test_gate_cell_lowering_tiny_mesh():
    run_with_devices(
        """
import jax
import dataclasses
from repro.launch import gate_cell
from repro.launch.cells import lower_cell
from repro.launch.mesh import make_host_mesh
# shrink the registered shape so a 4-device host mesh compiles fast
gs = gate_cell.GATE_SHAPES["search_1b"]
gate_cell.GATE_SHAPES["tiny"] = dataclasses.replace(
    gs, name="tiny", n_total=4096, d=32, R=8, batch=16, beam_width=8,
    num_hops=8, k=4)
mesh = make_host_mesh((2, 2), ("data", "model"))
cell = gate_cell.build_gate_cell("tiny", mesh)
with mesh:
    compiled = lower_cell(cell).compile()
print("ok", compiled.memory_analysis().temp_size_in_bytes)
""",
        n_devices=4,
        timeout=600,
    )
