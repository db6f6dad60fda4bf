"""Train-step factory: microbatched grad accumulation + optimizer update.

``num_microbatches > 1`` reshapes every batch leaf to (M, B/M, ...) and scans,
accumulating fp32 grads — the standard memory lever for the big train cells
(activation footprint scales with the microbatch, not the global batch).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import NULL_CTX, ShardingCtx
from repro.obs import LATENCY_BUCKETS, get_registry, get_tracer
from repro.train.optim import Optimizer


def make_train_state(model, optim: Optimizer, key) -> Dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt": optim.init(params)}


def train_state_specs(model, optim: Optimizer) -> Dict[str, Any]:
    """ShapeDtypeStructs for the train state (dry-run: no allocation)."""
    p = model.param_specs()
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return {
        "params": p,
        "opt": {
            "m": {k: f32(v) for k, v in p.items()},
            "v": {k: f32(v) for k, v in p.items()},
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        },
    }


def make_train_step(
    model,
    optim: Optimizer,
    *,
    num_microbatches: int = 1,
    ctx: ShardingCtx = NULL_CTX,
    grad_transform: Optional[Callable] = None,
):
    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, ctx)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if num_microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            return loss, metrics, grads

        def split(x):
            m = num_microbatches
            return x.reshape((m, x.shape[0] // m) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(acc, mb):
            loss_a, grads_a = acc
            (loss, _metrics), grads = grad_fn(params, mb)
            grads_a = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_a, grads
            )
            return (loss_a + loss, grads_a), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro
        )
        inv = 1.0 / num_microbatches
        grads = jax.tree.map(lambda g: g * inv, grads)
        loss = loss_sum * inv
        return loss, {"ce": loss, "aux": jnp.zeros((), jnp.float32)}, grads

    def train_step(state, batch):
        params = state["params"]
        loss, metrics, grads = compute_grads(params, batch)
        if grad_transform is not None:
            grads = grad_transform(grads)
        new_params, new_opt, gnorm = optim.apply(params, grads, state["opt"])
        out_metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": gnorm.astype(jnp.float32),
            **{k: v.astype(jnp.float32) for k, v in metrics.items()},
        }
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step


def instrument_step(step_fn, *, name: str = "train.step"):
    """Wrap a (possibly jitted) train step with a host-side span + registry
    metrics (step latency histogram, steps counter, loss/grad-norm gauges).

    The span/timing forces a sync on the returned metrics — which every
    driver fetches right after anyway — so the measured duration is the real
    device step, not dispatch time.  With both the tracer and the registry
    disabled the wrapper adds one branch per step.
    """
    tracer = get_tracer()

    def wrapped(state, batch):
        reg = get_registry()
        if not (tracer.enabled or reg.enabled):
            return step_fn(state, batch)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        if tracer.enabled:
            tracer.complete_event(name, t0, t0 + dt)
        if reg.enabled:
            reg.counter("train.steps", "optimizer steps").inc()
            reg.histogram(
                "train.step_seconds", "train step latency", LATENCY_BUCKETS
            ).observe(dt)
            if "loss" in metrics:
                reg.gauge("train.loss", "last step loss").set(
                    float(metrics["loss"])
                )
            if "grad_norm" in metrics:
                reg.gauge("train.grad_norm", "last step grad norm").set(
                    float(metrics["grad_norm"])
                )
        return state, metrics

    return wrapped
