"""Explicit-DP trainer: the paper's 1-factor schedule on the gradient
all-reduce, with optional int8 gradient compression.

Unlike the pjit trainer (where GSPMD inserts the DP reduction), this
variant runs the whole step inside a manual ``shard_map`` over the dp
axes, so per-device gradients exist as values and the LACIN schedule is
applied *explicitly*: reduce-scatter + all-gather chains of
``ppermute`` matchings (wire-optimal 2(N-1)/N bytes, one hop per datum on
the CIN).  Used on host-device meshes in tests/benchmarks and as the
reference implementation of the paper's technique on the DP axis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.fabric import LacinCollectives
from repro.models import ModelConfig
from repro.models.layers import AxisRules
from repro.models.transformer import forward_train
from repro.optim import OptConfig, adamw_update


def _quantize_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def lacin_grad_allreduce(grads, axis_name: str, coll: LacinCollectives,
                         compress: bool = False):
    """All-reduce a gradient pytree over one manual axis with the LACIN
    schedule.  ``coll`` is the mesh-bound collective set — the axis size
    comes from its mesh (or the bound axis environment), never from a
    hand-threaded count.  ``compress=True`` quantizes the *scattered*
    shards to int8 before the all-gather phase (error <= 1/254 of max |g|
    per tensor), halving...quartering the AG wire bytes."""
    axis_size = coll.axis_size(axis_name)

    def reduce_leaf(g):
        shape, dtype = g.shape, g.dtype
        flat = g.reshape(-1).astype(jnp.float32)
        pad = (-flat.size) % axis_size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        chunks = flat.reshape(axis_size, -1)
        shard = coll.reduce_scatter(chunks, axis_name)
        if compress:
            q, scale = _quantize_int8(shard)
            qs = coll.all_gather(q, axis_name)
            ss = coll.all_gather(scale[None], axis_name)
            full = _dequantize(qs, ss[:, 0][:, None])
        else:
            full = coll.all_gather(shard, axis_name)
        flat = full.reshape(-1)
        if pad:
            flat = flat[:-pad]
        return (flat / axis_size).reshape(shape).astype(dtype)

    return jax.tree_util.tree_map(reduce_leaf, grads)


def make_manual_dp_train_step(cfg: ModelConfig, mesh, opt: OptConfig,
                              *, axis_name: str = "data",
                              compress: bool = False,
                              instance: str = "auto"):
    """Whole-step shard_map over one dp axis; params replicated."""
    coll = LacinCollectives(mesh=mesh, instance=instance)
    inner_rules = AxisRules()  # single-device math inside the manual region

    def body(state, batch):
        params = state["params"]
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: forward_train(p, batch, cfg, inner_rules),
            has_aux=True)(params)
        grads = lacin_grad_allreduce(grads, axis_name, coll,
                                     compress=compress)
        loss = jax.lax.pmean(loss, axis_name)
        new_params, new_opt, om = adamw_update(params, grads, state["opt"],
                                               opt)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, **om}

    state_specs = jax.tree_util.tree_map(lambda _: P(), {"params": 0,
                                                         "opt": 0,
                                                         "step": 0})
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), {"tokens": P(axis_name), "labels": P(axis_name)}),
        out_specs=(P(), P()),
        axis_names={axis_name}, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))
