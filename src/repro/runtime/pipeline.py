"""Pipeline parallelism (GPipe-style) over a mesh axis.

Each device on the ``pipe`` axis owns a contiguous stage of layers;
microbatches stream through ``n_micro + n_stages - 1`` ticks of a
``lax.scan`` whose carry is the activation entering the local stage, and
stage-to-stage transfer is a single ``ppermute`` shift per tick.  Because
``ppermute``/``scan``/``where`` are all linearizable, **the backward
pipeline falls out of autodiff**: the transpose of the forward shift is
the reverse shift, so the 1F1B-ish reverse schedule needs no hand-written
machinery.

On the paper's fabric the shift permutation is a subset of a 1-factor
(neighbour exchanges), i.e. contention-free by construction.

Scope: uniform single-run stacks (all-ATTN architectures).  Stage
parameters are taken as layer-slices of the replicated stacked params —
a real deployment would shard the stack along the pipe axis; the schedule
and its gradients are what this module demonstrates (tests assert
loss/grad equality with the sequential forward).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.models import ModelConfig
from repro.models.layers import AxisRules
from repro.models import layers as L
from repro.models.transformer import (_run_body, build_runs, cross_entropy)


def make_pipeline_loss_fn(cfg: ModelConfig, mesh, *, axis_name: str = "pipe",
                          n_micro: int = 2):
    """Returns ``loss_fn(params, batch) -> loss`` running the layer stack
    as a pipeline over ``axis_name`` (params replicated, batch replicated;
    output loss replicated)."""
    runs = build_runs(cfg)
    if len(runs) != 1:
        raise ValueError("pipeline demo supports uniform single-run stacks")
    run = runs[0]
    n_stages = mesh.shape[axis_name]
    if run.count % n_stages:
        raise ValueError(f"{run.count} layers must divide {n_stages} stages")
    per_stage = run.count // n_stages
    rules = AxisRules()   # single-device math inside the manual region

    def local(params, batch):
        s = lax.axis_index(axis_name)
        tokens, labels = batch["tokens"], batch["labels"]
        b, t = tokens.shape
        assert b % n_micro == 0
        x = L.embed_tokens(params["embed"], tokens, cfg, rules)
        micro = x.reshape(n_micro, b // n_micro, t, cfg.d_model)
        pos = jnp.arange(t, dtype=jnp.int32)

        # this stage's layer slice of the stacked run params
        stage_p = jax.tree_util.tree_map(
            lambda a: lax.dynamic_slice_in_dim(a, s * per_stage, per_stage,
                                               axis=0),
            params["stack"][0])
        windows = lax.dynamic_slice_in_dim(
            jnp.asarray(run.windows, jnp.int32), s * per_stage, per_stage)
        thetas = lax.dynamic_slice_in_dim(
            jnp.asarray(run.thetas, jnp.float32), s * per_stage, per_stage)
        body = _run_body(run, cfg, rules, q_pos=pos, kv_pos=pos,
                         causal=True, cross_src=None, mode="train")

        def stage_fn(xb):
            dummy_cache = jnp.zeros((per_stage,), jnp.float32)
            y, _ = lax.scan(body, xb, (stage_p, windows, thetas, dummy_cache))
            return y

        shift = [(i, i + 1) for i in range(n_stages - 1)]
        n_ticks = n_micro + n_stages - 1

        def tick(buf, tk):
            y = stage_fn(buf)
            nxt = lax.ppermute(y, axis_name, shift)
            feed = micro[jnp.clip(tk + 1, 0, n_micro - 1)]
            newbuf = jnp.where(s == 0, feed, nxt)
            return newbuf, y

        buf0 = jnp.where(s == 0, micro[0], jnp.zeros_like(micro[0]))
        _, ys = lax.scan(tick, buf0, jnp.arange(n_ticks))
        # last stage: outputs for microbatch m are at tick m + S - 1
        outs = lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, axis=0)
        h = outs.reshape(b, t, cfg.d_model)
        h = L.apply_norm(params["final_norm"], h)
        logits = L.logits_from_hidden(h, params["embed"],
                                      params.get("lm_head"), cfg, rules)
        loss, _ = cross_entropy(logits, labels)
        # only the last stage's loss is real; replicate it across the axis
        loss = lax.psum(jnp.where(s == n_stages - 1, loss, 0.0), axis_name)
        return loss

    fn = shard_map(local, mesh=mesh,
                       in_specs=(P(), {"tokens": P(), "labels": P()}),
                       out_specs=P(), axis_names={axis_name},
                       check_vma=False)
    return fn
