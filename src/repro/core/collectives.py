"""LACIN-scheduled collectives: ppermute step chains over mesh axes.

These are the paper's 1-factor step schedules (§2, refs [8,9]) realized as
JAX collectives inside ``shard_map``.  Step ``i`` moves exactly the traffic
the port-``i`` 1-factor would carry on the physical CIN, so every step is a
perfect matching: contention-free by construction, with both endpoints of
every exchange using the same step index (the isoport property).

Wire-byte optimality (per device, shard bytes ``b = B/N``):

==================  ==========  =================
collective           steps       bytes on wire
==================  ==========  =================
all_to_all_lacin     N-1         (N-1) * b   (optimal)
all_gather_lacin     N-1         (N-1) * b   (optimal)
reduce_scatter       N-1         (N-1) * b   (optimal)
all_reduce           2(N-1)      2(N-1) * b  (optimal, RS+AG)
==================  ==========  =================

Unlike ring algorithms (same byte counts), every datum crosses exactly ONE
link — single-hop minimal routing on the CIN, the paper's diameter-1
advantage.  All functions must be called inside ``shard_map`` with
``axis_name`` bound.

``axis_size`` is optional: when omitted it is read statically from the
bound axis, so the schedule always matches the mesh.  The mesh-aware
front-end (``repro.fabric.LacinCollectives`` and the hierarchical
multi-axis / two-level schedules) builds on these single-axis chains.
"""
from __future__ import annotations

import warnings
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro._compat import LacinDeprecationWarning

from .schedule import LacinSchedule, make_schedule


def _resolve_axis_size(axis_name: str, axis_size: int | None) -> int:
    """``axis_size`` if given, else the static size of the bound axis."""
    if axis_size is None:
        return jax.lax.axis_size(axis_name)
    return int(axis_size)


def _partners_for(sched: LacinSchedule) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(steps, n) send-target and recv-source tables as device constants."""
    return (jnp.asarray(np.asarray(sched.table, dtype=np.int32)),
            jnp.asarray(np.asarray(sched.inv_table, dtype=np.int32)))


# ---------------------------------------------------------------------------
# all-to-all
# ---------------------------------------------------------------------------

def all_to_all_lacin(x: jax.Array, axis_name: str, *, axis_size: int | None = None,
                     instance: str = "auto") -> jax.Array:
    """Personalized all-to-all over ``axis_name``.

    ``x`` has leading dim ``axis_size``; ``x[j]`` is this device's chunk for
    device ``j``.  Returns ``out`` with ``out[j]`` = chunk from device ``j``
    for this device.  N-1 matching steps; step ``i`` exchanges with the
    1-factor-``i`` partner.
    """
    axis_size = _resolve_axis_size(axis_name, axis_size)
    sched = make_schedule(instance, axis_size)
    send_to, recv_from = _partners_for(sched)
    me = lax.axis_index(axis_name)
    out = jnp.zeros_like(x)
    own = jnp.take(x, me, axis=0)
    out = lax.dynamic_update_index_in_dim(out, own, me, axis=0)
    for step in range(sched.num_steps):
        perm = sched.perm(step)
        if not perm:
            continue
        target = send_to[step][me]
        source = recv_from[step][me]
        send = jnp.take(x, target, axis=0)           # my chunk for target
        recv = lax.ppermute(send, axis_name, perm)   # source's chunk for me
        # Idle device (odd-N circle): target == source == me; keep own chunk.
        recv = jnp.where(source == me, own, recv)
        out = lax.dynamic_update_index_in_dim(out, recv, source, axis=0)
    return out


# ---------------------------------------------------------------------------
# all-gather
# ---------------------------------------------------------------------------

@jax.named_scope("all_gather")
def all_gather_lacin(x: jax.Array, axis_name: str, *, axis_size: int | None = None,
                     instance: str = "auto", tiled: bool = False) -> jax.Array:
    """All-gather this device's shard across ``axis_name``.

    Every step sends the *original* shard to the step partner — on a CIN
    each shard travels exactly one hop to each consumer.  Returns shape
    ``(axis_size, *x.shape)`` or concatenated along axis 0 if ``tiled``.
    """
    axis_size = _resolve_axis_size(axis_name, axis_size)
    sched = make_schedule(instance, axis_size)
    _, recv_from = _partners_for(sched)
    me = lax.axis_index(axis_name)
    out = jnp.zeros((axis_size,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, me, axis=0)
    for step in range(sched.num_steps):
        perm = sched.perm(step)
        if not perm:
            continue
        source = recv_from[step][me]
        recv = lax.ppermute(x, axis_name, perm)      # source's original shard
        recv = jnp.where(source == me, x, recv)
        out = lax.dynamic_update_index_in_dim(out, recv, source, axis=0)
    if tiled:
        out = out.reshape((axis_size * x.shape[0],) + x.shape[1:])
    return out


# ---------------------------------------------------------------------------
# reduce-scatter
# ---------------------------------------------------------------------------

@jax.named_scope("reduce_scatter")
def reduce_scatter_lacin(x: jax.Array, axis_name: str, *, axis_size: int | None = None,
                         instance: str = "auto") -> jax.Array:
    """Reduce-scatter over ``axis_name``.

    ``x`` has leading dim ``axis_size``; ``x[j]`` is this device's
    contribution to device ``j``'s output shard.  Each step sends the
    partner its addend directly (one hop) and accumulates the received one.
    Returns the reduced shard ``sum_s x_s[me]`` of shape ``x.shape[1:]``.
    """
    axis_size = _resolve_axis_size(axis_name, axis_size)
    sched = make_schedule(instance, axis_size)
    send_to, recv_from = _partners_for(sched)
    me = lax.axis_index(axis_name)
    acc = jnp.take(x, me, axis=0)
    for step in range(sched.num_steps):
        perm = sched.perm(step)
        if not perm:
            continue
        target = send_to[step][me]
        source = recv_from[step][me]
        send = jnp.take(x, target, axis=0)           # my addend for target
        recv = lax.ppermute(send, axis_name, perm)   # source's addend for me
        recv = jnp.where(source == me, jnp.zeros_like(recv), recv)
        acc = acc + recv
    return acc


# ---------------------------------------------------------------------------
# all-reduce = reduce-scatter + all-gather
# ---------------------------------------------------------------------------

def all_reduce_lacin(x: jax.Array, axis_name: str, *, axis_size: int | None = None,
                     instance: str = "auto") -> jax.Array:
    """All-reduce (sum) of an arbitrary-shaped array over ``axis_name``.

    RS+AG decomposition over a flattened, padded view: 2(N-1) matching
    steps, wire-optimal 2(N-1)/N * bytes.
    """
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    n = _resolve_axis_size(axis_name, axis_size)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    shard = reduce_scatter_lacin(chunks, axis_name, axis_size=n, instance=instance)
    full = all_gather_lacin(shard, axis_name, axis_size=n, instance=instance)
    flat = full.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Deprecated shims (one release): superseded by the mesh-aware
# repro.fabric.LacinCollectives front-end.
# ---------------------------------------------------------------------------

def tree_all_reduce_lacin(tree, axis_name: str, *, axis_size: int | None = None,
                          instance: str = "auto"):
    """Deprecated: use ``repro.fabric.LacinCollectives(mesh).tree_all_reduce``."""
    warnings.warn(
        "tree_all_reduce_lacin is deprecated; use "
        "repro.fabric.LacinCollectives(mesh, instance=...).tree_all_reduce(tree, axis)",
        LacinDeprecationWarning, stacklevel=2)
    return jax.tree_util.tree_map(
        partial(all_reduce_lacin, axis_name=axis_name, axis_size=axis_size,
                instance=instance), tree)


def psum_or_lacin(x, axis_name: str, *, axis_size: int | None = None,
                  impl: str = "xla", instance: str = "auto"):
    """Deprecated: use ``repro.fabric.LacinCollectives(mesh, impl=...).psum``."""
    warnings.warn(
        "psum_or_lacin is deprecated; use "
        "repro.fabric.LacinCollectives(mesh, instance=..., impl=...).psum(x, axis)",
        LacinDeprecationWarning, stacklevel=2)
    if impl == "xla":
        return lax.psum(x, axis_name)
    return all_reduce_lacin(x, axis_name, axis_size=axis_size, instance=instance)
