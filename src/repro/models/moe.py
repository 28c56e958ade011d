"""Token-choice top-k MoE with LACIN expert-parallel dispatch.

The expert-parallel (EP) path is the paper's technique made first-class:
expert shards live on the "model" mesh axis (a radix-16 XOR CIN in the
production HyperX, §5), and the dispatch/combine all-to-alls execute as a
LACIN 1-factor step schedule via the mesh-aware
``repro.fabric.LacinCollectives`` (shard count read from the mesh axis) —
every step a perfect matching, single-hop, contention-free.

Pipeline (per DP shard, fully inside a manual ``shard_map``):

  router top-k -> capacity-bucketed sort-based dispatch (E, C, d)
  -> reshape (n_shards, E_loc*C, d) -> LACIN all-to-all ("model")
  -> expert FFN, batched einsum over local experts
  -> LACIN all-to-all back -> gate-weighted combine (+ dropped-token zeros)

``moe_impl='dense'`` runs the same math without the a2a (single shard) —
used on 1-device smoke tests and as the no-EP baseline.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.fabric import LacinCollectives
from .layers import AxisRules, dense_init


def expert_store_count(cfg) -> int:
    """Experts as stored: padded to a multiple of ``expert_pad_to`` so the
    store shards evenly over the EP axis (granite: 40 -> 48)."""
    pad = max(cfg.expert_pad_to, 1)
    return -(-cfg.num_experts // pad) * pad


def init_moe(key, cfg, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    e = expert_store_count(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, cfg.num_experts), dtype),
        "wi": dense_init(ks[1], (e, d, f), dtype, fan_in=d),
        "wo": dense_init(ks[2], (e, f, d), dtype, fan_in=f),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        p["wg"] = dense_init(ks[3], (e, d, f), dtype, fan_in=d)
    return p


def _capacity(tokens: int, cfg) -> int:
    c = int(np.ceil(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor))
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def _dispatch_indices(eidx, num_experts: int, capacity: int):
    """Sort-based capacity bucketing.

    eidx: (N,) int32 expert choice per assignment.  Returns (slot (N,),
    valid (N,)): position ``e*C + rank`` for assignments that fit.
    """
    n = eidx.shape[0]
    sort_idx = jnp.argsort(eidx, stable=True)
    sorted_e = eidx[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(num_experts),
                                 side="left")
    ranks_sorted = jnp.arange(n) - seg_start[sorted_e]
    ranks = jnp.zeros((n,), jnp.int32).at[sort_idx].set(
        ranks_sorted.astype(jnp.int32))
    valid = ranks < capacity
    slot = jnp.where(valid, eidx * capacity + ranks, num_experts * capacity)
    return slot, valid


def _expert_ffn(p, x, cfg):
    """x: (E_loc, Cap, d) -> (E_loc, Cap, d), batched over local experts."""
    h = jnp.einsum("ecd,edf->ecf", x, p["wi"].astype(x.dtype))
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["wg"].astype(x.dtype))) * h
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["wg"].astype(x.dtype)),
                        approximate=True) * h
    elif cfg.mlp == "squared_relu":
        r = jax.nn.relu(h)
        h = r * r
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))


def _moe_local(p, x, cfg, coll: LacinCollectives | None,
               axis_name: str | None):
    """The per-device MoE body.  x: (Tloc, d) local tokens.

    ``coll`` is the mesh-bound LACIN collective set (None = dense / single
    shard); the EP shard count comes from the mesh axis it is bound to,
    so schedule and mesh can never disagree.

    ``p['wi']/['wo']/['wg']`` may be zero-padded along the expert dim so it
    divides ``n_shards`` (e.g. granite's 40 -> 48); the router only ever
    selects real experts, so padding buckets stay empty.
    """
    n_shards = coll.axis_size(axis_name) if coll is not None else 1
    t, d = x.shape
    k = cfg.top_k
    # Bucket count: local expert rows times shards (== padded global count).
    e = p["wi"].shape[0] * n_shards
    e_real = p["router"].shape[1]
    cap = _capacity(t, cfg)

    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)               # (T, E_real)
    gates, eidx = lax.top_k(probs, k)                     # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1).astype(jnp.int32)           # (N=T*k,)
    slot, valid = _dispatch_indices(flat_e, e, cap)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[slot].add(jnp.where(valid[:, None], x[tok_idx], 0))
    buf = buf[:-1]                                        # drop overflow row

    e_loc = e // n_shards
    if n_shards > 1:
        send = buf.reshape(n_shards, e_loc * cap, d)
        recv = coll.all_to_all(send, axis_name)
        # recv[j] = tokens from source shard j for MY local experts
        xin = (recv.reshape(n_shards, e_loc, cap, d)
                   .transpose(1, 0, 2, 3)
                   .reshape(e_loc, n_shards * cap, d))
    else:
        xin = buf.reshape(e_loc, cap, d)

    yout = _expert_ffn(p, xin, cfg)

    if n_shards > 1:
        back = (yout.reshape(e_loc, n_shards, cap, d)
                    .transpose(1, 0, 2, 3)
                    .reshape(n_shards, e_loc * cap, d))
        ret = coll.all_to_all(back, axis_name)
        out_buf = ret.reshape(e * cap, d)
    else:
        out_buf = yout.reshape(e * cap, d)

    picked = jnp.where(valid[:, None],
                       out_buf[jnp.clip(slot, 0, e * cap - 1)], 0)
    y = (picked.reshape(t, k, d) * gates[..., None].astype(x.dtype)).sum(axis=1)

    # Switch-style load-balance aux loss + router z-loss (local stats).
    me = jnp.mean(probs, axis=0)                          # (E_real,)
    ce = (jnp.zeros((e_real,), jnp.float32)
          .at[jnp.clip(flat_e, 0, e_real - 1)].add(1.0) / max(t * k, 1))
    aux = e_real * jnp.sum(me * ce)
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, aux, zloss


def apply_moe(p: dict, x, cfg, rules: AxisRules):
    """x: (B, T, d) -> (y, aux_metrics dict).

    EP path runs under a manual shard_map over (dp..., tp); dense path runs
    inline (single shard).
    """
    b, t, d = x.shape
    if cfg.moe_impl == "dense" or rules.tp is None or rules.tp_size == 1:
        y2, aux, z = _moe_local(p, x.reshape(b * t, d), cfg, None, None)
        return y2.reshape(b, t, d), {"moe_aux": aux, "moe_z": z}

    mesh = rules.mesh
    # EP shard count and schedule both come from the mesh axis (the
    # mesh-aware API): no hand-threaded axis_size to disagree with it.
    coll = LacinCollectives(mesh=mesh, instance="auto")
    n_shards = coll.axis_size(rules.tp)
    dp = rules.dp
    manual = set(dp) | {rules.tp}

    # The expert STORE is padded at init (expert_store_count); if it still
    # doesn't divide the EP axis (off-spec config), pad here as a fallback.
    e = p["wi"].shape[0]
    e_pad = -(-e // n_shards) * n_shards
    if e_pad != e:
        padw = [(0, e_pad - e), (0, 0), (0, 0)]
        p = dict(p, wi=jnp.pad(p["wi"], padw), wo=jnp.pad(p["wo"], padw),
                 **({"wg": jnp.pad(p["wg"], padw)} if "wg" in p else {}))

    def body(xl, router, wi, wo, *rest):
        pl = {"router": router, "wi": wi, "wo": wo}
        if rest:
            pl["wg"] = rest[0]
        bl, tl, dl = xl.shape
        y2, aux, z = _moe_local(pl, xl.reshape(bl * tl, dl), cfg, coll,
                                rules.tp)
        aux = lax.pmean(aux, dp) if dp else aux
        z = lax.pmean(z, dp) if dp else z
        return y2.reshape(bl, tl, dl), aux, z

    args = [p["router"], p["wi"], p["wo"]]
    in_specs = [P(dp if dp else None, None, None), P(), P(rules.tp), P(rules.tp)]
    if "wg" in p:
        args.append(p["wg"])
        in_specs.append(P(rules.tp))
    out_specs = (P(dp if dp else None, None, None), P(), P())
    fn = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, axis_names=manual,
                       check_vma=False)
    y, aux, z = fn(x, *args)
    return y, {"moe_aux": aux, "moe_z": z}
