"""Pallas TPU flash attention (causal / sliding-window GQA).

TARGET: TPU MXU/VMEM.  Grid = (batch*q_heads, T/block_q, S/block_k) with
``dimension_semantics=("parallel", "parallel", "arbitrary")``: the KV axis
is the innermost sequential dimension, and the running (max, sum, acc)
online-softmax state lives in VMEM scratch that persists across KV steps —
the classic FlashAttention-2 schedule adapted to the TPU memory hierarchy
(HBM -> VMEM block DMA via BlockSpec, fp32 accumulation in VREGs, MXU
matmuls on (block_q x d) x (d x block_k) tiles with d padded to 128).

Numerics contract (must match ``ref.reference_attention``):
* logits scaled by 1/sqrt(d), fp32 softmax, output cast back to q.dtype;
* causal masking by absolute positions (q_pos, kv_pos);
* optional sliding window: key visible iff 0 <= q_pos - kv_pos < window;
* fully-masked rows produce zeros (guarded 1/l).

Validated on CPU with ``interpret=True`` (the kernel body executes in
Python) across the shape/dtype sweep in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
            window: int, block_q: int, block_k: int, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    qp = qpos_ref[...]                                   # (bq, 1)
    kp = kpos_ref[...]                                   # (1, bk)
    ok = kp >= 0                                         # padded kv slots < 0
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= (qp - kp) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    p = jnp.where(ok, p, 0.0)  # fully-masked rows: m_new == NEG_INF would
    #                            make exp(s - m_new) == 1, not 0 — mask again.
    l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.astype(o_ref.dtype)


def flash_attention(q, k, v, *, q_pos=None, kv_pos=None, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, T, H, D); k/v: (B, S, KV, D).  Returns (B, T, H, D).

    Compiles for the TPU; ``interpret=True`` runs the identical kernel
    body in Python on any backend, for validation.
    """
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if q_pos is None:
        q_pos = jnp.arange(t, dtype=jnp.int32)
    if kv_pos is None:
        kv_pos = jnp.arange(s, dtype=jnp.int32)

    block_q = min(block_q, max(t, 8))
    block_k = min(block_k, max(s, 8))
    pad_t = (-t) % block_q
    pad_s = (-s) % block_k
    qq = jnp.moveaxis(q, 2, 1).reshape(b * h, t, d)       # (BH, T, D)
    kk = jnp.moveaxis(k, 2, 1).reshape(b * kvh, s, d)
    vv = jnp.moveaxis(v, 2, 1).reshape(b * kvh, s, d)
    if pad_t:
        qq = jnp.pad(qq, ((0, 0), (0, pad_t), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_t))
    if pad_s:
        kk = jnp.pad(kk, ((0, 0), (0, pad_s), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, pad_s), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad_s), constant_values=-1)
    tp, sp = t + pad_t, s + pad_s
    n_q, n_k = tp // block_q, sp // block_k
    grid = (b * h, n_q, n_k)

    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(d), causal=causal, window=int(window),
        block_q=block_q, block_k=block_k, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda bh, qi, ki: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda bh, qi, ki: (0, ki)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g=g, kvh=kvh:
                         ((bh // (g * kvh)) * kvh + (bh % (g * kvh)) // g,
                          ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g=g, kvh=kvh:
                         ((bh // (g * kvh)) * kvh + (bh % (g * kvh)) // g,
                          ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos.astype(jnp.int32)[:, None], kv_pos.astype(jnp.int32)[None, :],
      qq, kk, vv)

    out = out[:, :t].reshape(b, h, t, d)
    return jnp.moveaxis(out, 1, 2)
