"""Pallas TPU chunkwise mLSTM (xLSTM matrix-memory cell).

TARGET: TPU.  Grid = (batch*heads, n_chunks) with
``dimension_semantics=("parallel", "arbitrary")``: the chunk axis is
sequential and the recurrent state (C: dk x dv matrix memory, n: dk
normalizer, m: scalar stabilizer) lives in VMEM scratch carried across
chunk steps — the HBM<->VMEM traffic per chunk is just the (C, d) q/k/v
tiles, and the state never leaves VMEM (the TPU-native answer to the
paper-adjacent GPU recurrence kernels: block the *time* axis, persist the
state in on-chip memory).

Semantics are exactly :func:`repro.models.xlstm.mlstm_sequential`
(stabilized exponential gating); equivalence is asserted in
tests/test_kernels.py over shape sweeps.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, h_ref,
            c_scr, n_scr, m_scr, *, chunk: int, dk: int, dv: int,
            scale: float):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, -1e30)

    q = q_ref[0].astype(jnp.float32) * scale              # (C, dk)
    k = k_ref[0].astype(jnp.float32)                      # (C, dk)
    v = v_ref[0].astype(jnp.float32)                      # (C, dv)
    li = li_ref[0].astype(jnp.float32)                    # (1, C)
    lf = lf_ref[0].astype(jnp.float32)                    # (1, C)
    m0 = m_scr[0, 0]

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = cols <= rows

    # Mosaic has no cumsum and no 1-D transposes: prefix sums and the
    # column forms of the gates are masked reductions over (C, C) tiles.
    def col(row):                                          # (1, C) -> (C, 1)
        return jnp.sum(jnp.where(rows == cols, row, 0.0), axis=1,
                       keepdims=True)

    li_c = col(li)
    bcum_c = jnp.sum(jnp.where(tri, lf, 0.0), axis=1, keepdims=True)
    bcum_r = jnp.sum(jnp.where(rows <= cols, col(lf), 0.0), axis=0,
                     keepdims=True)
    btot = jnp.sum(lf)

    e = bcum_c - bcum_r + li                               # (C, C)
    e = jnp.where(tri, e, -1e30)
    g = bcum_c + m0                                        # (C, 1)
    m_row = jnp.maximum(jnp.max(e, axis=1, keepdims=True), g)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = s * jnp.exp(e - m_row)
    p = jnp.where(tri, p, 0.0)
    c_in = jnp.exp(g - m_row)                              # (C, 1)
    num = (jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + c_in * jax.lax.dot_general(
               q, c_scr[...], (((1,), (0,)), ((), ())),
               preferred_element_type=jnp.float32))
    dot = (p.sum(axis=1, keepdims=True)
           + c_in * jnp.sum(q * n_scr[...], axis=1, keepdims=True))
    den = jnp.maximum(jnp.abs(dot), jnp.exp(-m_row))       # (C, 1)
    h_ref[0] = (num / den).astype(h_ref.dtype)

    # ---- chunk-end state update -----------------------------------------
    m_new = jnp.maximum(btot + m0, jnp.max(btot - bcum_r + li))
    w = jnp.exp(btot - bcum_c + li_c - m_new)              # (C, 1)
    decay = jnp.exp(btot + m0 - m_new)
    c_scr[...] = (decay * c_scr[...]
                  + jax.lax.dot_general(k * w, v,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    n_scr[...] = decay * n_scr[...] + jnp.sum(k * w, axis=0, keepdims=True)
    m_scr[...] = jnp.full_like(m_scr, m_new)


def mlstm_scan(q, k, v, log_i, log_f, *, chunk: int = 256,
               interpret: bool = False):
    """q/k/v: (B, T, H, D); log_i/log_f: (B, T, H) -> h: (B, T, H, D).

    T must be a multiple of ``chunk`` (pad upstream).  State starts at
    zero (use the pure-JAX path for cross-call state carry).
    """
    b, t, h, d = q.shape
    if t % chunk:
        raise ValueError(f"T={t} must be a multiple of chunk={chunk}")
    nc = t // chunk

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, t, *x.shape[3:])

    qf, kf, vf = flat(q), flat(k), flat(v)
    # Gates as (BH, 1, T): a (1, chunk) trailing block is full-height and
    # lane-aligned, which the TPU lowering requires.
    lif = jnp.moveaxis(log_i, 2, 1).reshape(b * h, 1, t)
    lff = jnp.moveaxis(log_f, 2, 1).reshape(b * h, 1, t)

    kernel = functools.partial(_kernel, chunk=chunk, dk=d, dv=d,
                               scale=1.0 / np.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, lif, lff)
    return jnp.moveaxis(out.reshape(b, h, t, d), 1, 2)
