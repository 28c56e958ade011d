"""jit'd public wrappers around the Pallas kernels.

Kernels compile for the TPU by default.  ``interpret=True`` runs the same
kernel body in Python on any backend; the CPU tests pass it explicitly.
"""
from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention as _flash_attention


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, q_pos=None, kv_pos=None, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    return _flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                            causal=causal, window=window, block_q=block_q,
                            block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q, k, v, log_i, log_f, *, chunk: int = 256,
               interpret: bool = False):
    from .mlstm_scan import mlstm_scan as _mlstm
    return _mlstm(q, k, v, log_i, log_f, chunk=chunk, interpret=interpret)
