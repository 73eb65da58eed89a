"""jit'd wrapper for the flash-attention Pallas kernel.

On TPU the kernel runs compiled with MXU-aligned tiles; elsewhere it runs in
``interpret=True`` mode (the kernel body executed by XLA:CPU) so correctness
is testable without a chip (see :mod:`repro.kernels.dispatch`).
Non-multiple sequence lengths are padded on the right (causal masking keeps
padded keys inert; padded queries are sliced off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import run_kernel
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, blk_q=128, blk_k=128,
                    interpret=None):
    b, s, h, hd = q.shape
    t = k.shape[1]
    blk_q = min(blk_q, max(8, s))
    blk_k = min(blk_k, max(8, t))
    pad_q = (-s) % blk_q
    pad_k = (-t) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    out = run_kernel(flash_attention_pallas, q, k, v, causal=causal,
                     blk_q=blk_q, blk_k=blk_k, kv_len=t, q_len=s,
                     interpret=interpret)
    return out[:, :s]
