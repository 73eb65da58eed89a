"""jit'd wrapper for the decode-attention Pallas kernel (compiled on TPU,
interpret mode elsewhere; see :mod:`repro.kernels.dispatch`)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas)
from repro.kernels.dispatch import run_kernel


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def decode_attention(q, k, v, lengths, *, blk_k=256, interpret=None):
    """q: (B,H,hd); k,v: (B,T,K,hd); lengths: (B,). Returns (B,H,hd).

    Rows with ``length == 0`` return zeros (empty online softmax): the
    serving path hands the kernel the full fixed-slot batch, and inactive
    slots carry length 0 — their output must be finite (it is discarded),
    never NaN.  ``T`` need not be a multiple of ``blk_k``."""
    b, h, hd = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, kh, h // kh, hd)
    out = run_kernel(decode_attention_pallas, qg, k, v,
                     lengths.astype(jnp.int32), blk_k=blk_k,
                     interpret=interpret)
    return out.reshape(b, h, hd)
