"""jit'd wrappers for the decode-attention Pallas kernel (compiled on TPU,
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
    return _decode(q, k[None], v[None], lengths, 0, blk_k, interpret)


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def decode_attention_stacked(q, k, v, lengths, layer, *, blk_k=256,
                             interpret=None):
    """:func:`decode_attention` over layer ``layer`` (int32 scalar) of
    stacked caches k,v: (P,B,T,K,hd).  Only that layer's blocks are read;
    no copy of the layer is made."""
    return _decode(q, k, v, lengths, layer, blk_k, interpret)


def _decode(q, k, v, lengths, layer, blk_k, interpret):
    b, h, hd = q.shape
    kh = k.shape[3]
    qg = q.reshape(b, kh, h // kh, hd)
    out = run_kernel(decode_attention_pallas, qg, k, v,
                     lengths.astype(jnp.int32),
                     jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
                     blk_k=blk_k, interpret=interpret)
    return out.reshape(b, h, hd)
