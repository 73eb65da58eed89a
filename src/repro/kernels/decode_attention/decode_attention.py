"""Cached decode attention (one new token per sequence) as a Pallas kernel.

Decode is HBM-bandwidth-bound: the kernel's job is to stream the KV cache
through VMEM exactly once at full bandwidth.  Grid = (batch, kv_block); each
step loads one ``(blk_k, K, hd)`` slab holding every KV head, so the block's
last two dims equal the cache's ``(K, hd)`` (the TPU tiling rule: the last
two block dims divide by (8, 128) or equal the array's).  Inside the step
the heads are walked in a static loop; all G query heads of a KV group are
processed together as a (G, hd) tile, and the online-softmax state (m, l,
acc) per KV head carries in VMEM scratch across KV blocks.

Per-sequence valid lengths ride in as a scalar-prefetch operand: they mask
trailing cache entries, and they clamp the K/V index map so blocks past a
row's length are never fetched (a repeated block index skips the DMA).  The
cache length need not be a multiple of ``blk_k``: the ragged last block is
masked by length like any other position past it.

The K/V operands are a stack of per-layer caches ``(P, B, T, K, hd)`` and
a second scalar-prefetch operand names the layer read: the index map
points every block at that layer, so the decode step hands the kernel the
whole cache its layer scan carries without slicing a layer out first.  A
single layer's cache is the stack of one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, blk_k: int, sm_scale: float):
    b_ = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b_]
    k_start = ki * blk_k

    @pl.when(k_start < length)
    def _compute():
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h].astype(jnp.float32)            # (G, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (blk_k, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            # rows past the length (incl. a ragged tail past the cache)
            # hold arbitrary values: zero them so 0-weight · NaN stays 0
            rows = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(rows < length, v, 0.0)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < length, s, NEG_INF)
            m_prev = m_scr[h]                              # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, layer, *, blk_k=256,
                            interpret=False):
    """q: (B,K,G,hd) grouped queries; k,v: (P,B,T,K,hd) stacked caches;
    lengths: (B,) int32 valid KV entries per row (0 ≤ length ≤ T); layer:
    (1,) int32, the index into P read."""
    b, kh, g, hd = q.shape
    t = k.shape[2]
    blk_k = min(blk_k, t)
    nk = pl.cdiv(t, blk_k)
    sm_scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(_kernel, blk_k=blk_k, sm_scale=sm_scale)

    def kv_map(b_, k_, lens, lyr):
        # blocks wholly past the row's length repeat its last live block
        last = jnp.maximum(lens[b_] - 1, 0) // blk_k
        return (lyr[0], b_, jnp.minimum(k_, last), 0, 0)

    def row_map(b_, k_, lens, lyr):
        return (b_, 0, 0, 0)

    # the layer axis is squeezed: the body sees (1, blk_k, K, hd) blocks
    kv_spec = pl.BlockSpec((None, 1, blk_k, kh, hd), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, kh, g, hd), row_map), kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, kh, g, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths, layer, q, k, v)
