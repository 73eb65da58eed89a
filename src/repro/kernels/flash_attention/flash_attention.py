"""Causal GQA flash attention as a Pallas TPU kernel.

TPU adaptation of the flash-attention tiling (DESIGN.md §3): the grid is
(batch, q_block, kv_block) with the KV axis innermost, and each step walks
every query head of its tiles in a static loop; per-head online-softmax
statistics (m, l) and the fp32 output accumulator live in VMEM scratch and
carry across the kv_block grid steps (TPU grids execute sequentially per
core, so scratch carries replace the CUDA warp-level loop).  Q/K/V tiles
stream HBM→VMEM per grid step; MXU-aligned block sizes (multiples of 128 on
the matmul dims) are chosen by the wrapper in ``ops.py``.

Causality is handled two ways: whole KV blocks strictly above the diagonal
are skipped via ``@pl.when`` (no compute issued), and the diagonal block is
masked elementwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            blk_q: int, blk_k: int, causal: bool, sm_scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k
    kv_len = lens_ref[0]
    q_len = lens_ref[1]
    # causal diagonal offset: with an offset KV cache (kv_len > q_len) the
    # first query row may already attend to kv_len - q_len leading keys
    off = kv_len - q_len
    run = jnp.logical_and(
        k_start < kv_len,
        (not causal) or (k_start <= q_start + blk_q - 1 + off))

    @pl.when(run)
    def _compute():
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        keep = cols < kv_len                               # padded keys inert
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            keep = jnp.logical_and(keep, cols <= rows + off)
        g = q_ref.shape[2] // k_ref.shape[2]
        for h in range(q_ref.shape[2]):
            q = q_ref[0, :, h, :].astype(jnp.float32)      # (blk_q, hd)
            k = k_ref[0, :, h // g, :].astype(jnp.float32)  # (blk_k, hd)
            v = v_ref[0, :, h // g, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[h]                              # (blk_q, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        for h in range(q_ref.shape[2]):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, :, h, :] = (acc_scr[h] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, blk_q=128, blk_k=128,
                           interpret=False, kv_len=None, q_len=None):
    """q: (B,S,H,hd); k,v: (B,T,K,hd), H = K·G, S % blk_q == 0 == T % blk_k.
    kv_len masks keys at positions ≥ kv_len (right padding).  q_len is the
    true (unpadded) query length: with kv_len > q_len the causal diagonal
    is shifted so the last query row attends to all kv_len keys (offset
    cache, matching the reference oracle).  Both reach the kernel as a
    scalar-prefetch operand.  Each grid step takes every head, so the
    blocks' last two dims equal the arrays' ``(H, hd)`` / ``(K, hd)``."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, t)
    assert s % blk_q == 0 and t % blk_k == 0
    sm_scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k,
                               causal=causal, sm_scale=sm_scale)
    if kv_len is None:
        kv_len = t
    if q_len is None:
        q_len = kv_len          # square case: diagonal ends at the corner
    lens = jnp.asarray([kv_len, q_len], jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // blk_q, t // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, h, hd), lambda b_, q_, k_, n: (b_, q_, 0, 0)),
            pl.BlockSpec((1, blk_k, kh, hd), lambda b_, q_, k_, n: (b_, k_, 0, 0)),
            pl.BlockSpec((1, blk_k, kh, hd), lambda b_, q_, k_, n: (b_, k_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, h, hd),
                               lambda b_, q_, k_, n: (b_, q_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, blk_q, 1), jnp.float32),
            pltpu.VMEM((h, blk_q, 1), jnp.float32),
            pltpu.VMEM((h, blk_q, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(lens, q, k, v)
