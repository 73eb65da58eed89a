"""Paged cached-decode attention (one new token per sequence) in Pallas.

Same online-softmax recurrence as ``decode_attention``, but the KV cache is
a *global page pool* ``(num_pages, block, K, hd)`` shared by every slot and
indirected through a per-slot page table ``(B, pages_per_slot)``: grid step
``(b, p)`` streams page ``table[b, p]`` of the pool, all KV heads at once,
through VMEM (the block's last two dims equal the pool's ``(K, hd)``, as the
TPU tiling rule requires).  The page table and ragged lengths ride in as
scalar-prefetch operands so the table lookup can happen inside the k/v
``BlockSpec`` index maps — the whole point of the kernel: the pool is never
gathered into a dense per-slot view.

Conventions shared with the serving engine: page id 0 is the reserved trash
page (unmapped table entries point at it and are masked by ``length``), and
rows with ``length == 0`` return finite zeros (inactive slots).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block: int, sm_scale: float):
    b_ = pl.program_id(0)
    pi = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b_]
    k_start = pi * block

    @pl.when(k_start < length)
    def _compute():
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h].astype(jnp.float32)            # (G, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (block, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
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

    @pl.when(pi == np_ - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, page_table, lengths, *,
                           interpret=False):
    """q: (B,K,G,hd) grouped queries; k_pool, v_pool: (N, block, K, hd)
    global page pools; page_table: (B, W) int32 page ids (entries must be
    valid pool indices — masked-off ones conventionally point at the trash
    page 0); lengths: (B,) valid KV entries per slot."""
    b, kh, g, hd = q.shape
    block = k_pool.shape[1]
    w = page_table.shape[1]
    sm_scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(_kernel, block=block, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, kh, g, hd),
                         lambda b_, p_, tbl, lens: (b_, 0, 0, 0)),
            pl.BlockSpec((1, block, kh, hd),
                         lambda b_, p_, tbl, lens: (tbl[b_, p_], 0, 0, 0)),
            pl.BlockSpec((1, block, kh, hd),
                         lambda b_, p_, tbl, lens: (tbl[b_, p_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kh, g, hd),
                               lambda b_, p_, tbl, lens: (b_, 0, 0, 0)),
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
        name="paged_attention",
    )(page_table, lengths, q, k_pool, v_pool)
