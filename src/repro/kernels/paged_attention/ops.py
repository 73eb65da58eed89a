"""jit'd wrapper for the paged-attention Pallas kernel (compiled on TPU,
interpret mode elsewhere; see :mod:`repro.kernels.dispatch`)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import (
    paged_attention_pallas)
from repro.kernels.paged_attention.ref import gather_pages  # noqa: F401
from repro.kernels.dispatch import run_kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    interpret=None):
    """q: (B,H,hd); k_pool, v_pool: (N, block, K, hd); page_table: (B, W)
    int32; lengths: (B,).  Returns (B,H,hd).

    Table entries are clamped into the pool so every grid step loads a real
    page (unmapped entries point at the trash page 0 and are masked by
    ``length``); lengths are clamped to the table's addressable window.
    Rows with ``length == 0`` return zeros — inactive serving slots must
    come back finite, never NaN."""
    b, h, hd = q.shape
    kh = k_pool.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, hd)
    table = jnp.clip(page_table.astype(jnp.int32), 0, k_pool.shape[0] - 1)
    lengths = jnp.minimum(lengths.astype(jnp.int32),
                          table.shape[1] * k_pool.shape[1])
    out = run_kernel(paged_attention_pallas, qg, k_pool, v_pool, table,
                     lengths, interpret=interpret)
    return out.reshape(b, h, hd)
