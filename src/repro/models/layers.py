"""Core transformer layers: RMSNorm, RoPE, GQA attention, MLPs.

Pure-JAX parameter-dict style.  Compute runs in bf16 with fp32 softmax and
norms; parameters are stored in the dtype handed to ``init`` (fp32 for
training, bf16 for serving).

Attention supports: causal self-attention (train / prefill), single-token
cached decode, bidirectional encoding, and cross-attention — all with
grouped-query heads.  When ``use_flash`` is set and the call is a pure causal
self-attention, the Pallas flash kernel is used instead of the XLA einsum
path (see ``repro.kernels.flash_attention``).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.models import runtime_flags as flags
from repro.sharding import shard

COMPUTE_DTYPE = jnp.bfloat16


def _init(rng, shape, scale, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- norms ----

def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------- rope ----

def rope_table(positions, head_dim, theta):
    """positions: int32 (...,S) → (cos, sin) each (...,S,head_dim//2) fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: (B,S,H,hd); cos/sin: (B,S,half) or (S,half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1f, x2f = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1f * cos - x2f * sin, x1f * sin + x2f * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention ----

def attention_init(rng, cfg, dtype, cross=False):
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    r = jax.random.split(rng, 5)
    s_in = d ** -0.5
    s_out = (h * hd) ** -0.5
    p = {
        "norm": rmsnorm_init(d, dtype),
        "wq": _init(r[0], (d, h, hd), s_in, dtype),
        "wk": _init(r[1], (d, k, hd), s_in, dtype),
        "wv": _init(r[2], (d, k, hd), s_in, dtype),
        "wo": _init(r[3], (h, hd, d), s_out, dtype),
    }
    return p


def _sdpa(q, k, v, mask, q_per_kv):
    """q: (B,S,H,hd) — k,v: (B,T,K,hd) — mask broadcastable to (B,K,G,S,T)."""
    b, s, h, hd = q.shape
    kheads = k.shape[2]
    q = q.reshape(b, s, kheads, q_per_kv, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, h, hd)


# Queries are processed in blocks of this length so the (S×T) score matrix is
# never fully materialized — the XLA-path analogue of flash-attention tiling
# (the Pallas kernel is the production TPU path).
Q_CHUNK = 1024


def _sdpa_chunked(q, k, v, qpos, q_per_kv, *, kind, kv_lengths=None,
                  q_chunk=None):
    if q_chunk is None:
        q_chunk = flags.Q_CHUNK_OVERRIDE or Q_CHUNK
    """Memory-bounded attention. kind: 'causal' (kv_pos<=q_pos), 'full',
    or 'length' (kv_pos < kv_lengths). qpos: (B,S) int32 query positions."""
    b, s, h, hd = q.shape
    t = k.shape[1]

    def block(q_blk, qp_blk):
        mask = None
        if kind == "causal":
            kv_pos = jnp.arange(t, dtype=jnp.int32)
            mask = kv_pos[None, None, None, None, :] <= qp_blk[:, None, None, :, None]
        elif kind == "length" and kv_lengths is not None:
            kv_pos = jnp.arange(t, dtype=jnp.int32)
            mask = kv_pos[None, None, None, None, :] < kv_lengths[:, None, None, None, None]
        return _sdpa(q_blk, k, v, mask, q_per_kv)

    if s <= q_chunk:
        return block(q, qpos)
    pad = (-s) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)))
    nq = q.shape[1] // q_chunk
    qr = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, hd), 1, 0)
    pr = jnp.moveaxis(qpos.reshape(b, nq, q_chunk), 1, 0)
    _, outs = jax.lax.scan(lambda c, args: (c, block(*args)), None, (qr, pr),
                           unroll=flags.inner_scan_unroll(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_chunk, h, hd)
    return out[:, :s]


def attention(params, x, cfg, *, positions=None, kv_cache=None, write_index=None,
              kv_source=None, causal=True, kv_lengths=None, use_rope=True,
              use_flash=False, decode_impl="sdpa", page_table=None,
              layer=None):
    """General GQA attention.

    x: (B,S,D) hidden states.
    positions: (S,) or (B,S) int32 query positions (for RoPE + causal mask).
    kv_cache: dict(k=(B,T,K,hd), v=...) — decode / incremental mode. K/V for
        the current tokens are written at ``write_index``; attention spans the
        whole cache masked by position.  Under a paged ``decode_impl`` the
        cache is instead the global page pool dict(k=(N,block,K,hd), v=...)
        indirected through ``page_table``.
    kv_source: (B,T,D) — cross-attention keys/values come from here.
    kv_lengths: (B,) valid KV length per batch row (cross / cache masking).
    decode_impl: "sdpa" (XLA einsum path) or "pallas" — on a single-token
        cached step the Pallas ragged decode-attention kernel streams the KV
        cache once, masked per-row by the (B,) position vector (TPU-compiled;
        interpret mode on CPU).  Multi-token calls always use the XLA path.
        "paged" / "paged_sdpa" use the page-pool layout: "paged" runs the
        Pallas paged-attention kernel (page-table-indirected block loads),
        "paged_sdpa" gathers the slot's pages into a dense view and reuses
        the XLA causal path (bit-compatible with "sdpa", CPU-meaningful).
    page_table: (B, W) int32 page ids per slot (paged decode only).
        Unmapped entries point at the trash page 0 and are masked by length.
    layer: int32 scalar — a single-token dense step on the stacked caches
        of every layer, dict(k=(P,B,T,K,hd), v=...), that the layer scan
        carries: row b's K/V is written at ``[layer, b, write_index[b]]``
        in place and attention reads layer ``layer`` of the stack.
    Returns (out, new_kv_cache_or_None).
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    xn = rmsnorm(params["norm"], x, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", xn, params["wq"].astype(COMPUTE_DTYPE))
    q = shard(q, "batch", "seq", "heads", "head_dim")
    src = xn if kv_source is None else kv_source.astype(xn.dtype)
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(COMPUTE_DTYPE))
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(COMPUTE_DTYPE))

    if use_rope and kv_source is None:
        if positions is None:
            positions = jnp.arange(s, dtype=jnp.int32)
        cos, sin = rope_table(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if positions is None:
        qp = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    elif positions.ndim == 1:
        qp = jnp.broadcast_to(positions[None].astype(jnp.int32), (b, s))
    else:
        qp = positions.astype(jnp.int32)

    if kv_cache is not None and layer is not None:
        # One position per row into the carried stack, in place: the
        # step's only cache traffic is B rows.  A write index past the
        # cache is dropped, as the masked rewrite below drops it.
        if s != 1:
            raise ValueError("the stacked-cache write takes one token a row")
        k = shard(k, "decode_batch", None, "kv_heads", "kv_head_dim")
        v = shard(v, "decode_batch", None, "kv_heads", "kv_head_dim")
        widx = jnp.broadcast_to(jnp.asarray(write_index, jnp.int32), (b,))
        rows = jnp.arange(b, dtype=jnp.int32)
        ck, cv = kv_cache["k"], kv_cache["v"]
        ck = ck.at[layer, rows, widx].set(k[:, 0].astype(ck.dtype),
                                          mode="drop", unique_indices=True)
        cv = cv.at[layer, rows, widx].set(v[:, 0].astype(cv.dtype),
                                          mode="drop", unique_indices=True)
        if decode_impl == "pallas":
            from repro.kernels.decode_attention import ops as decode_ops
            out = decode_ops.decode_attention_stacked(
                q[:, 0], ck, cv, qp[:, 0] + 1, layer)[:, None]
        else:
            kl = jax.lax.dynamic_index_in_dim(ck, layer, keepdims=False)
            vl = jax.lax.dynamic_index_in_dim(cv, layer, keepdims=False)
            out = _sdpa_chunked(q, kl.astype(COMPUTE_DTYPE),
                                vl.astype(COMPUTE_DTYPE), qp,
                                cfg.q_heads_per_kv, kind="causal")
        out = jnp.einsum("bshk,hkd->bsd", out,
                         params["wo"].astype(COMPUTE_DTYPE))
        return shard(out, "batch", "seq", "act_embed"), {"k": ck, "v": cv}

    if kv_cache is not None and decode_impl in ("paged", "paged_sdpa"):
        # Paged single-token decode: the cache is the global page pool
        # (N, block, K, hd); row b's KV position p lives in
        # pool[page_table[b, p // block], p % block].  Write this step's
        # K/V at the slot's current position (inactive rows sit at
        # position 0 with an all-trash table row, so their writes land in
        # the reserved trash page 0 and are masked by length), then attend
        # over the slot's pages up to kv_pos <= q_pos.
        if s != 1:
            raise ValueError("paged decode handles single-token steps only")
        if page_table is None:
            raise ValueError(f"decode_impl={decode_impl!r} needs a page_table")
        k = shard(k, "decode_batch", None, "kv_heads", "kv_head_dim")
        v = shard(v, "decode_batch", None, "kv_heads", "kv_head_dim")
        ck, cv = kv_cache["k"], kv_cache["v"]
        block = ck.shape[1]
        table = jnp.asarray(page_table, jnp.int32)
        pos = qp[:, 0]
        page = table[jnp.arange(b, dtype=jnp.int32), pos // block]
        off = pos % block
        ck = ck.at[page, off].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[page, off].set(v[:, 0].astype(cv.dtype))
        new_cache = {"k": ck, "v": cv}
        lengths = pos + 1
        if decode_impl == "paged":
            from repro.kernels.paged_attention import ops as paged_ops
            out = paged_ops.paged_attention(
                q[:, 0], ck, cv, table, lengths)[:, None]
        else:
            from repro.kernels.paged_attention.ref import gather_pages
            kd = gather_pages(ck, table).astype(COMPUTE_DTYPE)
            vd = gather_pages(cv, table).astype(COMPUTE_DTYPE)
            out = _sdpa_chunked(q, kd, vd, qp, cfg.q_heads_per_kv,
                                kind="causal")
        out = jnp.einsum("bshk,hkd->bsd", out,
                         params["wo"].astype(COMPUTE_DTYPE))
        return shard(out, "batch", "seq", "act_embed"), new_cache

    new_cache = None
    if kv_cache is not None:
        # write current K/V at write_index, attend over the full cache.
        # write_index may be a scalar (aligned batch) or an int32 (B,) vector
        # (ragged continuous batching — masked scatter, S must be 1).
        # Constrain the incoming K/V to the cache's layout first — otherwise
        # XLA's SPMD partitioner resolves the sharding mismatch inside the
        # update by replicating the FULL cache (§Perf iteration 2: this was
        # ~50% of decode collective traffic).
        k = shard(k, "decode_batch", None, "kv_heads", "kv_head_dim")
        v = shard(v, "decode_batch", None, "kv_heads", "kv_head_dim")
        ck, cv = kv_cache["k"], kv_cache["v"]
        widx = jnp.asarray(write_index, jnp.int32) if write_index is not None \
            else jnp.int32(0)
        if widx.ndim == 0:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, widx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, widx, 0, 0))
        else:
            sel = (jnp.arange(ck.shape[1], dtype=jnp.int32)[None, :, None, None]
                   == widx[:, None, None, None])
            ck = jnp.where(sel, k.astype(ck.dtype), ck)
            cv = jnp.where(sel, v.astype(cv.dtype), cv)
        new_cache = {"k": ck, "v": cv}
        k, v = ck.astype(COMPUTE_DTYPE), cv.astype(COMPUTE_DTYPE)

    if kv_cache is not None:
        if decode_impl == "pallas" and s == 1:
            # Ragged single-token decode: one kernel pass over the whole
            # slot batch, each row masked to its own valid prefix
            # (kv_pos <= q_pos  ⇔  kv_pos < q_pos + 1).  The kernel's
            # online softmax runs in fp32 like the _sdpa path's scores.
            from repro.kernels.decode_attention import ops as decode_ops
            lengths = qp[:, 0].astype(jnp.int32) + 1
            out = decode_ops.decode_attention(q[:, 0], k, v, lengths)[:, None]
            out = jnp.einsum("bshk,hkd->bsd", out,
                             params["wo"].astype(COMPUTE_DTYPE))
            return shard(out, "batch", "seq", "act_embed"), new_cache
        kind = "causal"
    elif kv_source is not None:
        kind = "length" if kv_lengths is not None else "full"
    elif causal:
        if use_flash and s == k.shape[1] and s % 128 == 0:
            from repro.kernels.flash_attention import ops as flash_ops
            out = flash_ops.flash_attention(q, k, v, causal=True)
            out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(COMPUTE_DTYPE))
            return shard(out, "batch", "seq", "act_embed"), new_cache
        kind = "causal"
    else:
        kind = "full"

    out = _sdpa_chunked(q, k, v, qp, cfg.q_heads_per_kv, kind=kind,
                        kv_lengths=kv_lengths)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(COMPUTE_DTYPE))
    return shard(out, "batch", "seq", "act_embed"), new_cache


def attention_cache_init(cfg, batch, max_len, dtype=COMPUTE_DTYPE):
    k, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, k, hd), dtype),
        "v": jnp.zeros((batch, max_len, k, hd), dtype),
    }


def paged_attention_cache_init(cfg, num_pages, block, dtype=COMPUTE_DTYPE):
    """Global KV page pool shared by every decode slot.  ``num_pages`` must
    include the reserved trash page 0 (the engine allocates pool size
    ``allocatable + 1``)."""
    k, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((num_pages, block, k, hd), dtype),
        "v": jnp.zeros((num_pages, block, k, hd), dtype),
    }


# ------------------------------------------------------------------ mlp ----

def mlp_init(rng, cfg, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    r = jax.random.split(rng, 3)
    p = {"norm": rmsnorm_init(d, dtype)}
    if cfg.activation == "swiglu":
        p["wg"] = _init(r[0], (d, f), d ** -0.5, dtype)
    p["wu"] = _init(r[1], (d, f), d ** -0.5, dtype)
    p["wd"] = _init(r[2], (f, d), f ** -0.5, dtype)
    return p


def mlp(params, x, cfg):
    xn = rmsnorm(params["norm"], x, cfg.norm_eps)
    wu = params["wu"].astype(COMPUTE_DTYPE)
    wd = params["wd"].astype(COMPUTE_DTYPE)
    h = jnp.einsum("bsd,df->bsf", xn, wu)
    h = shard(h, "batch", "seq", "act_mlp")
    if cfg.activation == "swiglu":
        g = jnp.einsum("bsd,df->bsf", xn, params["wg"].astype(COMPUTE_DTYPE))
        h = jax.nn.silu(g) * h
    elif cfg.activation == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("bsf,fd->bsd", h, wd)
    return shard(out, "batch", "seq", "act_embed")
