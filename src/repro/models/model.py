"""Unified model: one scan-over-layers decoder covering all assigned families.

Layers are grouped into *periods* (the smallest repeating block pattern —
1 for dense/MoE, 8 for Jamba's 1:7 attn:mamba interleave, 4 for xLSTM's
mLSTM/sLSTM mix) and the stack is a ``lax.scan`` over ``num_layers //
period`` periods with stacked parameters, keeping HLO size independent of
depth.

Three entry points per model:
  * ``train_loss(params, batch)``      — next-token loss (teacher forcing)
  * ``prefill(params, batch, max_len)``— fills KV/state caches, last logits
  * ``decode(params, caches, tokens, cur_index)`` — one token w/ cache

``input_specs``/``cache_specs`` provide ShapeDtypeStruct stand-ins for the
multi-pod dry-run (no allocation).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import runtime_flags as flags
from repro.models import ssm as ssm_lib
from repro.sharding import shard

# Deterministic synthetic-shape conventions for enc-dec / VLM cells
ENC_CTX_DECODE = 4_096   # encoder context length used by decode shapes
DEC_PREFIX = 64          # decoder prefix length for enc-dec prefill cells


@dataclass(frozen=True)
class BlockDesc:
    mixer: str                 # attn | mamba | mlstm | slstm
    mlp: Optional[str]         # dense | moe | None
    cross: bool = False


ENC_DESC = BlockDesc("attn", "dense")


def layer_layout(cfg: ModelConfig):
    """Return (period, [BlockDesc per position within the period])."""
    if cfg.family == "ssm":
        x = cfg.xlstm
        period = x.slstm_every
        descs = [BlockDesc("slstm" if i % x.slstm_every == x.slstm_offset
                           else "mlstm", None) for i in range(period)]
        return period, descs
    period = cfg.attn_layer_period
    if cfg.moe is not None:
        period = int(np.lcm(period, cfg.moe.every_k_layers))
    descs = []
    for i in range(period):
        mixer = "attn"
        if cfg.family == "hybrid" and i % cfg.attn_layer_period != cfg.attn_layer_offset:
            mixer = "mamba"
        if cfg.moe is not None and i % cfg.moe.every_k_layers == cfg.moe.moe_layer_offset:
            mlp = "moe"
        elif cfg.d_ff > 0:
            mlp = "dense"
        else:
            mlp = None
        descs.append(BlockDesc(mixer, mlp, cross=cfg.cross_attention))
    assert cfg.num_layers % period == 0, (cfg.name, cfg.num_layers, period)
    return period, descs


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.period, self.descs = layer_layout(cfg)
        self.n_periods = cfg.num_layers // self.period
        self.use_flash = False  # engines may switch on Pallas attention
        self._init_jit = jax.jit(self._init, static_argnums=1)

    # ------------------------------------------------------------- init ----

    def _block_init(self, rng, desc: BlockDesc, dtype):
        cfg = self.cfg
        r = jax.random.split(rng, 4)
        p = {}
        if desc.mixer == "attn":
            p["attn"] = L.attention_init(r[0], cfg, dtype)
        elif desc.mixer == "mamba":
            p["mamba"] = ssm_lib.mamba_init(r[0], cfg, dtype)
        elif desc.mixer == "mlstm":
            p["mlstm"] = ssm_lib.mlstm_init(r[0], cfg, dtype)
        elif desc.mixer == "slstm":
            p["slstm"] = ssm_lib.slstm_init(r[0], cfg, dtype)
        if desc.cross:
            p["xattn"] = L.attention_init(r[1], cfg, dtype)
        if desc.mlp == "dense":
            p["mlp"] = L.mlp_init(r[2], cfg, dtype)
        elif desc.mlp == "moe":
            p["moe"] = moe_lib.moe_init(r[2], cfg, dtype)
        return p

    def _period_init(self, rng, dtype, descs=None):
        descs = descs if descs is not None else self.descs
        rs = jax.random.split(rng, len(descs))
        return {f"p{i}": self._block_init(rs[i], d, dtype)
                for i, d in enumerate(descs)}

    def init(self, rng, dtype=jnp.float32):
        """Seeded random parameters in ``dtype``, drawn under ``jit``: each
        weight is sampled and cast inside one program on the default
        device, so a bf16 model never holds a float32 copy of its stack."""
        return self._init_jit(rng, dtype)

    def _init(self, rng, dtype):
        cfg = self.cfg
        r = jax.random.split(rng, 6)
        params = {
            "embed": (jax.random.normal(r[0], (cfg.vocab_size, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dtype),
            "unembed": (jax.random.normal(r[1], (cfg.d_model, cfg.vocab_size),
                                          jnp.float32)
                        * cfg.d_model ** -0.5).astype(dtype),
            "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
            "stack": jax.vmap(lambda k: self._period_init(k, dtype))(
                jax.random.split(r[2], self.n_periods)),
        }
        if cfg.frontend:
            params["frontend_proj"] = (
                jax.random.normal(r[3], (cfg.frontend_dim, cfg.d_model),
                                  jnp.float32) * cfg.frontend_dim ** -0.5
            ).astype(dtype)
        if cfg.num_encoder_layers:
            params["enc_stack"] = jax.vmap(
                lambda k: self._period_init(k, dtype, [ENC_DESC]))(
                jax.random.split(r[4], cfg.num_encoder_layers))
            params["enc_final_norm"] = L.rmsnorm_init(cfg.d_model, dtype)
        return params

    def init_abstract(self, dtype=jnp.float32):
        return jax.eval_shape(lambda k: self.init(k, dtype),
                              jax.random.PRNGKey(0))

    # ------------------------------------------------------------ blocks ----

    def _block_apply(self, desc, bp, x, bc, *, positions, write_index,
                     enc_out, causal=True, decode_impl="sdpa",
                     page_table=None):
        """Apply one block. bc (the block cache) is None in train mode.
        Returns (x, new_block_cache, moe_aux or None)."""
        cfg = self.cfg
        is_step = x.shape[1] == 1 and bc is not None
        nc = {}
        if desc.mixer == "attn":
            h, kv = L.attention(bp["attn"], x, cfg, positions=positions,
                                kv_cache=bc.get("kv") if bc else None,
                                write_index=write_index, causal=causal,
                                use_flash=self.use_flash,
                                decode_impl=decode_impl,
                                page_table=page_table)
            if bc is not None:
                nc["kv"] = kv
            x = x + h
        elif desc.mixer == "mamba":
            h, st = ssm_lib.mamba_block(
                bp["mamba"], x, cfg, cache=bc.get("state") if is_step else None)
            if bc is not None:
                nc["state"] = st
            x = x + h
        elif desc.mixer == "mlstm":
            h, st = ssm_lib.mlstm_block(
                bp["mlstm"], x, cfg, cache=bc.get("state") if is_step else None)
            if bc is not None:
                nc["state"] = st
            x = x + h
        elif desc.mixer == "slstm":
            h, st = ssm_lib.slstm_block(
                bp["slstm"], x, cfg, cache=bc.get("state") if is_step else None)
            if bc is not None:
                nc["state"] = st
            x = x + h
        if desc.cross:
            if bc is not None:
                xk, xv = bc["xk"], bc["xv"]
                h = self._cross_cached(bp["xattn"], x, xk, xv)
                nc["xk"], nc["xv"] = xk, xv
            else:
                h, _ = L.attention(bp["xattn"], x, cfg, kv_source=enc_out,
                                   causal=False, use_rope=False)
            x = x + h
        aux = None
        if desc.mlp == "dense":
            x = x + L.mlp(bp["mlp"], x, cfg)
        elif desc.mlp == "moe":
            h, aux = moe_lib.moe(bp["moe"], x, cfg)
            x = x + h
        return x, nc, aux

    def _cross_cached(self, params, x, xk, xv):
        """Cross-attention against precomputed (cached) encoder K/V."""
        cfg = self.cfg
        xn = L.rmsnorm(params["norm"], x, cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", xn, params["wq"].astype(L.COMPUTE_DTYPE))
        out = L._sdpa(q, xk.astype(L.COMPUTE_DTYPE), xv.astype(L.COMPUTE_DTYPE),
                      None, cfg.q_heads_per_kv)
        return jnp.einsum("bshk,hkd->bsd", out,
                          params["wo"].astype(L.COMPUTE_DTYPE))

    # ------------------------------------------------------------ stacks ----

    def _run_stack(self, stack, x, *, caches=None, positions=None,
                   write_index=None, enc_out=None, causal=True, remat=False,
                   decode_impl="sdpa", page_table=None):
        """lax.scan over periods. Returns (x, new_caches_or_None, aux_sum)."""
        collect = caches is not None

        def body(carry, per):
            xx = carry
            pp, pc = per if collect else (per, None)
            new_c = {}
            aux_sum = jnp.zeros((), jnp.float32)
            for i, desc in enumerate(self.descs):
                bc = pc[f"p{i}"] if pc is not None else None
                xx, ncb, aux = self._block_apply(
                    desc, pp[f"p{i}"], xx, bc, positions=positions,
                    write_index=write_index, enc_out=enc_out, causal=causal,
                    decode_impl=decode_impl, page_table=page_table)
                new_c[f"p{i}"] = ncb
                if aux is not None:
                    aux_sum = aux_sum + aux["moe_aux_loss"]
            return xx, ((new_c, aux_sum) if collect else aux_sum)

        if remat:
            body = jax.checkpoint(body)
        unroll = flags.scan_unroll(self.n_periods)
        if collect:
            x, (new_caches, aux) = jax.lax.scan(body, x, (stack, caches),
                                                unroll=unroll)
        else:
            x, aux = jax.lax.scan(body, x, stack, unroll=unroll)
            new_caches = None
        return x, new_caches, jnp.sum(aux)

    def _run_encoder(self, params, frames):
        cfg = self.cfg
        x = jnp.einsum("bsf,fd->bsd", frames.astype(L.COMPUTE_DTYPE),
                       params["frontend_proj"].astype(L.COMPUTE_DTYPE))
        x = shard(x, "batch", "seq", "act_embed")

        def body(xx, pp):
            xx, _, _ = self._block_apply(ENC_DESC, pp["p0"], xx, None,
                                         positions=None, write_index=None,
                                         enc_out=None, causal=False)
            return xx, None

        x, _ = jax.lax.scan(body, x, params["enc_stack"],
                            unroll=flags.scan_unroll(cfg.num_encoder_layers))
        return L.rmsnorm(params["enc_final_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------- embed ----

    def _embed_inputs(self, params, batch):
        """Returns (x, enc_out, label_offset)."""
        cfg = self.cfg
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self._run_encoder(params, batch["frames"])
        x = params["embed"].astype(L.COMPUTE_DTYPE)[batch["tokens"]]
        offset = 0
        if cfg.family == "vlm" and "patches" in batch:
            pe = jnp.einsum("bpf,fd->bpd",
                            batch["patches"].astype(L.COMPUTE_DTYPE),
                            params["frontend_proj"].astype(L.COMPUTE_DTYPE))
            x = jnp.concatenate([pe, x], axis=1)
            offset = pe.shape[1]
        return shard(x, "batch", "seq", "act_embed"), enc_out, offset

    # ------------------------------------------------------------- train ----

    def train_loss(self, params, batch, *, remat=True):
        """Next-token cross-entropy (+ MoE load-balance aux loss)."""
        cfg = self.cfg
        x, enc_out, offset = self._embed_inputs(params, batch)
        x, _, aux = self._run_stack(params["stack"], x, enc_out=enc_out,
                                    remat=remat)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if offset:
            x = x[:, offset:, :]
        tokens = batch["tokens"]
        loss = _chunked_ce(x[:, :-1], tokens[:, 1:], params["unembed"])
        if cfg.moe is not None:
            loss = loss + 0.01 * aux / max(self.n_periods, 1)
        return loss

    # ----------------------------------------------------------- serving ----

    def cache_init(self, batch, max_len, abstract=False):
        """Stacked caches pytree for a decode session (zeros/-inf or SDS)."""
        def build():
            per = {}
            for i, desc in enumerate(self.descs):
                c = {}
                if desc.mixer == "attn":
                    c["kv"] = L.attention_cache_init(self.cfg, batch, max_len)
                elif desc.mixer == "mamba":
                    c["state"] = ssm_lib.mamba_cache_init(self.cfg, batch)
                elif desc.mixer == "mlstm":
                    c["state"] = ssm_lib.mlstm_cache_init(self.cfg, batch)
                elif desc.mixer == "slstm":
                    c["state"] = ssm_lib.slstm_cache_init(self.cfg, batch)
                if desc.cross:
                    k, hd = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
                    c["xk"] = jnp.zeros((batch, ENC_CTX_DECODE, k, hd),
                                        L.COMPUTE_DTYPE)
                    c["xv"] = jnp.zeros((batch, ENC_CTX_DECODE, k, hd),
                                        L.COMPUTE_DTYPE)
                per[f"p{i}"] = c
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_periods,) + a.shape)
                          + jnp.zeros((), a.dtype), per)
        if abstract:
            return jax.eval_shape(build)
        return build()

    def paged_cache_init(self, num_pages, block, abstract=False):
        """Global KV page-pool pytree for paged decode: same per-period
        structure as :meth:`cache_init`, but every "kv" leaf is a page pool
        ``(num_pages + 1, block, K, hd)`` shared by all slots — the +1 is
        the reserved trash page 0 (inactive slots write there; never
        allocated).  Attention-only stacks, see
        :attr:`supports_paged_decode`."""
        assert self.supports_paged_decode, self.cfg.name
        def build():
            per = {f"p{i}": {"kv": L.paged_attention_cache_init(
                        self.cfg, num_pages + 1, block)}
                   for i in range(len(self.descs))}
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_periods,) + a.shape)
                          + jnp.zeros((), a.dtype), per)
        if abstract:
            return jax.eval_shape(build)
        return build()

    @property
    def supports_paged_decode(self) -> bool:
        """The paged KV layout holds every sequence mixer's decode state in
        the shared page pool, so (like padded prefill) it requires a pure
        causal-attention stack: recurrent mixers carry dense per-slot state
        that has no block-granular form."""
        return (all(d.mixer == "attn" and not d.cross for d in self.descs)
                and self.cfg.family not in ("encdec", "vlm"))

    def prefill(self, params, batch, max_len=None):
        """Process the prompt; returns (last_logits (B,V), caches)."""
        cfg = self.cfg
        x, enc_out, _ = self._embed_inputs(params, batch)
        b, s = x.shape[0], x.shape[1]
        max_len = max_len or s
        caches = self.cache_init(b, max_len)
        if cfg.family == "encdec" and enc_out is not None:
            caches = self._fill_cross_cache(params, caches, enc_out)
        positions = jnp.arange(s, dtype=jnp.int32)
        x, new_caches, _ = self._run_stack(
            params["stack"], x, caches=caches, positions=positions,
            write_index=0, enc_out=enc_out)
        x = L.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["unembed"].astype(L.COMPUTE_DTYPE))
        return logits[:, 0].astype(jnp.float32), new_caches

    def prefill_batched(self, params, tokens, lengths, max_len=None):
        """Ragged prompt batch: one jitted pass over right-padded prompts.

        ``tokens``: (B, S) int32, each row right-padded to S; ``lengths``:
        (B,) valid prompt length per row.  Returns (last_logits (B, V) —
        row ``i``'s logits taken at position ``lengths[i] - 1`` — and the
        batch cache bundle; row ``i`` of the caches is a valid decode/donor
        cache for positions < ``lengths[i]``).

        Exactness under right-padding needs every sequence mixer to be
        causal attention (:attr:`supports_padded_prefill`): a padding token
        at position j ≥ length is never attended by a query at position
        < j, and the garbage K/V it writes is masked (and later overwritten
        by decode) before any real query can reach it.  Recurrent mixers
        (mamba/xLSTM) would absorb padding tokens into their terminal
        state, so padded batches are gated off for them — equal-length
        groups (no padding) remain exact for every family."""
        cfg = self.cfg
        x = params["embed"].astype(L.COMPUTE_DTYPE)[tokens]
        x = shard(x, "batch", "seq", "act_embed")
        b, s = tokens.shape
        max_len = max_len or s
        caches = self.cache_init(b, max_len)
        positions = jnp.arange(s, dtype=jnp.int32)
        x, new_caches, _ = self._run_stack(
            params["stack"], x, caches=caches, positions=positions,
            write_index=0)
        idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, s - 1)
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)   # (B,1,D)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["unembed"].astype(L.COMPUTE_DTYPE))
        return logits[:, 0].astype(jnp.float32), new_caches

    @property
    def supports_padded_prefill(self) -> bool:
        """Right-padded ragged prompt batches are exact only for pure
        causal-attention stacks (see :meth:`prefill_batched`); recurrent
        mixers fold padding tokens into their terminal decode state.
        Equal-length (padding-free) batches are always allowed."""
        return (all(d.mixer == "attn" and not d.cross for d in self.descs)
                and self.cfg.family not in ("encdec", "vlm"))

    @property
    def supports_prefill_resume(self) -> bool:
        """Prefix-resumable prompt passes need every mixer's sequence state
        to live in the KV cache: attention attends over the cache with a
        positional causal mask, so writing the suffix at ``start`` and
        masking does the right thing; SSM/recurrent mixers (mamba/xLSTM)
        recompute their state from the visible window during a multi-token
        pass, so a resumed window would silently drop the prefix state."""
        return (all(d.mixer == "attn" and not d.cross for d in self.descs)
                and self.cfg.family not in ("encdec", "vlm"))

    def prefill_resume(self, params, caches, tokens, start):
        """Continue a prompt pass from position ``start``.

        ``caches`` must hold valid K/V for positions < ``start`` (from an
        earlier :meth:`prefill` of a prompt sharing that prefix); ``tokens``
        is the (B, S_suffix) suffix starting at ``start``.  The suffix K/V
        is written at ``start``..``start+S_suffix-1``, overwriting whatever
        the donor prompt had there; stale donor positions at or beyond the
        new total length stay masked (kv_pos ≤ q_pos never reaches them),
        so the pass is exact — attention-only models, see
        :attr:`supports_prefill_resume`.  Returns (last_logits (B,V),
        caches), like :meth:`prefill`."""
        assert self.supports_prefill_resume, self.cfg.name
        cfg = self.cfg
        x = params["embed"].astype(L.COMPUTE_DTYPE)[tokens]
        x = shard(x, "batch", "seq", "act_embed")
        s = x.shape[1]
        start = jnp.asarray(start, jnp.int32)
        positions = jnp.arange(s, dtype=jnp.int32) + start
        x, new_caches, _ = self._run_stack(
            params["stack"], x, caches=caches, positions=positions,
            write_index=start, enc_out=None)
        x = L.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["unembed"].astype(L.COMPUTE_DTYPE))
        return logits[:, 0].astype(jnp.float32), new_caches

    def _fill_cross_cache(self, params, caches, enc_out):
        def fill(pp, pc):
            out = dict(pc)
            for i, desc in enumerate(self.descs):
                if desc.cross:
                    xp = pp[f"p{i}"]["xattn"]
                    src = enc_out.astype(L.COMPUTE_DTYPE)
                    xk = jnp.einsum("bsd,dhk->bshk", src,
                                    xp["wk"].astype(L.COMPUTE_DTYPE))
                    xv = jnp.einsum("bsd,dhk->bshk", src,
                                    xp["wv"].astype(L.COMPUTE_DTYPE))
                    c = dict(out[f"p{i}"])
                    t = c["xk"].shape[1]
                    c["xk"] = _fit_len(xk, t)
                    c["xv"] = _fit_len(xv, t)
                    out[f"p{i}"] = c
            return out
        return jax.vmap(fill, in_axes=(0, 0))(params["stack"], caches)

    def decode(self, params, caches, tokens, cur_index, decode_impl="sdpa",
               page_table=None):
        """One decode step. tokens: (B,1) int32; cur_index: scalar int32, or
        an int32 (B,) vector for ragged continuous batching.

        ``decode_impl="pallas"`` routes the cached-attention step through
        the Pallas ragged decode kernel (per-row length masking from the
        position vector); ``"sdpa"`` keeps the XLA einsum path.  The paged
        impls ("paged" — Pallas paged kernel — and "paged_sdpa" — gathered
        dense XLA path) expect ``caches`` from :meth:`paged_cache_init` and
        a ``page_table`` (B, W) int32 mapping each slot's KV blocks into
        the shared page pool."""
        cfg = self.cfg
        x = params["embed"].astype(L.COMPUTE_DTYPE)[tokens]
        x = shard(x, "decode_batch", None, "act_embed")
        cur = jnp.asarray(cur_index, jnp.int32)
        if cur.ndim == 0:
            positions = jnp.full((tokens.shape[0], 1), cur, jnp.int32)
        else:
            positions = cur[:, None]
        x, new_caches, _ = self._run_stack(
            params["stack"], x, caches=caches, positions=positions,
            write_index=cur, decode_impl=decode_impl, page_table=page_table)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["unembed"].astype(L.COMPUTE_DTYPE))
        return logits[:, 0].astype(jnp.float32), new_caches

    # ----------------------------------------------------------- dry-run ----

    def input_specs(self, shape: ShapeConfig):
        """ShapeDtypeStruct stand-ins for the step inputs (no allocation)."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32, bf16 = jnp.int32, jnp.bfloat16
        if shape.kind in ("train", "prefill"):
            if cfg.family == "encdec":
                dec = s if shape.kind == "train" else DEC_PREFIX
                return {"frames": jax.ShapeDtypeStruct((b, s, cfg.frontend_dim), bf16),
                        "tokens": jax.ShapeDtypeStruct((b, dec), i32)}
            if cfg.family == "vlm":
                return {"patches": jax.ShapeDtypeStruct(
                            (b, cfg.num_patches, cfg.frontend_dim), bf16),
                        "tokens": jax.ShapeDtypeStruct((b, s - cfg.num_patches), i32)}
            return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
                "cur_index": jax.ShapeDtypeStruct((), i32)}

    def cache_specs(self, shape: ShapeConfig):
        assert shape.kind == "decode"
        return self.cache_init(shape.global_batch, shape.seq_len, abstract=True)

    # ------------------------------------------------------------- flops ----

    def model_flops(self, shape: ShapeConfig) -> float:
        """MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference), N = active params."""
        n = self.cfg.active_param_count()
        if shape.kind == "train":
            return 6.0 * n * shape.global_batch * shape.seq_len
        if shape.kind == "prefill":
            return 2.0 * n * shape.global_batch * shape.seq_len
        return 2.0 * n * shape.global_batch  # decode: one token per sequence


LOSS_CHUNK = 512


def _chunked_ce(x, tgt, unembed, chunk=LOSS_CHUNK):
    """Cross-entropy without materializing the full (B,S,V) logits: the
    sequence is processed in blocks of ``chunk`` via lax.map (checkpointed so
    the backward pass also stays block-sized)."""
    b, s, d = x.shape

    @jax.checkpoint
    def block(args):
        xb, tb, wb = args
        logits = jnp.einsum("bsd,dv->bsv", xb,
                            unembed.astype(L.COMPUTE_DTYPE))
        logits = shard(logits, "batch", "seq", "vocab").astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - ll) * wb), jnp.sum(wb)

    if s <= chunk:
        tot, cnt = block((x, tgt, jnp.ones((b, s), jnp.float32)))
        return tot / cnt
    pad = (-s) % chunk
    w = jnp.ones((b, s), jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xs = jnp.moveaxis(x.reshape(b, nc, chunk, d), 1, 0)
    ts = jnp.moveaxis(tgt.reshape(b, nc, chunk), 1, 0)
    ws = jnp.moveaxis(w.reshape(b, nc, chunk), 1, 0)
    _, (tots, cnts) = jax.lax.scan(
        lambda c, args: (c, block(args)), None, (xs, ts, ws),
        unroll=flags.scan_unroll(nc))
    return jnp.sum(tots) / jnp.sum(cnts)


def _fit_len(x, t):
    if x.shape[1] == t:
        return x
    if x.shape[1] > t:
        return x[:, :t]
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, t - x.shape[1])
    return jnp.pad(x, pad)


_MODEL_CACHE = {}


def build_model(cfg: ModelConfig) -> Model:
    if cfg not in _MODEL_CACHE:
        _MODEL_CACHE[cfg] = Model(cfg)
    return _MODEL_CACHE[cfg]
