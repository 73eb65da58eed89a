"""The dense architecture module: seeded weights in the served layout, the
plain float32 reference, and the work of a decode step.

A configuration names its architecture module with ``"reference":
"<name>"`` (``bench/<name>.py``); one that names none, as the dense
configurations do, is judged by this one.  Every such module exports
``QUANTS``, ``init_params(m, seed)``, ``served_gaps(params, m, seqs,
t_pad, p_pad, controls)``, ``decode_step_flops(m, lengths)``,
``attention_flops(m, lengths)`` and ``attention_bytes(m, lengths)``; a
module of another architecture may build on the pieces here
(``attention``, ``forward_logits(..., stack=...)``, ``served_gaps(...,
stack=...)``).

The benchmark makes the weights itself, from the seed, on the device, in
one jitted call and in the type they are served in (bfloat16).  The tree
has the layout the served model reads: ``embed`` (V, d), ``unembed``
(d, V), ``final_norm.scale`` and ``stack.p0.{attn, mlp}`` with one leading
axis over the layers.

The reference is a decoder written out in ``jax.numpy``: RMSNorm, full
rotary embedding, grouped-query causal attention, a SwiGLU or squared-ReLU
MLP, an untied head.  It runs in float32 with every product at
``Precision.HIGHEST``, one layer at a time, so that float32 copies of the
weights are made for one layer and the head only.  It imports nothing of
the program under test.  The work counts are ``bench/flops.py``'s.

``quant`` gives the control: the same reference with every weight rounded
per output channel to int8 or float8 (e4m3) before use.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import (attention_bytes, attention_flops,  # noqa: F401
                         decode_step_flops)

HI = jax.lax.Precision.HIGHEST
QUANTS = ("int8", "fp8")    # the control's weight types
NORM_SCALE_STD = 0.1   # norm scales are 1 + N(0, 0.1): a dropped scale shows


def _key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _sizes(m: dict) -> Tuple:
    return (m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], m["d_ff"], m["vocab_size"], m["activation"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, sizes, dtype):
    n_layers, d, h, k, hd, f, v, act = sizes
    ks = iter(jax.random.split(key, 16))

    def w(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(dtype)

    def scale(shape):
        return (1.0 + NORM_SCALE_STD * jax.random.normal(next(ks), shape,
                                                         jnp.float32)
                ).astype(dtype)

    attn = {"norm": {"scale": scale((n_layers, d))},
            "wq": w((n_layers, d, h, hd), d ** -0.5),
            "wk": w((n_layers, d, k, hd), d ** -0.5),
            "wv": w((n_layers, d, k, hd), d ** -0.5),
            "wo": w((n_layers, h, hd, d), (h * hd) ** -0.5)}
    mlp = {"norm": {"scale": scale((n_layers, d))},
           "wu": w((n_layers, d, f), d ** -0.5),
           "wd": w((n_layers, f, d), f ** -0.5)}
    if act == "swiglu":
        mlp["wg"] = w((n_layers, d, f), d ** -0.5)
    return {"embed": w((v, d), 0.02),
            "unembed": w((d, v), d ** -0.5),
            "final_norm": {"scale": scale((d,))},
            "stack": {"p0": {"attn": attn, "mlp": mlp}}}


def init_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """The seeded weights of configuration ``m``, drawn on the default
    device in one jitted call."""
    return _init(_key(seed), _sizes(m), dtype)


# ------------------------------------------------------------ reference ----

def _quantize(w, contract_axes: Sequence[int], quant: Optional[str]):
    """Round float32 ``w`` per output channel (absmax over the contracted
    axes) to ``quant`` and back; ``None`` leaves it."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=tuple(contract_axes), keepdims=True)
    amax = jnp.maximum(amax, 1e-30)
    if quant == "int8":
        return jnp.clip(jnp.round(w / amax * 127.0), -127, 127) * (amax / 127.0)
    if quant == "fp8":
        s = amax / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quant {quant!r}")


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, T, H, hd) at positions 0..T-1, rotating the two halves."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def weight(a, layer, axes=None, quant=None):
    """Layer ``layer`` of a stacked weight, in float32; with ``axes`` (the
    contracted axes) rounded to ``quant`` per output channel."""
    a = jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
    a = a.astype(jnp.float32)
    return a if axes is None else _quantize(a, axes, quant)


def attention(at, x, layer, eps, theta, quant):
    """``x`` plus layer ``layer``'s grouped-query causal attention over x:
    (B, T, d); ``at`` holds the stacked attention weights."""
    b, t, _ = x.shape
    xn = _rms(x, weight(at["norm"]["scale"], layer), eps)
    q = jnp.einsum("btd,dhk->bthk", xn, weight(at["wq"], layer, (0,), quant),
                   precision=HI)
    k = jnp.einsum("btd,dhk->bthk", xn, weight(at["wk"], layer, (0,), quant),
                   precision=HI)
    v = jnp.einsum("btd,dhk->bthk", xn, weight(at["wv"], layer, (0,), quant),
                   precision=HI)
    q, k = _rope(q, theta), _rope(k, theta)
    nh, nk, hd = q.shape[2], k.shape[2], q.shape[3]
    q = q.reshape(b, t, nk, nh // nk, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v, precision=HI)
    o = o.reshape(b, t, nh, hd)
    return x + jnp.einsum("bthk,hkd->btd", o,
                          weight(at["wo"], layer, (0, 1), quant), precision=HI)


def mlp(ml, x, layer, act, eps, quant):
    """``x`` plus layer ``layer``'s SwiGLU or squared-ReLU MLP."""
    xn = _rms(x, weight(ml["norm"]["scale"], layer), eps)
    u = jnp.einsum("btd,df->btf", xn, weight(ml["wu"], layer, (0,), quant),
                   precision=HI)
    if act == "swiglu":
        g = jnp.einsum("btd,df->btf", xn, weight(ml["wg"], layer, (0,), quant),
                       precision=HI)
        u = jax.nn.silu(g) * u
    elif act == "squared_relu":
        u = jnp.square(jax.nn.relu(u))
    else:
        raise ValueError(f"unknown activation {act!r}")
    return x + jnp.einsum("btf,fd->btd", u, weight(ml["wd"], layer, (0,), quant),
                          precision=HI)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer(stack, x, layer, act, eps, theta, quant):
    """One decoder layer in float32 over x: (B, T, d)."""
    x = attention(stack["p0"]["attn"], x, layer, eps, theta, quant)
    return mlp(stack["p0"]["mlp"], x, layer, act, eps, quant)


def dense_stack(params, m: dict, x, quant=None):
    """Every layer of the dense decoder over x: (B, T, d), one at a time."""
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    for layer in range(m["num_layers"]):
        x = _layer(params["stack"], x, layer, m["activation"], eps, theta,
                   quant)
    return x


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def vocab_chunks(v: int, most: int = 65536) -> int:
    """Fewest equal chunks of at most ``most`` columns that split ``v``."""
    n = -(-v // most)
    while v % n:
        n += 1
    return n


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(final_scale, unembed, x, out_pos, eps, quant):
    """Logits (B, P, V) at positions ``out_pos`` (B, P).  The head is made
    float32 (and rounded, for the control) one vocabulary chunk at a time
    in a loop, so that no float32 copy of the whole head is held."""
    h = jnp.take_along_axis(x, out_pos[:, :, None], axis=1)
    h = _rms(h, final_scale, eps)
    v = unembed.shape[1]
    c = v // vocab_chunks(v)

    def body(i, out):
        w = jax.lax.dynamic_slice_in_dim(unembed, i * c, c, axis=1)
        w = _quantize(w.astype(jnp.float32), (0,), quant)
        part = jnp.einsum("bpd,dv->bpv", h, w, precision=HI)
        return jax.lax.dynamic_update_slice_in_dim(out, part, i * c, axis=2)

    out = jnp.zeros(h.shape[:2] + (v,), jnp.float32)
    return jax.lax.fori_loop(0, v // c, body, out)


def forward_logits(params, m: dict, tokens, out_pos, quant=None,
                   stack=dense_stack):
    """Reference logits (B, P, V) float32 at ``out_pos`` of the
    right-padded ``tokens`` (B, T); causal, so padding never reaches a
    position before it.  ``stack(params, m, x, quant)`` runs the layers."""
    x = _embed(params["embed"], tokens)
    x = stack(params, m, x, quant)
    return _head(params["final_norm"]["scale"], params["unembed"], x, out_pos,
                 float(m["norm_eps"]), quant)


@jax.jit
def _gaps(logits, chosen, valid):
    """Per position: the reference's best logit minus the chosen token's."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return jnp.where(valid, best - got, 0.0)


def pack(seqs: Sequence[Tuple[Sequence[int], Sequence[int]]], t_pad: int,
         p_pad: int):
    """Right-pad (prompt, served tokens) pairs: input tokens (B, t_pad),
    positions predicting each served token (B, p_pad), the served tokens
    and a validity mask."""
    b = len(seqs)
    toks = np.zeros((b, t_pad), np.int32)
    pos = np.zeros((b, p_pad), np.int32)
    served = np.zeros((b, p_pad), np.int32)
    valid = np.zeros((b, p_pad), bool)
    for i, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        assert len(seq) <= t_pad and len(out) <= p_pad, (len(seq), len(out))
        toks[i, :len(seq)] = seq
        pos[i, :len(out)] = len(prompt) - 1 + np.arange(len(out))
        served[i, :len(out)] = out
        valid[i, :len(out)] = True
    return toks, pos, served, valid


def served_gaps(params, m: dict, seqs, t_pad: int, p_pad: int,
                controls: Sequence[str] = (), block: int = 4,
                stack=dense_stack) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served token of ``seqs``.  For each of
    ``controls``, also the widest gap of the tokens that the reference
    computed with weights rounded to that type puts first, at the same
    positions.  The reference runs over ``block`` requests at a time, its
    layers by ``stack`` (see ``forward_logits``)."""
    out = {"served": 0.0, "tokens": 0, "control": {q: 0.0 for q in controls}}
    for i in range(0, len(seqs), block):
        toks, pos, served, valid = pack(seqs[i:i + block], t_pad, p_pad)
        toks, pos, served_d, valid_d = (jnp.asarray(a) for a in
                                        (toks, pos, served, valid))
        ref = forward_logits(params, m, toks, pos, stack=stack)
        out["served"] = max(out["served"], float(jnp.max(
            _gaps(ref, served_d, valid_d))))
        out["tokens"] += int(valid.sum())
        for q in controls:
            ctl = forward_logits(params, m, toks, pos, quant=q,
                                 stack=stack)
            first = jnp.argmax(ctl, axis=-1).astype(jnp.int32)
            del ctl
            out["control"][q] = max(out["control"][q], float(jnp.max(
                _gaps(ref, first, valid_d))))
        del ref
    return out
