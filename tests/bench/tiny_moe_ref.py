"""Architecture module of a routed-expert decoder, as a configuration
brings it: copied into a benchmark tree as ``bench/tiny_moe_ref.py`` and
named by ``"reference": "tiny_moe_ref"`` (a helper of the benchmark's
tests, not a test file).

Every layer is grouped-query attention followed by a mixture of experts:
RMSNorm, router logits over ``num_experts``, the ``top_k`` largest, a
softmax over the chosen logits, SwiGLU experts and the weighted sum of
their outputs.  Weights in the served layout ``stack.p0.{attn, moe}``;
the reference in float32 at ``Precision.HIGHEST``, every expert computed
and the unchosen ones weighted 0; the work of a step counts the ``top_k``
experts a token runs through."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import model_ref
from bench.flops import attention_bytes, attention_flops  # noqa: F401
from bench.model_ref import HI, QUANTS, weight  # noqa: F401

TOP_K = None     # None: the configuration's ``moe.top_k``


def _sizes(m: dict):
    e = m["moe"]
    return (m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], e["num_experts"], e["d_ff_expert"],
            m["vocab_size"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, sizes, dtype):
    n, d, h, k, hd, ne, f, v = sizes
    ks = iter(jax.random.split(key, 16))

    def w(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale
                ).astype(dtype)

    def scale(shape):
        return (1.0 + model_ref.NORM_SCALE_STD
                * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    attn = {"norm": {"scale": scale((n, d))},
            "wq": w((n, d, h, hd), d ** -0.5),
            "wk": w((n, d, k, hd), d ** -0.5),
            "wv": w((n, d, k, hd), d ** -0.5),
            "wo": w((n, h, hd, d), (h * hd) ** -0.5)}
    moe = {"norm": {"scale": scale((n, d))},
           "wr": w((n, d, ne), d ** -0.5),
           "wu": w((n, ne, d, f), d ** -0.5),
           "wg": w((n, ne, d, f), d ** -0.5),
           "wd": w((n, ne, f, d), f ** -0.5)}
    return {"embed": w((v, d), 0.02),
            "unembed": w((d, v), d ** -0.5),
            "final_norm": {"scale": scale((d,))},
            "stack": {"p0": {"attn": attn, "moe": moe}}}


def init_params(m: dict, seed: int, dtype=jnp.bfloat16):
    return _init(model_ref._key(seed), _sizes(m), dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer(stack, x, layer, top_k, eps, theta, quant):
    x = model_ref.attention(stack["p0"]["attn"], x, layer, eps, theta, quant)
    mo = stack["p0"]["moe"]
    xn = model_ref._rms(x, weight(mo["norm"]["scale"], layer), eps)
    logits = jnp.einsum("btd,de->bte", xn, weight(mo["wr"], layer, (0,), quant),
                        precision=HI)
    top, idx = jax.lax.top_k(logits, top_k)
    gate = jax.nn.softmax(top, axis=-1)
    share = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1]) * gate[..., None],
                    axis=-2)                                    # (B, T, E)
    u = jnp.einsum("btd,edf->btef", xn, weight(mo["wu"], layer, (1,), quant),
                   precision=HI)
    g = jnp.einsum("btd,edf->btef", xn, weight(mo["wg"], layer, (1,), quant),
                   precision=HI)
    y = jnp.einsum("btef,efd->bted", jax.nn.silu(g) * u,
                   weight(mo["wd"], layer, (1,), quant), precision=HI)
    return x + jnp.einsum("bte,bted->btd", share, y, precision=HI)


def moe_stack(params, m: dict, x, quant=None):
    top_k = TOP_K or m["moe"]["top_k"]
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    for layer in range(m["num_layers"]):
        x = _layer(params["stack"], x, layer, top_k, eps, theta, quant)
    return x


served_gaps = functools.partial(model_ref.served_gaps, stack=moe_stack)


def matmul_params_per_token(m: dict) -> int:
    """Attention, the router over every expert, ``top_k`` SwiGLU experts
    and the head."""
    d, h, k, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    e = m["moe"]
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    moe = d * e["num_experts"] + e["top_k"] * 3 * d * e["d_ff_expert"]
    return m["num_layers"] * (attn + moe) + d * m["vocab_size"]


def decode_step_flops(m: dict, lengths) -> float:
    return 2.0 * matmul_params_per_token(m) * len(lengths) \
        + attention_flops(m, lengths)
