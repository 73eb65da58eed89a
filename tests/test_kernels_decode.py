"""Pallas decode-attention kernel vs oracle: shape/dtype/length sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_stacked)
from repro.kernels.decode_attention.ref import decode_attention_ref

SHAPES = [
    # (b, t, h, kh, hd)
    (2, 128, 4, 2, 64),
    (1, 512, 8, 8, 128),
    (3, 96, 8, 2, 32),     # padding path
    (2, 256, 8, 1, 64),    # MQA
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_matches_ref(shape, dtype):
    b, t, h, kh, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kh, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kh, hd), jnp.float32).astype(dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, t + 1)
    out = decode_attention(q, k, v, lengths, blk_k=64, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=atol, rtol=atol)


def test_decode_length_one_returns_v0():
    b, t, h, hd = 2, 128, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, t, h, hd))
    v = jax.random.normal(ks[2], (b, t, h, hd))
    out = decode_attention(q, k, v, jnp.ones((b,), jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v[:, 0]), atol=1e-5)


def test_decode_block_size_invariance():
    b, t, h, kh, hd = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, t, kh, hd))
    v = jax.random.normal(ks[2], (b, t, kh, hd))
    lengths = jnp.asarray([100, 256], jnp.int32)
    o1 = decode_attention(q, k, v, lengths, blk_k=32, interpret=True)
    o2 = decode_attention(q, k, v, lengths, blk_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_decode_agrees_with_flash_last_row():
    """Decode of the last position == flash attention's last row."""
    from repro.kernels.flash_attention.ops import flash_attention
    b, s, h, kh, hd = 1, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kh, hd))
    v = jax.random.normal(ks[2], (b, s, kh, hd))
    full = flash_attention(q, k, v, causal=True, interpret=True)
    dec = decode_attention(q[:, -1], k, v, jnp.full((b,), s, jnp.int32),
                           interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               atol=1e-5)


@pytest.mark.parametrize("layers", [1, 3])
def test_stacked_entry_reads_one_layer(layers):
    """The stacked-cache entry at layer ``l`` is the per-layer entry on
    ``k[l]``, ``v[l]``, bit for bit: a cache not a multiple of the 256-row
    block, and rows of length 0, 1 and T."""
    b, t, h, kh, hd = 3, 1156, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(layers), 3)
    q = jax.random.normal(ks[0], (b, h, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (layers, b, t, kh, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (layers, b, t, kh, hd)).astype(jnp.bfloat16)
    lengths = jnp.asarray([0, 1, t], jnp.int32)
    for l in range(layers):
        out = decode_attention_stacked(q, k, v, lengths, jnp.int32(l),
                                       interpret=True)
        one = decode_attention(q, k[l], v[l], lengths, interpret=True)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(one, np.float32))
        assert not np.asarray(out[0]).any()          # length 0: zeros
