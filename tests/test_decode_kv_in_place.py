"""The dense decode step writes each row's new K/V into the donated cache in
place: its compiled program copies no stacked cache, rewrites no layer's
cache with a select, and aliases the cache parameters to the returned
cache.

The step is the engine's own (``DecodeEngine._decode``) for a 2-layer,
4-slot, 64-position attention-only model, compiled for the CPU.  It runs
the ``sdpa`` attention: the Pallas interpreter carries its operands through
a loop of its own, which copies them on the CPU only, so the ``pallas``
step is held to the same checks compiled for the chip, in
``test_tpu_compile.py``.  The path the carried cache replaced (each
layer's cache through the layer scan's ``xs``/``ys``) is compiled too, and
the checks must find its rewrite: they are known to bite.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.models.model import Model
from repro.serving.engine import DecodeEngine

SLOTS, MAX_LEN = 4, 64


def cache_rewrites(hlo: str, stacked_shape) -> list:
    """Instructions of a compiled decode step that rewrite the KV cache
    whole: a ``copy`` whose result has the stacked cache's shape
    ``(P, B, T, K, hd)``, or a ``select`` over one layer's ``(B, T, K,
    hd)`` (with or without leading unit dims), whatever the element
    type."""
    dims = [str(d) for d in stacked_shape]
    stacked = r"\[" + ",".join(dims) + r"\]"
    layer = r"\[(?:1,)*" + ",".join(dims[1:]) + r"\]"
    found = re.findall(rf"%\S+ = \w+{stacked}\S* copy\(", hlo)
    found += re.findall(rf"%\S+ = \w+{layer}\S* select\(", hlo)
    return found


def cache_aliased(hlo: str, n_params: int, n_cache: int) -> bool:
    """Each cache leaf (parameters ``n_params ..``) is aliased to the same
    leaf of the returned cache (outputs ``1 ..``, after the logits)."""
    head = hlo.split("\n", 1)[0]
    return all(re.search(rf"\{{{1 + j}\}}: \({n_params + j}, \{{\}}", head)
               for j in range(n_cache))


def _step(in_place: bool):
    cfg = dataclasses.replace(get_reduced("phi4-mini-3.8b"), num_layers=2)
    model = Model(cfg)
    if not in_place:
        model.kv_write_in_place = lambda impl: False
    params = model.init(jax.random.PRNGKey(0), jnp.bfloat16)
    eng = DecodeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                       decode_impl="sdpa")
    assert eng.kv_write_in_place == in_place
    hlo = eng._decode.lower(params, eng.caches, jnp.asarray(eng.tokens),
                            jnp.zeros((SLOTS,), jnp.int32)).compile().as_text()
    return hlo, params, eng.caches


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["carried", "xs_ys"])
def test_decode_step_writes_kv_in_place(in_place):
    hlo, params, caches = _step(in_place)
    leaves = jax.tree.leaves(caches)
    shape = leaves[0].shape
    assert shape[1:3] == (SLOTS, MAX_LEN)
    assert cache_aliased(hlo, len(jax.tree.leaves(params)), len(leaves))
    found = cache_rewrites(hlo, shape)
    if in_place:
        assert found == []
    else:   # the rebuilt stack is copied out, each layer's cache selected
        assert any(" copy(" in f for f in found), found
        assert any(" select(" in f for f in found), found
