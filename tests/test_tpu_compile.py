"""The TPU compiler accepts the Pallas kernels, and the decode steps that
hold them, at phi4-mini's published widths in bf16.

Each test compiles for one chip of a described ``v5e:2x2`` topology; no
chip is needed and nothing runs.  Interpret mode never applies the
Mosaic tiling rules, so these are the tests that catch a block shape the
chip would refuse.  The topology is described inside a fixture, never at
import, and every test of this kind stays in this one file: only one
process at a time may load the TPU library.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import build_model
from test_decode_kv_in_place import cache_aliased, cache_rewrites

CFG = get_config("phi4-mini-3.8b")
H, K, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
BATCH = 8
CACHE_LEN = 548          # not a multiple of the 256-row decode block
PAGE = 16
PAGES_PER_SLOT = -(-CACHE_LEN // PAGE)
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs outside
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:   # raised only when no TPU library loads
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    mp.undo()


def _compile(fn, *args, **kwargs):
    return jax.jit(fn, **kwargs).lower(*args).compile()


def _kernel_args(kernel, sds):
    if kernel == "decode":
        return (decode_attention, sds((BATCH, H, HD)),
                sds((BATCH, CACHE_LEN, K, HD)), sds((BATCH, CACHE_LEN, K, HD)),
                sds((BATCH,), jnp.int32))
    if kernel == "paged":
        n = BATCH * PAGES_PER_SLOT + 1
        return (paged_attention, sds((BATCH, H, HD)),
                sds((n, PAGE, K, HD)), sds((n, PAGE, K, HD)),
                sds((BATCH, PAGES_PER_SLOT), jnp.int32),
                sds((BATCH,), jnp.int32))
    s = 256
    return (functools.partial(flash_attention, causal=True),
            sds((2, s, H, HD)), sds((2, s, K, HD)), sds((2, s, K, HD)))


@pytest.mark.parametrize("kernel", ["decode", "paged", "flash"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    """Each kernel, called through its wrapper with the default
    ``interpret=None``, lowers to a Mosaic custom call for the chip."""
    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, *args = _kernel_args(kernel, sds)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("impl", ["pallas", "paged"])
def test_decode_step_compiles_for_v5e(one_chip, impl):
    """The served decode step (two layers at published widths) compiles
    with its kernel inside, for the slot batch the engine hands it; the
    dense step neither copies nor rewrites its cache whole."""
    cfg = dataclasses.replace(CFG, num_layers=2)
    model = build_model(cfg)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(model.init_abstract(BF16))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    args = [params, None, i32((BATCH, 1)), i32((BATCH,))]
    kw = {}
    if impl == "paged":
        args[1] = place(model.paged_cache_init(BATCH * PAGES_PER_SLOT, PAGE,
                                               abstract=True))
        kw["page_table"] = i32((BATCH, PAGES_PER_SLOT))
    else:
        args[1] = place(model.cache_init(BATCH, CACHE_LEN, abstract=True))
    step = functools.partial(model.decode, decode_impl=impl)
    compiled = jax.jit(step, donate_argnums=1).lower(*args, **kw).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    if impl == "pallas":   # each row's K/V written into the cache in place
        leaves = jax.tree.leaves(args[1])
        assert cache_rewrites(hlo, leaves[0].shape) == []
        assert cache_aliased(hlo, len(jax.tree.leaves(params)), len(leaves))
