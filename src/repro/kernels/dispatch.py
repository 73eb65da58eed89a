"""Compiled-or-interpreted dispatch shared by the kernel wrappers."""
from __future__ import annotations

import functools

import jax


def run_kernel(kernel, *args, interpret=None, **kwargs):
    """``kernel(*args, interpret=..., **kwargs)``: compiled by Mosaic where
    the enclosing program is lowered for a TPU, run by the Pallas
    interpreter on every other platform.  The choice is made per lowering
    platform rather than from ``jax.default_backend()``, so a program
    compiled for a TPU always holds the compiled kernel.  An explicit
    ``interpret`` overrides it."""
    if interpret is not None:
        return kernel(*args, interpret=interpret, **kwargs)
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **kwargs),
        default=functools.partial(kernel, interpret=True, **kwargs))
