"""Kernel micro-benchmarks: flash / decode / paged attention vs their jnp
oracles.

Default mode times the Pallas kernels in interpret mode (CPU wall-time —
a correctness-adjacent smoke number, not a speed claim).  ``--compiled``
adds real compiled-kernel rows (``interpret=False``); it requires a TPU
backend and exits non-zero anywhere else.

Schema (``reports/benchmarks/bench_kernels.json``): per kernel,
``ref_us`` (jitted jnp oracle), ``pallas_interpret_us``, and with
``--compiled`` also ``pallas_compiled_us`` — plus a work descriptor
(``flops`` / ``kv_bytes``).

    PYTHONPATH=src python -m benchmarks.bench_kernels [--compiled]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_json
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(compiled: bool = False):
    if compiled and jax.default_backend() != "tpu":
        raise SystemExit(f"--compiled needs a TPU; JAX found "
                         f"{jax.default_backend()!r}")

    results = {}
    b, s, h, kh, hd = 1, 512, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)

    t_ref = _time(jax.jit(lambda *a: flash_attention_ref(*a)), q, k, v)
    t_pal = _time(lambda *a: flash_attention(*a, interpret=True), q, k, v)
    flops = 4 * b * s * s * h * hd / 2  # causal
    results["flash_attention"] = dict(ref_us=t_ref, pallas_interpret_us=t_pal,
                                      flops=flops)
    if compiled:
        results["flash_attention"]["pallas_compiled_us"] = _time(
            lambda *a: flash_attention(*a, interpret=False), q, k, v)
    emit("bench_flash_attention", t_pal,
         f"ref_us={t_ref:.0f};causal_gqa_{s}x{s}x{h}h")

    t = 2048
    q1 = jax.random.normal(ks[0], (8, h, hd), jnp.float32)
    k1 = jax.random.normal(ks[1], (8, t, kh, hd), jnp.float32)
    v1 = jax.random.normal(ks[2], (8, t, kh, hd), jnp.float32)
    lengths = jnp.full((8,), t, jnp.int32)
    t_ref = _time(jax.jit(lambda *a: decode_attention_ref(*a)), q1, k1, v1,
                  lengths)
    t_pal = _time(lambda *a: decode_attention(*a, interpret=True), q1, k1, v1,
                  lengths)
    kv_bytes = 2 * 8 * t * kh * hd * 4
    results["decode_attention"] = dict(ref_us=t_ref,
                                       pallas_interpret_us=t_pal,
                                       kv_bytes=kv_bytes)
    if compiled:
        results["decode_attention"]["pallas_compiled_us"] = _time(
            lambda *a: decode_attention(*a, interpret=False), q1, k1, v1,
            lengths)
    emit("bench_decode_attention", t_pal,
         f"ref_us={t_ref:.0f};kv_bytes={kv_bytes}")

    # paged decode: 8 sequences reading scattered 16-token pages from a
    # shared pool (the serving path's KV layout)
    bp, block, pages, per_seq = 8, 16, 128, 8
    q2 = jax.random.normal(ks[0], (bp, h, hd), jnp.float32)
    k2 = jax.random.normal(ks[1], (pages, block, kh, hd), jnp.float32)
    v2 = jax.random.normal(ks[2], (pages, block, kh, hd), jnp.float32)
    table = jax.random.permutation(
        jax.random.PRNGKey(7), pages)[: bp * per_seq].reshape(bp, per_seq)
    table = table.astype(jnp.int32)
    plen = jnp.full((bp,), block * per_seq, jnp.int32)
    t_ref = _time(jax.jit(lambda *a: paged_attention_ref(*a)), q2, k2, v2,
                  table, plen)
    t_pal = _time(lambda *a: paged_attention(*a, interpret=True), q2, k2, v2,
                  table, plen)
    paged_bytes = 2 * bp * per_seq * block * kh * hd * 4
    results["paged_attention"] = dict(ref_us=t_ref,
                                      pallas_interpret_us=t_pal,
                                      kv_bytes=paged_bytes)
    if compiled:
        results["paged_attention"]["pallas_compiled_us"] = _time(
            lambda *a: paged_attention(*a, interpret=False), q2, k2, v2,
            table, plen)
    emit("bench_paged_attention", t_pal,
         f"ref_us={t_ref:.0f};kv_bytes={paged_bytes}")

    results["compiled"] = compiled
    save_json("bench_kernels", results)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compiled", action="store_true",
                    help="also time interpret=False Pallas kernels "
                         "(TPU only; fails elsewhere)")
    args = ap.parse_args()
    run(compiled=args.compiled)


if __name__ == "__main__":
    main()
