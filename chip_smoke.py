"""Serve phi4-mini at its published widths through the engine backend on one
TPU chip, and check what comes out.

    python chip_smoke.py

One process drives every phase and starts no other; it exits non-zero
before any work when JAX finds no TPU.  The phases:

1. seeded random bf16 weights for ``phi4-mini-3.8b`` (32 layers, d_model
   3072, vocab 200064), drawn on the device under ``jit``;
2. the ``cache-pressure-70b`` scenario's request stream (two decode
   workers, Zipf-skewed prompt templates) through the engine backend, once
   with the dense Pallas decode kernel and flooded (batched prefill,
   continuous decode), once with the paged Pallas kernel and serialized;
3. after each run, one fixed ragged decode batch admitted through the
   engine's own admit path: the step logits of the kernel's decode step
   against the XLA path of the same family (``sdpa`` / ``paged_sdpa``) on
   the same chip.  The largest absolute difference must stay below
   ``LOGIT_TOL`` times the spread of the XLA logits, the bound the
   repository's CPU parity tests use for the same comparison.

Earlier lines report widths, parameter count, compile seconds, each
request's TTFT and token count, the logit differences and peak HBM.  The
last line is one JSON object naming the device.
"""
from __future__ import annotations

import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "phi4-mini-3.8b"
SCENARIO = "cache-pressure-70b"
SEED = 0
NUM_REQUESTS = 8
SLOTS_PER_WORKER = 4
# max |kernel - XLA| over (max - min) of the XLA step logits: bf16 rounding
# of the attention output compounds through the residual stack, while a
# masking or layout bug moves logits by the scale of the spread itself
LOGIT_TOL = 0.02
REFERENCE_IMPL = {"pallas": "sdpa", "paged": "paged_sdpa"}


def check_step_logits(model, params, dec, prefill, prompts,
                      log=print) -> dict:
    """Admit ``prompts`` (one per slot, truncated to ragged lengths) into
    the idle decoder ``dec`` and compare one decode step of its kernel
    impl with the XLA impl of the same family on that state.

    Returns ``kernel_in_step`` (the compiled kernel step holds a Mosaic
    custom call), ``max_abs_diff``, ``spread`` and ``ok``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    outs = prefill.prefill_many([(p, None, None) for p in prompts])
    for slot, (prompt, (logits, caches, row)) in enumerate(
            zip(prompts, outs)):
        # a prefix of a prompt is a valid prompt: its K/V rows are the
        # first ``n`` rows of the full pass (causal attention)
        n = len(prompt) - slot * (len(prompt) // len(prompts) + 1)
        dec.admit(slot, f"check{slot}", caches, int(np.argmax(logits)),
                  prompt_len=n, max_new=1, hashes=(), src_row=row)
    args = (params, dec.caches, jnp.asarray(dec.tokens),
            jnp.asarray([s.length for s in dec.slots], jnp.int32))
    kw = {"page_table": jnp.asarray(dec.page_table)} if dec.paged else {}
    got = want = None
    kernel_in_step = False
    for impl in (dec.decode_impl, REFERENCE_IMPL[dec.decode_impl]):
        step = jax.jit(functools.partial(model.decode, decode_impl=impl))
        compiled = step.lower(*args, **kw).compile()
        logits = np.asarray(compiled(*args, **kw)[0])
        if impl == dec.decode_impl:
            got = logits
            kernel_in_step = "tpu_custom_call" in compiled.as_text()
        else:
            want = logits
    for slot in range(len(prompts)):
        dec.release(slot)
    diff = float(np.abs(got - want).max())
    spread = float(want.max() - want.min())
    ok = bool(np.isfinite(got).all()) and diff <= LOGIT_TOL * spread
    log(f"[{dec.decode_impl}] step logits vs {REFERENCE_IMPL[dec.decode_impl]}"
        f": lengths={[int(x) for x in args[3]]} max_abs_diff={diff!r} "
        f"spread={spread!r} tol={LOGIT_TOL * spread!r} "
        f"kernel_in_step={kernel_in_step}")
    return dict(kernel_in_step=kernel_in_step, max_abs_diff=diff,
                spread=spread, ok=ok)


def serve(cfg, *, prompt_tokens: int = 512, output_tokens: int = 32,
          log=print) -> dict:
    """Serve ``cfg`` with seeded random weights through the engine backend,
    once per decode kernel, and check each kernel's step logits.

    Returns a report: ``params``, ``init_s`` and, per decode impl,
    ``compile_s``, ``run_s``, ``requests`` (id, TTFT, token count) and the
    ``check`` dict of :func:`check_step_logits`.  Raises when a request
    is missing or an output is malformed."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.serving.engine_backend import EngineScenarioRunner
    from repro.serving.scenarios import get_scenario

    model = build_model(cfg)
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
        f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init(jax.random.PRNGKey(SEED), jnp.bfloat16))
    init_s = time.perf_counter() - t0
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"params: {n_params} bf16, drawn on {jax.devices()[0].platform} in "
        f"{init_s!r} s (compile included)")
    report = {"params": n_params, "init_s": init_s}
    for impl, serialize in (("pallas", False), ("paged", True)):
        runner = EngineScenarioRunner(
            get_scenario(SCENARIO, input_tokens=prompt_tokens), seed=SEED,
            model=model, params=params, num_requests=NUM_REQUESTS,
            input_tokens=prompt_tokens, output_tokens=output_tokens,
            slots_per_worker=SLOTS_PER_WORKER, serialize=serialize,
            warmup=False, decode_impl=impl)
        cl = runner.cluster
        log(f"[{impl}] scenario={SCENARIO} decode_workers={len(cl.decoders)} "
            f"slots_per_worker={SLOTS_PER_WORKER} requests={NUM_REQUESTS} "
            f"serialize={serialize} max_len={cl.prefill.max_len}")
        t0 = time.perf_counter()
        runner.warmup()
        compile_s = time.perf_counter() - t0
        log(f"[{impl}] warmup (compile) {compile_s!r} s")
        t0 = time.perf_counter()
        res = runner.run()
        run_s = time.perf_counter() - t0
        if len(res.requests) != NUM_REQUESTS:
            raise RuntimeError(f"[{impl}] {len(res.requests)} of "
                               f"{NUM_REQUESTS} requests completed")
        rows = []
        for r in sorted(res.requests, key=lambda r: int(r.request_id[1:])):
            log(f"[{impl}] {r.request_id} worker={r.worker} "
                f"prompt={len(r.tokens)} ttft_s={r.ttft!r} "
                f"tokens={len(r.output)}")
            if len(r.output) != r.max_new_tokens + 1 or not all(
                    0 <= t < cfg.vocab_size for t in r.output):
                raise RuntimeError(f"[{impl}] malformed output for "
                                   f"{r.request_id}: {r.output}")
            rows.append((r.request_id, r.ttft, len(r.output)))
        st = res.prefill_stats
        log(f"[{impl}] run {run_s!r} s: prefill batches={st['batches']} "
            f"batched_requests={st['batched_requests']} "
            f"reused_blocks={st['reused_blocks']}")
        dec = cl.decoders[0]
        prompts = [list(s.tokens) for s in runner.specs[:dec.num_slots]]
        check = check_step_logits(model, params, dec, cl.prefill, prompts,
                                  log=log)
        report[impl] = dict(compile_s=compile_s, run_s=run_s,
                            requests=rows, check=check)
        del runner, cl, dec, res
        gc.collect()
    return report


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {backend!r}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    report = serve(get_config(MODEL),
                   log=functools.partial(print, flush=True))
    for impl in REFERENCE_IMPL:
        check = report[impl]["check"]
        if not check["kernel_in_step"]:
            raise RuntimeError(f"{impl}: no Mosaic kernel in the compiled "
                               "decode step")
        if not check["ok"]:
            raise RuntimeError(f"{impl}: step logits off the XLA path by "
                               f"{check['max_abs_diff']!r} (spread "
                               f"{check['spread']!r})")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use={peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
