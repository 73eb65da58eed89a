"""The benchmark's harness end to end on the CPU at a tiny size: the device
check, a sound run, planted faults, the control, a cell added as new files
only, and a configuration that brings its own architecture as new files."""
from __future__ import annotations

import hashlib
import json
import shutil
import time

import pytest

from benchtest_common import (ROOT, StubChip, TINY_CLOSED, TINY_MODEL,
                              TINY_SESSIONS, TINY_UNSHARED, harness, run_tiny,
                              tiny_config)

from bench import faults, loadgen


def test_device_check_refuses_the_cpu(capsys):
    with pytest.raises(harness.NoChip):
        harness.check_device(1)
    rc = harness.main(["--workload", "phi4mini-sessions-8c", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], time.monotonic())
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    for cell in bench["workloads"]:
        _, c, cfg, mix = harness.load_cell(ROOT, cell["name"])
        assert cfg["name"] == c["config"]
        assert mix["loop"] in ("open", "closed")
        e2e, layer = harness.cell_metrics(bench, cell["name"])
        assert any(m["name"] == "setup_s" for m in e2e) and len(e2e) >= 2
        assert layer
        for m in layer:
            assert hasattr(harness.reader(m["name"]), "read")
            assert m["moves"] in {x["name"] for x in e2e}
    for conf in bench["configs"]:
        cfg = harness.load_json(ROOT / conf["file"])
        assert cfg["reduced"] == conf["reduced"]
        assert cfg["correct"]["max_logit_gap"] > 0


def test_sound_run_is_correct_with_end_to_end_metrics():
    res = run_tiny("phi4mini-sessions-8c", TINY_CLOSED, tiny_config())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p50_ms", "itl_p99_ms",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"


def test_control_is_not_correct():
    """The reference with fp8 weights, judged in the program's place."""
    res = run_tiny("phi4mini-sessions-8c", TINY_CLOSED, tiny_config(),
                   control=("fp8",))
    assert not res["correct"], res["checks"]
    checks = res["checks"]
    assert checks["program_max_logit_gap"]["value"] <= \
        checks["max_logit_gap"]["limit"] < checks["max_logit_gap"]["value"]
    assert checks["fp8_max_logit_gap"] == checks["max_logit_gap"]


def test_control_list_parses_and_refuses_unknown_types():
    assert harness.parse_args(["--workload", "w", "--seed", "1", "--seconds",
                               "1", "--control", "fp8,int8"]).control == \
        ("fp8", "int8")
    assert harness.parse_args(["--workload", "w", "--seed", "1",
                               "--seconds", "1"]).control == ()
    # the configuration's architecture module lists the types, and a run
    # refuses any other before it draws a weight
    with pytest.raises(SystemExit, match="fp4"):
        run_tiny("phi4mini-sessions-8c", TINY_CLOSED, tiny_config(),
                 control=("fp4",))


def test_traced_run_reports_per_layer_metrics():
    seen = []
    res = run_tiny("phi4mini-sessions-8c", TINY_UNSHARED, tiny_config(),
                   trace=True, seconds=4.0, fault=seen.append)
    assert res["correct"]
    # no reader of the cell asks for the program's spans: its tracer stays off
    assert not seen[0].tracer.enabled and seen[0].tracer.spans == []
    got = set(res["metrics"])
    assert {"route_ms_mean", "prefix_hit_share",
            "prefill_ms_per_ktok", "decode_step_ms",
            "decode_step_mfu"} <= got
    # no TPU plane in a CPU trace: the device readers find nothing
    assert "decode_attn_roofline" not in got
    assert res["metrics"]["prefix_hit_share"]["value"] == 0.0


def test_closed_loop_run():
    mix = dict(TINY_SESSIONS, loop="closed", clients_per_slot=2)
    res = run_tiny("minitron-sessions-closed", mix, tiny_config("minitron-4b"))
    assert res["correct"]
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    res = run_tiny("phi4mini-sessions-8c", TINY_CLOSED, tiny_config(),
                   fault=faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


def _sha(paths):
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest() for p in paths}


def _bench_files():
    return sorted(p for p in (ROOT / "bench").rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts)


def test_new_cell_config_mix_and_metric_as_files_only(tmp_path):
    """A later change adds a cell with its own configuration, traffic mix
    and per-layer metric by adding files; no existing file changes."""
    existing = _bench_files()
    before = _sha(existing)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cfg = tiny_config("minitron-4b")
    cfg["name"] = "tiny-minitron"
    (tmp_path / "bench/configs/tiny-minitron.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/tiny-unshared.json").write_text(
        json.dumps(TINY_UNSHARED))
    (tmp_path / "bench/metrics/tokens_done.py").write_text(
        '"""Requests finished in the window (count)."""\n\n\n'
        "def read(ctx, variant=None):\n"
        "    w0, w1 = ctx['window']\n"
        "    return float(sum(1 for t in ctx['tracks'] if t.done"
        " and w0 <= t.due < w1))\n")
    bench["workloads"].append({"name": "tiny-cell", "config": "tiny-minitron",
                               "traffic": "tiny-unshared", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "tokens_done.tiny", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "ttft_p90_ms",
                               "workloads": ["tiny-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b, cell, cfgdoc, mix = harness.load_cell(tmp_path, "tiny-cell")
    assert mix == TINY_UNSHARED and cfgdoc["name"] == "tiny-minitron"
    res = harness.run_cell(b, cell, cfgdoc, mix, seed=5, seconds=2.0,
                           trace=True, t_start=time.monotonic(),
                           devs=[StubChip()], drain_s=20.0,
                           bench_dir=tmp_path / "bench")
    assert res["correct"]
    assert set(res["metrics"]) == {"tokens_done.tiny"}
    assert res["metrics"]["tokens_done.tiny"]["value"] > 0
    assert _sha(existing) == before
    assert loadgen.load_mix("tiny-unshared", tmp_path / "bench" / "traffic")


# Every token goes to all four experts, weighted by the softmax of their
# router logits; capacity factor E / k gives each expert the token count,
# so none is dropped.  With 2 of 4 the program's bfloat16 router flips a
# near-tied choice now and then, and the widest gap of a sound run then
# reads up to 1.8 (CPU, 9 seeds); with 4 of 4 sound runs read 0-0.042 and
# the top-1 variant 3.46-3.87 (CPU, 5 seeds), on either side of the limit.
TINY_MOE = {"num_experts": 4, "top_k": 4, "d_ff_expert": 64,
            "capacity_factor": 1.0}

PROGRAM_READER = '''"""Cluster tick: prompt passes (the program's ``prefill.pass`` spans)
and scheduler ticks (its ``ticks_total`` counter) in the window."""
NEEDS = ("program",)


def read(ctx, variant=None):
    w0, w1 = ctx["window"]
    if variant == "passes":
        return float(len(ctx["program"].within("prefill.pass", w0, w1)))
    a, b = ctx["counters"]
    return b["ticks_total"] - a["ticks_total"]
'''


@pytest.mark.parametrize("top_k,correct", [(None, True), (1, False)],
                         ids=["own-reference", "top1-reference"])
def test_new_architecture_as_files_only(tmp_path, top_k, correct):
    """A routed-expert configuration brings its architecture module
    (weights in ``stack.p0.{attn, moe}``, a float32 MoE reference, work
    counts of its top-k experts) and a reader of the program's own spans
    and counters, as new files; no existing file changes.  The same module
    routing each token to one expert judges the same program not correct:
    the configuration's reference is what judges."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.model import Model

    existing = _bench_files()
    before = _sha(existing)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = (ROOT / "tests" / "bench" / "tiny_moe_ref.py").read_text()
    if top_k is not None:
        assert "TOP_K = None" in src
        src = src.replace("TOP_K = None", f"TOP_K = {top_k}")
    (tmp_path / "bench/tiny_moe_ref.py").write_text(src)
    (tmp_path / "bench/metrics/program_reads.py").write_text(PROGRAM_READER)
    cfg = tiny_config()
    cfg.update(name="tiny-moe", registry="qwen3-moe-30b-a3b",
               reference="tiny_moe_ref")
    cfg["model"] = dict(TINY_MODEL, activation="swiglu", rope_theta=10000.0,
                        norm_eps=1e-5, moe=dict(TINY_MOE))
    (tmp_path / "bench/configs/tiny-moe.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/tiny-closed.json").write_text(
        json.dumps(TINY_CLOSED))
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    bench["workloads"].append({"name": "tiny-moe-cell", "config": "tiny-moe",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    for name in ("program_reads.passes", "program_reads.ticks",
                 "decode_step_mfu.tiny"):
        bench["per_layer"].append({"name": name, "unit": "count",
                                   "better": "higher", "source": "program_span",
                                   "layer": "cluster tick",
                                   "moves": "ttft_p90_ms",
                                   "workloads": ["tiny-moe-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b, cell, cfgdoc, mix = harness.load_cell(tmp_path, "tiny-moe-cell")

    arch = harness.architecture(cfgdoc, tmp_path / "bench")
    m = cfgdoc["model"]
    served = harness.model_config(get_config("qwen3-moe-30b-a3b"), m)
    assert served.moe.top_k == 4 and served.moe.capacity_factor == 1.0
    want = Model(served).init_abstract(jnp.bfloat16)
    got = jax.eval_shape(lambda: arch.init_params(m, 1))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(a.shape == w.shape and a.dtype == w.dtype for a, w in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    # a token multiplies through its top-k experts, not all of them
    for top_k in (4, 2, 1):
        mk = dict(m, moe=dict(TINY_MOE, top_k=top_k))
        active = harness.model_config(get_config("qwen3-moe-30b-a3b"), mk) \
            .active_param_count()
        assert arch.matmul_params_per_token(mk) == \
            active - m["vocab_size"] * m["d_model"]

    seen = []
    res = harness.run_cell(b, cell, cfgdoc, mix, seed=2**33 + 41,
                           seconds=2.0, trace=True, t_start=time.monotonic(),
                           devs=[StubChip()], drain_s=20.0, fault=seen.append,
                           bench_dir=tmp_path / "bench")
    assert res["correct"] is correct, res["checks"]
    assert seen[0].tracer.enabled
    assert set(res["metrics"]) == {"program_reads.passes",
                                   "program_reads.ticks",
                                   "decode_step_mfu.tiny"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert _sha(existing) == before


def test_configuration_without_reference_is_the_dense_module():
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    for conf in bench["configs"]:
        cfg = harness.load_json(ROOT / conf["file"])
        assert "reference" not in cfg
        arch = harness.architecture(cfg)
        assert arch.__file__ == str(ROOT / "bench" / "model_ref.py")
        for name in ("QUANTS", "init_params", "served_gaps",
                     "decode_step_flops", "attention_flops",
                     "attention_bytes"):
            assert hasattr(arch, name), name


@pytest.mark.parametrize("registry,model,error,named", [
    ("phi4-mini-3.8b", {"num_layer": 2}, KeyError, "num_layer"),
    ("qwen3-moe-30b-a3b", {"moe": {"num_expert": 4}}, KeyError, "num_expert"),
    ("phi4-mini-3.8b", {"moe": {"num_experts": 4}}, ValueError, "moe"),
])
def test_build_cluster_refuses_keys_the_registry_lacks(registry, model, error,
                                                       named):
    cfg = tiny_config()
    cfg["registry"] = registry
    cfg["model"] = dict(cfg["model"], **model)
    with pytest.raises(error, match=named):
        harness.build_cluster(cfg, None, 0)


def test_nested_configuration_fields_replace_the_registry_s():
    from repro.configs import get_config
    base = get_config("qwen3-moe-30b-a3b")
    cfg = harness.model_config(base, {"num_layers": 2, "moe": TINY_MOE})
    assert cfg.num_layers == 2 and cfg.moe.num_experts == 4
    assert cfg.moe.every_k_layers == base.moe.every_k_layers
    assert harness.model_config(base, {}) == base
