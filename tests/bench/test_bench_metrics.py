"""Percentiles, end-to-end metrics and the trace reduction of the
benchmark, on known inputs."""
from __future__ import annotations

import json

import pytest

from benchtest_common import ROOT, harness

from bench import flops, loadgen, model_ref, peaks, stats, tracefile

FIXTURE = ROOT / "bench" / "fixtures" / "trace_small.json"


def test_percentile_is_nearest_rank_over_all_samples():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(vals, 90) == 90
    assert stats.percentile(vals, 99) == 99
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def _track(rid, due, times, done=True):
    req = loadgen.Req(rid, [0] * 128, len(times), due)
    return harness.Track(req, None, due, due, list(times), done)


def test_e2e_metrics_take_the_whole_window():
    w0, w1 = 10.0, 20.0
    tracks = [
        _track("warm", 9.0, [9.5, 10.5, 11.0]),            # due before w0
        _track("a", 10.0, [10.1, 10.2, 10.4]),
        _track("b", 15.0, [15.5, 15.6]),
        _track("late", 19.9, [], done=False),               # never served
        _track("after", 20.5, [20.6, 20.7]),                # due after w1
    ]
    ttft90 = harness.e2e_value("ttft_p90_ms", tracks, w0, w1, 80.0, 0.0)
    # three requests due in the window; the unserved one counts to the
    # drain deadline
    assert ttft90 == pytest.approx((80.0 - 19.9) * 1e3)
    ttft50 = harness.e2e_value("ttft_p50_ms", tracks, w0, w1, 80.0, 0.0)
    assert ttft50 == pytest.approx(500.0)
    gaps = sorted([1000.0, 500.0, 100.0, 200.0, 100.0])  # ending in the window
    itl50 = harness.e2e_value("itl_p50_ms", tracks, w0, w1, 80.0, 0.0)
    assert itl50 == pytest.approx(stats.percentile(gaps, 50))
    itl99 = harness.e2e_value("itl_p99_ms", tracks, w0, w1, 80.0, 0.0)
    assert itl99 == pytest.approx(1000.0)
    tok = harness.e2e_value("output_tok_s", tracks, w0, w1, 80.0, 0.0)
    assert tok == pytest.approx(7 / 10.0)
    assert harness.e2e_value("setup_s", tracks, w0, w1, 80.0, 3.5) == 3.5
    with pytest.raises(KeyError):
        harness.e2e_value("nonsense", tracks, w0, w1, 80.0, 0.0)


def _fixture():
    with open(FIXTURE) as f:
        return json.load(f)


def test_trace_busy_and_idle_on_the_recorded_trace():
    tr = _fixture()
    b = tracefile.busy(tr)
    assert b["window_s"] == pytest.approx(10000e-9)
    assert b["busy_s"] == pytest.approx(4500e-9)
    assert b["gaps"][0] == [(4000.0, 6000.0), (7000.0, 9000.0),
                            (9500.0, 11000.0)]


def test_trace_gap_labels_and_breakdown():
    bd = tracefile.breakdown(_fixture())
    assert [g[0] for g in bd["idle_gaps"]] == ["decode step", "generator wait",
                                               "other"]
    assert bd["idle_gaps"][0][1] == pytest.approx(2000e-9)
    ops = dict((k, v) for k, v in bd["device_ops"])
    assert ops["jit_decode(1)/fusion.1"] == pytest.approx(2000e-9)
    assert ops["jit_decode(1)/custom-call.7"] == pytest.approx(2000e-9)
    assert ops["jit__lambda(2)/fusion.3"] == pytest.approx(1000e-9)


def test_trace_kernel_seconds_and_idle_reader():
    tr = _fixture()
    assert tracefile.op_seconds(tr, r"^jit_decode", r"custom-call") == \
        pytest.approx(2000e-9)
    idle = harness.reader("device_idle_share").read({"trace": tr}, None)
    assert idle == pytest.approx(55.0)
    assert harness.reader("device_idle_share.closed").read({"trace": None},
                                                           "closed") is None


def test_roofline_reader_on_the_recorded_trace():
    """The decode-attention roofline: least time for the steps' work over
    the kernel's device time, the same work whichever kernel runs."""
    tr = _fixture()
    m = dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16)

    class Spans:
        def within(self, name, a, b):
            assert name == "decode_step"
            return [(1.0, 1.5, [100, 50]), (2.0, 2.5, []), (9.0, 9.5, [10])]

    ctx = {"trace": tr, "traced": (0.0, 5.0), "spans": Spans(), "model": m,
           "work": model_ref, "device_kind": "TPU v5 lite",
           "decode_kernels": {"module": "jit_decode", "ops": ["custom-call.7"]}}
    pk = peaks.peaks("TPU v5 lite")
    least = sum(flops.roofline_seconds(flops.attention_flops(m, lens),
                                       flops.attention_bytes(m, lens), pk)
                for lens in ([100, 50], [10]))
    got = harness.reader("decode_attn_roofline").read(ctx, None)
    assert got == pytest.approx(100.0 * least / 2000e-9)
    # a kernel taken off the path, or named otherwise, leaves it silent
    for kern in ({"module": "jit_decode", "ops": ["custom-call.8"]},
                 {"module": "jit_decod", "ops": ["custom-call.7"]},
                 {"module": "jit_decode", "ops": []}, None):
        assert harness.reader("decode_attn_roofline").read(
            dict(ctx, decode_kernels=kern), None) is None


class _Steps:
    """``decode_step`` spans with the live rows' lengths."""
    def within(self, name, a, b):
        assert name == "decode_step"
        return [s for s in [(1.0, 1.5, [100, 50]), (2.0, 2.5, []),
                            (3.0, 3.25, [1156, 7, 640]), (9.0, 9.5, [10])]
                if a <= s[0] < b]


@pytest.mark.parametrize("config", ["phi4-mini-3.8b", "minitron-4b"])
def test_work_readers_count_through_the_dense_module_as_before(config):
    """Through ``ctx["work"]`` (the dense module, which the committed
    configurations resolve to), the step share and the roofline read what
    ``bench/flops.py`` gave them before, to the last bit."""
    m = harness.load_json(ROOT / "bench" / "configs" / f"{config}.json")[
        "model"]
    work = harness.architecture({"model": m})
    ctx = {"trace": _fixture(), "traced": (0.0, 5.0), "window": (0.0, 5.0),
           "spans": _Steps(), "model": m, "work": work,
           "device_kind": "TPU v5 lite",
           "decode_kernels": {"module": "jit_decode", "ops": ["custom-call.7"]}}
    pk = peaks.peaks("TPU v5 lite")
    st = [s for s in _Steps().within("decode_step", 0.0, 5.0) if s[2]]
    mfu = 100.0 * sum(flops.decode_step_flops(m, lens) for _, _, lens in st) \
        / sum(e - s for s, e, _ in st) / pk["bf16_flops"]
    least = sum(flops.roofline_seconds(flops.attention_flops(m, lens),
                                       flops.attention_bytes(m, lens), pk)
                for _, _, lens in st)
    assert harness.reader("decode_step_mfu").read(ctx, None) == mfu
    assert harness.reader("decode_attn_roofline").read(ctx, None) == \
        100.0 * least / 2000e-9


# The decode step (two layers at phi4-mini's widths) as compiled for a
# TPU v5e: its first line and its Mosaic call, abridged.
COMPILED_DECODE = """\
HloModule jit__unknown, is_scheduled=true, input_output_alias={ {1}: (12, {}, may-alias) }
  %fusion.3 = bf16[8,3072]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.3
  %branch_0_fun.4 = bf16[8,8,3,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(%add_bitcast_fusion.2, %pad_maximum_fusion.4, %get-tuple-element.314, %get-tuple-element.315), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[8]{0}}
  %custom-call.9 = bf16[8]{0} custom-call(%fusion.3), custom_call_target="Sharding"
"""


def test_trace_op_names_are_instruction_names():
    """The trace names an op by its whole HLO instruction; the reduction
    keys it by the instruction's name, as the compiled program does."""
    text = ('%branch_0_fun.4 = bf16[8,8,3,128]{3,2,1,0} custom-call(%a, %b),'
            ' custom_call_target="tpu_custom_call"')
    assert tracefile.op_name(text) == "branch_0_fun.4"
    assert tracefile.op_name("fusion.1") == "fusion.1"
    tr = _fixture()
    tr["ops"] = [dict(e, text=f"%{e['name']} = f32[2] op(%x)") for e in tr["ops"]]
    ops = dict(tracefile.breakdown(tr)["device_ops"])
    assert ops["jit_decode(1)/%custom-call.7 = f32[2] op(%x)"] == \
        pytest.approx(2000e-9)
    # a loop that spans the ops of its body is left out of the breakdown
    loop = dict(tr["ops"][0], name="while.1", text="%while.1 = loop",
                dur_ns=3000.0)
    tr["ops"].append(loop)
    ops = dict(tracefile.breakdown(tr)["device_ops"])
    assert "jit_decode(1)/%while.1 = loop" not in ops
    assert ops["jit_decode(1)/%fusion.1 = f32[2] op(%x)"] == \
        pytest.approx(2000e-9)


def test_kernel_names_from_compiled_text():
    assert harness.kernel_names(COMPILED_DECODE) == {
        "module": "jit__unknown", "ops": ["branch_0_fun.4"]}
    assert harness.kernel_names("") == {"module": None, "ops": []}


def test_decode_kernels_asks_the_program():
    """On the CPU the decode step holds the interpreted kernel, not a
    Mosaic call: the module is named, the kernel list is empty."""
    import dataclasses

    from benchtest_common import TINY_MODEL
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serving.engine import DecodeEngine

    from bench import model_ref
    m = dict(TINY_MODEL, activation="swiglu", rope_theta=1e4, norm_eps=1e-5)
    base = get_config("phi4-mini-3.8b")
    fields = {f.name for f in dataclasses.fields(base)}
    model = Model(dataclasses.replace(
        base, **{k: v for k, v in m.items() if k in fields}))
    dec = DecodeEngine(model, model_ref.init_params(m, 1), num_slots=2,
                       max_len=64)
    assert harness.decode_kernels(dec) == {"module": "jit__unknown",
                                           "ops": []}


def test_decode_flops_from_shapes():
    m = dict(num_layers=2, d_model=8, num_heads=2, num_kv_heads=1,
             head_dim=4, d_ff=16, vocab_size=10, activation="swiglu")
    per_tok = 2 * (8 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8 + 3 * 8 * 16) + 8 * 10
    assert flops.matmul_params_per_token(m) == per_tok
    assert flops.decode_step_flops(m, [3, 5]) == pytest.approx(
        2 * per_tok * 2 + 4 * 8 * 2 * 4 * 2)
    assert flops.attention_bytes(m, [3, 5]) == pytest.approx(
        (2 * 8 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2) * 2)


def test_peaks_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
