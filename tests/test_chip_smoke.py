"""CPU rehearsal of ``chip_smoke.py``: its serving phases run end to end on
the reduced phi4-mini config (Pallas kernels in interpret mode), and its
entry point refuses to run without a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_reduced

ROOT = Path(__file__).resolve().parents[1]
OUTPUT_TOKENS = 6


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def report(smoke):
    lines = []
    rep = smoke.serve(get_reduced(smoke.MODEL), prompt_tokens=40,
                      output_tokens=OUTPUT_TOKENS, log=lines.append)
    rep["lines"] = lines
    return rep


@pytest.mark.parametrize("impl", ["pallas", "paged"])
def test_every_request_completes(smoke, report, impl):
    rows = report[impl]["requests"]
    assert len(rows) == smoke.NUM_REQUESTS
    for _rid, ttft, n_tokens in rows:
        assert ttft > 0.0
        assert n_tokens == OUTPUT_TOKENS + 1    # first token + max_new
    assert any(line.startswith(f"[{impl}] r0 ") for line in report["lines"])


@pytest.mark.parametrize("impl", ["pallas", "paged"])
def test_step_logit_check_runs(report, impl):
    """The kernel-vs-XLA comparison runs and holds; off the chip the
    kernel is interpreted, so the compiled step holds no Mosaic call."""
    check = report[impl]["check"]
    assert check["ok"], check
    assert 0.0 <= check["max_abs_diff"] <= 0.02 * check["spread"]
    assert check["kernel_in_step"] is False


def test_entry_point_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "needs a TPU" in err
