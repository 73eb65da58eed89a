"""Dry-run machinery on a small multi-device mesh (subprocess: the device
count must be set before JAX initializes, and the main test process runs on
one device)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch.dryrun_lib import run_cell

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rec = run_cell("xlstm-125m", "decode_32k", mesh, verbose=False)
print("JSON:" + json.dumps({
    "devices": rec["devices"],
    "flops": rec["cost"]["flops"],
    "coll": rec["collectives"]["total_bytes"],
    "bottleneck": rec["roofline"]["bottleneck"],
    "mem_args": rec["memory"]["argument_size_in_bytes"],
}))
"""


@pytest.mark.slow
def test_dryrun_cell_on_8_devices():
    # the child stays on the CPU: the TPU library belongs to one process
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("JSON:")][0]
    rec = json.loads(line[5:])
    assert rec["devices"] == 8
    assert rec["flops"] > 0
    assert rec["mem_args"] > 0
    assert rec["bottleneck"] in ("compute", "memory", "collective")
