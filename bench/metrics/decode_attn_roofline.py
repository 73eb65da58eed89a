"""Kernels (decode attention): share of the roofline.

Reads the profiler trace of the traced sub-window: the device time of the
decode-attention kernel's events, and the benchmark's ``decode_step``
spans that ran in that sub-window, with the live rows' cached lengths.

The kernel's events are found by the names the program's own compiled
decode step gives them (``harness.decode_kernels``): ops of the ``XLA
Ops`` line whose name is one of the step's ``tpu_custom_call``
instructions, inside the step's module on the ``XLA Modules`` line.  On
phi4-mini's and minitron-4b's dense path the step's module is
``jit__unknown`` (a ``functools.partial`` of ``Model.decode``, which JAX
names so) and its one Mosaic call is the instruction that
``jax.lax.platform_dependent`` names ``branch_0_fun.<n>``.  A trace with
no such event (the kernel taken off the path, or a CPU run) gives no
value.

The least time for that work is the larger of its operations over the
bf16 peak and its bytes over HBM bandwidth (``bench/peaks.py``), both
counted by the configuration's architecture module (``ctx["work"]``; for
the dense module, ``bench/flops.py``: each live row's K and V over its
positions, its query and output, every layer).  Value: least time over
kernel time, in %.  It reads the same work whichever kernel implements
it."""
import re
import sys

from bench import tracefile
from bench.flops import roofline_seconds
from bench.metrics_common import decode_steps
from bench.peaks import peaks


def read(ctx, variant=None):
    tr, traced, kern = ctx["trace"], ctx["traced"], ctx.get("decode_kernels")
    if not tr or not traced or not kern or not kern["module"] \
            or not kern["ops"]:
        return None
    module = "^" + re.escape(kern["module"]) + r"(\(|$)"
    ops = "^(" + "|".join(re.escape(o) for o in kern["ops"]) + ")$"
    kernel_s = tracefile.op_seconds(tr, module, ops)
    if kernel_s <= 0:
        if tracefile.op_seconds(tr, module, ".") > 0:
            print(f"decode_attn_roofline: the decode step {kern['module']} "
                  f"ran, but no event named {kern['ops']}", file=sys.stderr)
        return None
    st = decode_steps(ctx, *traced)
    if not st:
        return None
    pk, m, work = peaks(ctx["device_kind"]), ctx["model"], ctx["work"]
    least = sum(roofline_seconds(work.attention_flops(m, lens),
                                 work.attention_bytes(m, lens), pk)
                for _, _, lens in st)
    return 100.0 * least / kernel_s
