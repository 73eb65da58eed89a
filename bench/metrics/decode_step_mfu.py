"""Model (``Model.decode``): the whole decode step's share of the chip's
peak.

Reads the benchmark's ``decode_step`` spans (as ``decode_step_ms``) with
the live rows' cached lengths taken before each step.  Operations come
from the shapes, counted by the configuration's architecture module
(``ctx["work"].decode_step_flops``; for the dense module, ``bench/flops.py``:
2 x the weights each token multiplies through x live rows, plus
attention's 4 x positions x heads x head_dim x layers).  Value: operations
over span time over the bf16 peak of the ``device_kind``
(``bench/peaks.py``), in %."""
from bench.metrics_common import decode_steps
from bench.peaks import peaks


def read(ctx, variant=None):
    st = decode_steps(ctx, *ctx["window"])
    if not st:
        return None
    pk = peaks(ctx["device_kind"])
    count = ctx["work"].decode_step_flops
    work = sum(count(ctx["model"], lens) for _, _, lens in st)
    span = sum(e - s for s, e, _ in st)
    return 100.0 * work / span / pk["bf16_flops"]
