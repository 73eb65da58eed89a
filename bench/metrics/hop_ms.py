"""KV hop (``DecodeEngine.admit``): device time per hop of a prefill cache
into a decode slot.

Reads the profiler trace of the traced sub-window: the union of the
device ops of the hop's own program, the module the program names
``kv_hop`` (``jit_kv_hop`` on the ``XLA Modules`` line), and the number of
hops that began in that sub-window, counted by the benchmark's ``admit``
span around ``DecodeEngine.admit`` (the boundary of the program's own
``hop`` span).  The hop runs asynchronously, so its host span says nothing
of its device time.  A trace with no ``jit_kv_hop`` module (a program that
does not name its hop, or a CPU run) gives no value.  Value: device ms per
hop."""
import re

from bench import tracefile

MODULE = re.compile(r"^jit_kv_hop(\(|$)")


def read(ctx, variant=None):
    tr, traced = ctx["trace"], ctx["traced"]
    w = tracefile.window(tr) if tr else None
    if w is None or not traced:
        return None
    hops = ctx["spans"].within("admit", *traced)
    iv = sorted((max(e["start_ns"], w[0]),
                 min(e["start_ns"] + e["dur_ns"], w[1]))
                for e in tr["ops"] if MODULE.search(e["module"]))
    busy, end = 0.0, w[0]
    for s, t in iv:            # union: a fusion may enclose other ops
        s = max(s, end)
        if t > s:
            busy += t - s
            end = t
    if not hops or busy <= 0:
        return None
    return busy / 1e6 / len(hops)
