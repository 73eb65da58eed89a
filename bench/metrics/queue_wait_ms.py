"""Cluster tick (``DisaggregatedCluster._try_schedule``): wait in the queue
from submission to a decode slot.

Reads the program's own stamps on each request (``ServeRequest``):
``submit_t`` and ``placed_t``, the ends of its ``queue`` span on the
cluster's clock, for every request placed in the window (``placed_t``
carried onto the benchmark's clock by the request's own submission
stamps).  A program that stamps no placement gives no value.  Value: mean
wait, in ms."""


def read(ctx, variant=None):
    w0, w1 = ctx["window"]
    waits = []
    for tr in ctx["tracks"]:
        placed = getattr(tr.serve, "placed_t", 0.0)
        if placed <= 0.0:
            continue
        if w0 <= placed + (tr.submit - tr.serve.submit_t) < w1:
            waits.append(placed - tr.serve.submit_t)
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
