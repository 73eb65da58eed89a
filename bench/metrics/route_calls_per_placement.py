"""Control plane (``ControlPlane.route``): routing calls per placement.

The cluster routes every pending request on every tick and puts it back in
the queue when the chosen decoder has no free slot.  Reads the program's
count on each request (``ServeRequest.route_calls``, the request's share
of the control plane's ``route_calls_total``) for every request placed
(``ServeRequest.placed_t``) in the window.  A program that keeps no such
count gives no value.  Value: routing calls over placements."""


def read(ctx, variant=None):
    w0, w1 = ctx["window"]
    calls = placed = 0
    for tr in ctx["tracks"]:
        n = getattr(tr.serve, "route_calls", 0)
        t = getattr(tr.serve, "placed_t", 0.0)
        if n <= 0 or t <= 0.0:
            continue
        if w0 <= t + (tr.submit - tr.serve.submit_t) < w1:
            calls += n
            placed += 1
    if not placed:
        return None
    return calls / placed
