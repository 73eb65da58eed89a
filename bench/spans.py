"""Host spans recorded by the benchmark around calls into the program.

A span wraps one bound method of one object at run time (the program is
not edited): its name, start and end on the benchmark's clock, and an
optional detail taken before the call.  Each span also opens a
``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so the profiler's
trace holds the same spans on the device's clock.

Span names: ``route`` (``ControlPlane.route``), ``prefill``
(``PrefillEngine.prefill_many``), ``admit`` (``DecodeEngine.admit``),
``decode_step`` (``DecodeEngine.step``, detail: the cached lengths of the
live rows), ``tick`` (``DisaggregatedCluster.step``) and
``generator_wait`` (the load generator sleeping until the next arrival).
``ProgramSpans`` reads the program's own spans on the same clock.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

import jax

PREFIX = "bench:"


class Spans:
    def __init__(self, t0: float):
        self.t0 = t0
        self.spans: Dict[str, List[Tuple[float, float, object]]] = \
            defaultdict(list)

    def now(self) -> float:
        return time.monotonic() - self.t0

    def record(self, name: str, start: float, end: float, detail=None):
        self.spans[name].append((start, end, detail))

    def wrap(self, obj, attr: str, name: str,
             detail: Optional[Callable[[object], object]] = None) -> None:
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            d = detail(obj) if detail is not None else None
            with jax.profiler.TraceAnnotation(PREFIX + name):
                t = self.now()
                try:
                    return fn(*a, **kw)
                finally:
                    self.record(name, t, self.now(), d)

        setattr(obj, attr, wrapped)

    def sleep(self, seconds: float) -> None:
        with jax.profiler.TraceAnnotation(PREFIX + "generator_wait"):
            t = self.now()
            time.sleep(seconds)
            self.record("generator_wait", t, self.now())

    def within(self, name: str, start: float, end: float):
        """Spans of ``name`` that began in [start, end)."""
        return [s for s in self.spans.get(name, ()) if start <= s[0] < end]


class ProgramSpans:
    """The program's own spans (``cluster.tracer.spans``, on
    ``time.monotonic()``) read on the benchmark's clock, which starts at
    ``t0``.  ``spans`` keeps the program's list, parents by index, for
    ``repro.core.tracing.exclusive_seconds``."""

    def __init__(self, spans, t0: float):
        self.spans = spans
        self.t0 = t0

    def within(self, name: str, start: float, end: float):
        """(start, end, span) on the benchmark's clock of the spans of
        ``name`` that began in [start, end)."""
        out = []
        for s in self.spans:
            if s.name == name and start <= s.start - self.t0 < end:
                out.append((s.start - self.t0, s.end - self.t0, s))
        return out


def live_lengths(decoder) -> List[int]:
    """Positions each live row of a decoder attends over in its next step
    (its cached length plus the new token)."""
    return [s.length + 1 for s in decoder.slots if s.active and s.generated]


def instrument(cluster, spans: Spans) -> None:
    spans.wrap(cluster.control, "route", "route")
    spans.wrap(cluster.prefill, "prefill_many", "prefill")
    for dec in cluster.decoders:
        spans.wrap(dec, "admit", "admit")
        spans.wrap(dec, "step", "decode_step", detail=live_lengths)
    spans.wrap(cluster, "step", "tick")
