"""Host spans of the served path, on the profiler's clock.

One :class:`Tracer` is shared by a cluster's control plane, prefill engine
and decode engines.  It is off unless :meth:`Tracer.enable` is called.

* ``span(name, rid=None, detail=None)`` is a context manager.  When the
  tracer is on it records a :class:`Span` (name, start, end, parent, rid,
  detail) on ``time.monotonic()``, the clock the cluster's ``_now()``
  offsets, and opens ``jax.profiler.TraceAnnotation("repro:" + name)``, so
  the profiler's host plane holds the span on the device trace's clock.
  When it is off, ``span`` returns one shared no-op context: no clock read
  and no allocation.
* ``timed(name)`` is a span whose length the caller reads (``.seconds``)
  whether the tracer is on or not, for code that already timed the work for
  an output of its own: the output and the span share one pair of clock
  reads.
* ``record(name, start, end)`` keeps a span that crosses ticks (a
  request's wait in the queue); such spans are kept in memory only.

``exclusive_seconds`` reads a span's time less that of chosen descendants
(a scheduling pass less the prompt passes and hops it runs).
"""
from __future__ import annotations

import time
from typing import Collection, Dict, List, Optional

PREFIX = "repro:"


class Span:
    """One recorded span; also the context that records it.  ``parent``
    is the index in :attr:`Tracer.spans` of the span open when this one
    began (``None`` at the top, and for spans given to ``record``)."""

    __slots__ = ("name", "start", "end", "parent", "rid", "detail",
                 "_tracer", "_ann")

    def __init__(self, name: str, start: float, end: float,
                 parent: Optional[int] = None, rid=None, detail=None):
        self.name, self.start, self.end = name, start, end
        self.parent, self.rid, self.detail = parent, rid, detail
        self._tracer = self._ann = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        tr = self._tracer
        self.parent = tr._open[-1] if tr._open else None
        tr._open.append(len(tr.spans))
        tr.spans.append(self)
        self._ann = tr._annotation(PREFIX + self.name)
        self._ann.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.monotonic()
        self._ann.__exit__(*exc)
        self._ann = None
        self._tracer._open.pop()
        return False


class _Off:
    """The tracer's shared context while it is off.  A ``detail`` set on it
    is dropped."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    detail = property(lambda self: None, lambda self, value: None)


OFF = _Off()


class _Stopwatch:
    """What ``timed`` gives while the tracer is off: the clock pair alone."""

    __slots__ = ("start", "end", "detail")

    def __enter__(self) -> "_Stopwatch":
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.monotonic()
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Tracer:
    def __init__(self, enabled: bool = False):
        self.enabled = False
        self.spans: List[Span] = []
        self._open: List[int] = []
        self._annotation = None
        if enabled:
            self.enable()

    def enable(self) -> None:
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.enabled = True

    def span(self, name: str, rid=None, detail=None):
        if not self.enabled:
            return OFF
        sp = Span(name, 0.0, 0.0, None, rid, detail)
        sp._tracer = self
        return sp

    def timed(self, name: str):
        if not self.enabled:
            return _Stopwatch()
        return self.span(name)

    def record(self, name: str, start: float, end: float, rid=None,
               detail=None) -> None:
        if self.enabled:
            self.spans.append(Span(name, start, end, None, rid, detail))


def exclusive_seconds(spans: List[Span], name: str,
                      exclude: Collection[str]) -> Dict[int, float]:
    """For each span called ``name`` (by index): its length less that of
    its outermost descendants whose names are in ``exclude``."""
    out = {i: s.seconds for i, s in enumerate(spans) if s.name == name}
    for s in spans:
        if s.name not in exclude:
            continue
        p = s.parent
        while p is not None:
            anc = spans[p]
            if anc.name in exclude:
                break        # counted with its excluded ancestor
            if anc.name == name:
                out[p] -= s.seconds
                break
            p = anc.parent
    return out
