"""Host spans of the serving path: one helper for the engine and sampler.

``spans.span(name, **meta)`` does two things:

* it opens a :class:`jax.profiler.TraceAnnotation`, so the span lands in
  the profiler's own ``.xplane.pb`` on the clock the device operations are
  aligned to (a trace taken with ``jax.profiler.start_trace`` shows which
  host phase the device waited on);
* on exit it adds the span's duration, read on the engine's clock, to the
  engine's :class:`~repro.serving.metrics.MetricsCollector` — so an
  untraced run still says where its time went (``EngineSnapshot.phases``,
  ``step_max_phases``).

Durations are taken on the clock the engine was built with, never on a
wall clock of its own: an engine inside a ``ServingFleet`` runs on sim
time, and its snapshot must replay bit-identically from a seed.

``meta`` is passed to the profiler only while it records, so an untraced
step formats nothing.  Values that are sequences (the rids of an
admission) are joined with spaces: the profiler's own encoding separates
its arguments with commas.

With the profiler off a span costs one ``TraceAnnotation`` enter and exit
plus two clock reads.  Spans open nothing on the device: no sync and no
transfer.
"""

from __future__ import annotations

from typing import Callable, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.serving.metrics import STEP_SPAN as STEP


def _fmt(v) -> object:
    if isinstance(v, (list, tuple)):
        return " ".join(str(x) for x in v)
    return v


class Spans:
    """Span factory bound to a clock and (optionally) a collector.

    Without a collector a span only annotates the profiler's trace.  The
    collector is looked up at each span's exit, so an engine may swap it
    (``reset_stats``) between steps.  Spans nest; the direct children of
    ``serve.step`` make up the per-step split the collector keeps for the
    longest step.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 collector=None):
        self.clock = clock
        self.collector = collector
        self._open: list = []          # names of the spans now open

    def span(self, name: str, start: Optional[float] = None,
             **meta) -> "_Span":
        """``start`` backdates the counted duration to an engine-clock time
        already read (the profiler's span opens now).  ``serve.step`` is a
        profiler step event (give it ``step_num``)."""
        return _Span(self, name, start, meta)


class _Span:
    __slots__ = ("_spans", "_name", "_t0", "_meta", "_ann")

    def __init__(self, spans: Spans, name: str, start, meta):
        self._spans = spans
        self._name = name
        self._t0 = start
        self._meta = meta

    def __enter__(self) -> "_Span":
        sp = self._spans
        meta = self._meta
        if meta and TraceAnnotation.is_enabled():
            meta = {k: _fmt(v) for k, v in meta.items()}
        else:
            meta = {}
        cls = StepTraceAnnotation if self._name == STEP else TraceAnnotation
        self._ann = cls(self._name, **meta)
        self._ann.__enter__()
        sp._open.append(self._name)
        if sp.collector is not None and self._t0 is None:
            self._t0 = sp.clock()
        return self

    def __exit__(self, *exc) -> None:
        sp = self._spans
        sp._open.pop()
        col = sp.collector
        if col is not None and self._t0 is not None:
            col.on_phase(self._name, sp.clock() - self._t0,
                         sp._open[-1] if sp._open else None)
        self._ann.__exit__(*exc)
