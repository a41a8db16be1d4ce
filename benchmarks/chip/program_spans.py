"""The program's own host spans in a traced run, joined with the device's
idle gaps.

The serving engine opens ``serve.*`` spans (``repro.serving.spans``) as
profiler annotations, so they land in the run's own
``.xplane.pb`` on the clock the device operations are aligned to.  A
traced serving run writes that file under ``<checkout>/.bench_trace``,
which the runner removes only after every reader has run.

:func:`join` clips the spans to the traced window and gives the device's
idle seconds by the innermost program span open at each gap
(``trace.idle_by_span`` on the trace with these spans in place of the
benchmark's own).  A program without such spans gives no spans, and the
readers built on them report nothing.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional

import trace as bench_trace
from trace import Event, Trace

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
PREFIX = "serve."


def load(path: Path = TRACE_DIR) -> List[Event]:
    """Every ``serve.*`` host span of the trace under ``path``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(bench_trace._xplane(str(path)))
    return [Event(ev.name, ev.start_ns, ev.end_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def clip(tr: Trace, spans: List[Event]) -> List[Event]:
    lo, hi = tr.window
    return [Event(s.name, max(s.start, lo), min(s.end, hi))
            for s in spans if s.end > lo and s.start < hi]


def join(tr: Trace, spans: List[Event]) -> Dict[str, float]:
    """Idle seconds of the window by the innermost program span open at
    each gap ("no span" where none is)."""
    spans = clip(tr, spans)
    keep = len({s.name for s in spans}) + 1
    return dict(bench_trace.idle_by_span(dataclasses.replace(tr, spans=spans),
                                         top=keep))


def spans(run) -> Optional[List[Event]]:
    """The run's program spans inside the window, or None in an untraced
    run."""
    if run.trace is None:
        return None
    return run._once("program_spans", lambda: clip(run.trace, load()))


def idle(run) -> Optional[Dict[str, float]]:
    """Idle seconds by innermost program span, or None where the run is
    untraced or the program opened no span."""
    sp = spans(run)
    if not sp:
        return None
    return run._once("program_idle", lambda: join(run.trace, sp))


def count(run, name: str) -> int:
    return sum(s.name == name for s in spans(run) or ())
