"""Reduction of a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` and keeps, inside the traced window (the
host span ``bench.window``):

* per device, the operations (line ``XLA Ops``) and the program
  executions (line ``XLA Modules``), each as (name, start_ns, end_ns);
* the host spans whose name starts with ``bench.``.

On a TPU an operation's name is its HLO instruction
(``%fusion.12 = bf16[32,14336]{...} fusion(...), kind=kOutput, ...``), a
Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` and no name of its own, and a
control-flow operation (a layer scan's ``while``) is listed together
with the operations it encloses.  So a kernel is told apart by the
program it runs in: a harness gives ``classify(module, ops_inside)``,
which names each program execution's class (or None).

:func:`busy_s` gives busy time (the union of operation intervals),
:func:`idle_by_span` attributes each idle gap to the innermost host span
open at its midpoint, :func:`program_s` and :func:`kernel_s` give device
time by program class, and :func:`top_ops` sums the time of innermost
operations by class and kind.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
KERNEL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    window: Interval                 # ns
    devices: List[Device]
    spans: List[Event]               # host spans bench.*

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


Classify = Callable[[Event, Sequence[Event]], Optional[str]]


def _device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def _xplane(path: str) -> str:
    files = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {path}")
    return files[-1]


def load(path: str, window_span: str = "bench.window") -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append(Event(ev.name, ev.start_ns, ev.end_ns))
    win = [s for s in spans if s.name == window_span]
    if not win:
        raise RuntimeError(f"no host span {window_span!r} in the trace")
    lo, hi = win[0].start, win[0].end
    for plane in pd.planes:
        if not _device_plane(plane.name):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            out = ops if line.name == "XLA Ops" else mods
            for ev in line.events:
                if ev.end_ns <= lo or ev.start_ns >= hi:
                    continue
                out.append(Event(ev.name, max(ev.start_ns, lo),
                                 min(ev.end_ns, hi)))
        devices.append(Device(plane.name, ops, mods))
    spans = [s for s in spans if s.end > lo and s.start < hi]
    return Trace((lo, hi), devices, spans)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_s(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union((e.start, e.end) for e in events)) * 1e-9


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    return sum(union_s(d.ops) for d in tr.devices) / len(tr.devices)


def is_kernel(e: Event) -> bool:
    return KERNEL in e.name


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations that enclose no other (a ``while`` is left out, the
    operations of its body are kept)."""
    s = sorted(ops, key=lambda e: (e.start, -e.end))
    return [e for i, e in enumerate(s)
            if not (i + 1 < len(s) and s[i + 1].start < e.end
                    and s[i + 1].end <= e.end)]


def kind(e: Event) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; a Pallas kernel ->
    ``pallas_kernel``; a program ``jit_f(123)`` -> ``jit_f``."""
    if is_kernel(e):
        return "pallas_kernel"
    head = e.name.split(" = ", 1)[0].lstrip("%")
    head = re.sub(r"\(\d+\)$", "", head)
    return re.sub(r"[.\d]+$", "", head) or head


def programs(tr: Trace, classify: Classify, device: int
             ) -> List[Tuple[Event, Optional[str], List[Event]]]:
    """Each program execution of one device with its class and the
    operations that ran inside it."""
    d = tr.devices[device]
    ops = sorted(d.ops, key=lambda e: e.start)
    starts = [e.start for e in ops]
    out = []
    for m in sorted(d.modules, key=lambda e: e.start):
        inside = ops[bisect.bisect_left(starts, m.start):
                     bisect.bisect_right(starts, m.end)]
        out.append((m, classify(m, inside), inside))
    return out


def _mean(tr: Trace, per_device: Callable[[int], float]) -> float:
    n = len(tr.devices)
    return sum(per_device(i) for i in range(n)) / n if n else 0.0


def program_s(tr: Trace, classify: Classify, cls: str) -> float:
    """Device seconds of the programs of one class, averaged over the
    devices."""
    return _mean(tr, lambda i: sum(
        (m.end - m.start) * 1e-9
        for m, c, _ in programs(tr, classify, i) if c == cls))


def kernel_s(tr: Trace, classify: Classify, cls: str) -> float:
    """Device seconds of the Pallas kernels inside the programs of one
    class, averaged over the devices."""
    return _mean(tr, lambda i: union_s(
        [o for _, c, inside in programs(tr, classify, i) if c == cls
         for o in inside if is_kernel(o)]))


def idle_by_span(tr: Trace, device: int = 0,
                 top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds of one device inside the window, by the innermost host
    span open at each gap's midpoint ("no span" where none is)."""
    if not tr.devices:
        return []
    busy = union((e.start, e.end) for e in tr.devices[device].ops)
    gaps, t = [], tr.window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if tr.window[1] > t:
        gaps.append((t, tr.window[1]))
    spans = sorted((s for s in tr.spans if s.name != "bench.window"),
                   key=lambda s: s.end - s.start)
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        name = next((s.name for s in spans if s.start <= mid <= s.end),
                    "no span")
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def top_ops(tr: Trace, classify: Classify, device: int = 0, top: int = 10
            ) -> List[Tuple[str, float]]:
    """Seconds of the innermost operations of one device, summed by
    ``<program class>/<operation kind>`` (the program's own name where it
    has no class)."""
    if not tr.devices:
        return []
    out: Dict[str, float] = {}
    for m, c, inside in programs(tr, classify, device):
        for e in leaves(inside):
            k = f"{c or kind(m)}/{kind(e)}"
            out[k] = out.get(k, 0.0) + (e.end - e.start) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]
