"""Device time of one program class by the program's own named scope.

A traced serving run's harness attaches each program's optimised HLO
text (``window.programs``: ``{"decode": ..., "prefill": ...}``).  On a
TPU an ``XLA Ops`` event is named by its HLO instruction (``%fusion.12 =
...``); the instruction's ``op_name`` metadata in that text carries the
``jax.named_scope`` path (``jit(decode_pool_step)/while/body/mamba/...``).
An instruction with no ``op_name`` of its own (a fusion) takes the most
common scope among the instructions of the computation it calls.  A call
the compiler made in place of a program's operation carries a name of the
compiler's instead of the program's path (the grouped matmuls of
``lax.ragged_dot`` on the TPU: ``op_name="ragged-dot-none"``); it takes
the path of the nearest instruction that consumes its result, directly or
through instructions with no path of their own.  The readers then sum the
innermost operations of the class's programs whose path holds
``/<scope>/``.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional

import trace as bench_trace

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _path(op: str) -> bool:
    """An op_name the program wrote (``jit(f)/scope/op``), not one the
    compiler gave a call of its own."""
    return "/" in op


def op_names(hlo: str) -> Dict[str, str]:
    """Instruction name -> op_name path, fusions resolved through the
    computation they call, the compiler's own calls through the
    instructions that consume their results."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    users: Dict[str, list] = collections.defaultdict(list)
    comps: Dict[str, list] = collections.defaultdict(list)
    comp = None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
            if comp is not None and _path(op.group(1)):
                comps[comp].append(op.group(1))
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        for operand in _OPERAND.findall(line.split(" = ", 1)[1]):
            users[operand].append(name)
    out = {k: v for k, v in own.items() if _path(v)}
    for name, comp in calls.items():
        if name in out or not comps.get(comp):
            continue
        out[name] = collections.Counter(comps[comp]).most_common(1)[0][0]

    def through_users(name, seen):
        for u in users.get(name, ()):
            if u in out:
                return out[u]
            if u not in seen and (u not in own or not _path(own[u])):
                seen.add(u)
                found = through_users(u, seen)
                if found:
                    return found
        return None

    for name, op in own.items():
        if name not in out and not _path(op):
            found = through_users(name, {name})
            out[name] = found if found else op
    return out


def instruction(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _programs(run):
    """Device 0's program executions with their classes and operations,
    reduced once per run."""
    return run._once("scopes_programs", lambda: bench_trace.programs(
        run.trace, run.classify, 0))


def scope_s(run, cls: str, scope: str) -> Optional[float]:
    """Device seconds of the innermost operations of the class's programs
    under ``scope`` (device 0), or None without a trace or a program
    text."""
    texts = getattr(run.window, "programs", None)
    if run.trace is None or not run.trace.devices or not texts \
            or cls not in texts:
        return None
    names = run._once(("op_names", cls), lambda: op_names(texts[cls]))
    tag = f"/{scope}/"
    total = 0.0
    for _, c, inside in _programs(run):
        if c != cls:
            continue
        for e in bench_trace.leaves(inside):
            if tag in names.get(instruction(e.name), ""):
                total += (e.end - e.start) * 1e-9
    return total


def kernel_named_s(run, cls: str, kernel: str) -> Optional[float]:
    """Device seconds of the Pallas kernel ``kernel`` (by its instruction
    name) inside the class's programs (device 0)."""
    if run.trace is None or not run.trace.devices:
        return None
    return sum((e.end - e.start) * 1e-9
               for _, c, inside in _programs(run)
               if c == cls for e in inside
               if bench_trace.is_kernel(e)
               and re.match(rf"{kernel}(\.\d+)?$", instruction(e.name)))
