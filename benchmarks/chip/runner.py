"""From a cell to its result line: the harness runs the cell, each metric's
own reader reads it, and the comparison decides ``correct``.

:func:`run_cell` is everything of a run after the look for the chip, so a
test can drive it on the CPU.
"""

from __future__ import annotations

import dataclasses
import shutil
from typing import Callable, Dict, List, Optional

import peaks as bench_peaks
import spec
import trace as bench_trace


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    cell: spec.Cell
    peaks: dict
    setup_s: float
    window: object                  # the harness's record of the window
    snapshot: object                # repro EngineSnapshot of the window
    trace: Optional[bench_trace.Trace]
    classify: Optional[bench_trace.Classify]
    _cache: Dict[object, float] = dataclasses.field(default_factory=dict)

    @property
    def config(self) -> dict:
        return self.cell.config

    def counts(self, name: str):
        """The module ``counts/<name>.py``."""
        return spec.module("counts", name)

    def program_s(self, cls: str) -> float:
        """Device seconds of the programs of one class (the harness's
        ``program_class``), averaged over the devices."""
        return self._once(("program", cls), lambda: bench_trace.program_s(
            self.trace, self.classify, cls))

    def kernel_s(self, cls: str) -> float:
        """Device seconds of the Pallas kernels inside the programs of one
        class, averaged over the devices."""
        return self._once(("kernel", cls), lambda: bench_trace.kernel_s(
            self.trace, self.classify, cls))

    def _once(self, key, fn):
        if key not in self._cache:
            self._cache[key] = fn()
        return self._cache[key]

    def busy_s(self) -> float:
        return self._once("busy", lambda: bench_trace.busy_s(self.trace))


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices: List, t_start: float, log: Callable[[str], None],
             control: Optional[str] = None,
             peak_table: Optional[dict] = None) -> dict:
    """``peak_table`` stands in for the device's peaks where the device has
    none (a CPU test of the readers); a chip run looks its kind up."""
    harness = spec.module("harness", cell.config["harness"])
    out = harness.run(cell, seed, seconds, traced, devices, control=control)
    dev = devices[0]
    run = Run(cell=cell, peaks=peak_table or bench_peaks.peaks(dev.device_kind),
              setup_s=out["window_start"] - t_start, window=out["window"],
              snapshot=out["snapshot"], trace=out.get("trace"),
              classify=harness.program_class(cell.config))
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    result = {"attempted": int(out["attempted"]), "failed": int(out["failed"]),
              "metrics": metrics, "device": device}
    if traced:
        tr = run.trace
        device["busy_s"] = run.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in
                           bench_trace.top_ops(tr, run.classify)],
            "idle_gaps": [[k, v] for k, v in bench_trace.idle_by_span(tr)]}
        shutil.rmtree(out["trace_dir"], ignore_errors=True)
    win = out["window"]
    log(f"{cell.name} seed {seed}: window {win.seconds:.3f} s, {win.steps} "
        f"steps, {win.tokens} tokens, {len(win.tracks)} requests, "
        f"generator late by at most {win.late_max_s * 1e3:.1f} ms; longest "
        f"step {win.step_max_s * 1e3:.1f} ms with {win.step_max_firsts} "
        f"first tokens; "
        f"compilations inside the window: {win.compiles} "
        f"{win.compile_names}; collector {win.gc_s * 1e3:.1f} ms in "
        f"{win.gc_runs} runs by generation")
    log(f"set-up {run.setup_s:.2f} s; reference {out['reference_s']:.2f} s "
        f"over {out['seqs_compared']} requests, "
        f"{out['served_tokens_compared']} served tokens")
    if control is not None:
        log(f"control ({control}) compared in the program's place; the "
            f"program's own logit_gap {out['program_gap']!r}")
        result["control"] = {"precision": control,
                             "program_logit_gap": out["program_gap"]}
    compared = out["compared"]
    correct = all(v is not None and v <= lim for v, lim in compared.values())
    result = {"correct": bool(correct), **result}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        log(f"compared {k} {v!r} limit {lim!r}")
    return result
