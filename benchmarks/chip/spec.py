"""Look-ups in BENCHMARK.json: a cell, its configuration, its traffic mix,
its metrics and their readers, each found by the name the file gives it.

Nothing here imports JAX, so a run can refuse a bad name before it
touches the chip.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict            # the configuration file as it is run
    traffic: dict           # the traffic mix's parameters
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, config_name=w["config"], traffic_name=w["traffic"],
                chips=int(w["chips"]), config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def _load(path: Path):
    name = "bench_{}_{}".format(path.parent.name,
                                path.stem.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_MODULES: Dict[Path, object] = {}


def module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark (a metric's reader,
    a kernel's counts, a harness, a reference), loaded once."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"run: no {kind} file {path.name} for {name!r}")
    if path not in _MODULES:
        _MODULES[path] = _load(path)
    return _MODULES[path]


def reader(metric: str) -> Callable:
    """``read(run) -> float | None`` of the metric's own file."""
    return module("metrics", metric).read
