#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic mix; the configuration names its harness
(``harness/<name>.py``) and its reference (``reference/<name>.py``); each
metric is read by ``metrics/<metric>.py``.  The run makes its weights and
inputs from ``--seed``, warms up, measures for ``--seconds``, compares
what the timed path produced with the float32 reference, and prints one
JSON line last.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.

It refuses to run without a TPU (no fallback to the CPU) and without as
many chips as the cell asks for.  JAX's persistent compilation cache is
kept in ``<checkout>/.jax_cache``.  ``--control int8`` (not used by the
benchmark's own runs) puts the int8 control in the program's place in
the comparison, so ``correct`` judges the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import spec  # noqa: E402


def pin_tpu() -> None:
    """JAX may use the TPU and nothing else: an environment that lists the
    TPU among other platforms is narrowed to it, one that does not list it
    is refused."""
    platforms = os.environ.get("JAX_PLATFORMS") or "tpu"
    if "tpu" not in platforms.split(","):
        raise SystemExit(f"run: JAX_PLATFORMS={platforms!r} does not list "
                         f"the TPU; this benchmark runs on the chip only")
    os.environ["JAX_PLATFORMS"] = "tpu"


def enable_cache(root: Path) -> str:
    """The persistent compilation cache at a fixed path in the checkout,
    given to the program's own helper; every program is cached, however
    fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    d = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="compare this control precision (int8) in "
                         "the program's place")
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(ROOT, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"run: the program (src/repro) is not in {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    pin_tpu()
    enable_cache(ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run: JAX found {devices[0].platform!r}, not a TPU")
    if len(devices) < cell.chips:
        raise SystemExit(f"run: {cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devices)}")
    import runner

    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             devices[:cell.chips], T_START, log,
                             control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
