#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep of fixed rates on the chip.

    python3 benchmarks/chip/sweep.py --workload granite-8b-18l.chat \
        --seed 11 --seconds 30 --rates 2,3,4,5,6

One process builds and warms the cell's engine, then for each rate runs
the cell's window with its mix at that rate, drains the engine untimed,
and prints a line per rate: requests due, waiting at the end, TTFT
median and 95th percentile from the due time, and the TTFT median of the
last fifth of the arrivals against the first fifth.  The knee is the
highest rate at which the queue does not grow over the window.  The
benchmark's own runs never sweep: a cell's mix holds its rate as a
number.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import run as bench_run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = bench_run.spec.load_cell(bench_run.ROOT, args.workload)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    bench_run.pin_tpu()
    bench_run.enable_cache(bench_run.ROOT)
    import jax
    import numpy as np

    import stats
    from harness import serve

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: no TPU")
    engine = serve.build(cell.config, args.seed)
    serve.warm(engine, cell.config["vocab_size"])
    for rate in [float(r) for r in args.rates.split(",")]:
        c = dataclasses.replace(cell, traffic={**cell.traffic, "rate_rps": rate})
        win = serve.drive(engine, c, args.seed, args.seconds, traced=False)
        waiting = engine.scheduler.depth
        due = [t for t in win.tracks if t.due is not None and t.due < win.seconds]
        ttft = [(t.first if t.first is not None else win.seconds) - t.due
                for t in due]
        k = max(1, len(ttft) // 5)
        line = {"rate_rps": rate, "due": len(due), "waiting_at_end": waiting,
                "active_at_end": engine.active(),
                "ttft_p50_ms": 1e3 * stats.pct(ttft, 50),
                "ttft_p95_ms": 1e3 * stats.pct(ttft, 95),
                "ttft_first_fifth_p50_ms": 1e3 * float(np.median(ttft[:k])),
                "ttft_last_fifth_p50_ms": 1e3 * float(np.median(ttft[-k:])),
                "steps": win.steps, "tokens": win.tokens,
                "compiles": win.compiles}
        print(json.dumps(line), flush=True)
        t0 = time.perf_counter()
        engine.run_until_drained()
        engine.reset_stats()
        print(f"[sweep] drained in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
