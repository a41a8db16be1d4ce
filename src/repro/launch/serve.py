"""Serving launcher: continuous-batching engine + optional async tools.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
        --requests 8 --max-new 16 [--tools]

``--reduced`` (the default) serves the smoke-sized config in float32 with
the jnp attention path, which the CPU runs.  ``--no-reduced`` serves the
published widths in bf16 through the Pallas kernels, for a TPU; only the
depth may be cut (``--layers``), and ``--kv-blocks`` gives the engine a
paged KV pool of that many blocks.  ``chip_smoke.py`` drives the same
:func:`build_engine` on the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import numpy as np

from repro.configs import ARCH_IDS, RunConfig, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model, init_params
from repro.offload.tools import ToolExecutor
from repro.offload.vectordb import VectorDB
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.tool_loop import run_scenario


def build_engine(arch: str = "granite-8b", *, reduced: bool = True,
                 layers: int = 0, max_batch: int = 4, max_len: int = 128,
                 kv_blocks: Optional[int] = None,
                 seed: int = 0) -> ServeEngine:
    """The engine this launcher serves, with seeded random weights.

    ``reduced``: smoke widths, float32, jnp attention.  Otherwise the
    published widths in bf16 with ``use_kernels``; ``layers`` (0 = the
    config's own depth) is the only cut."""
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
        rcfg = RunConfig(param_dtype="float32", compute_dtype="float32",
                         remat=False)
    else:
        rcfg = RunConfig(param_dtype="bfloat16", compute_dtype="bfloat16",
                         remat=False, use_kernels=True)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    model = build_model(cfg, rcfg)
    return ServeEngine(model, init_params(model, seed), max_batch, max_len,
                       config=EngineConfig(kv_blocks=kv_blocks))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke widths in float32 (--no-reduced: published "
                         "widths in bf16 through the Pallas kernels)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="serve from a paged KV pool of this many blocks")
    ap.add_argument("--tools", action="store_true",
                    help="run the paper's §4.3 agent scenario instead")
    args = ap.parse_args()

    enable_compile_cache()
    engine = build_engine(args.arch, reduced=args.reduced, layers=args.layers,
                          max_batch=args.max_batch, max_len=args.max_len,
                          kv_blocks=args.kv_blocks)
    cfg = engine.model.cfg

    if args.tools:
        db = VectorDB(n_docs=20_000, dim=128)
        ex = ToolExecutor(n_workers=3)
        ex.register("vector_db_begin_search",
                    lambda query, k: db.search_text(query, int(k)),
                    simulated_seconds=0.5)
        tr = run_scenario(engine, ex,
                          ["google search engine", "apple ipod",
                           "microsoft windows"], async_tools=True)
        print(f"[serve] agent scenario: total {tr.total:.2f}s, "
              f"tool_wait {tr.time_in('tool_wait'):.2f}s "
              f"(tools ran fully overlapped)")
        for seg in tr.timeline():
            print(f"  {seg['kind']:10s} {seg['start']:6.2f}-{seg['end']:6.2f}s"
                  f" {seg['label']}")
        return

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        engine.submit(rng.integers(0, cfg.vocab_size, size=8 + i % 5),
                      max_new=args.max_new)
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, {engine.steps} engine steps, "
          f"{args.max_batch} lanes)")
    for r in done[:3]:
        ttft = (r.first_token_t - r.submitted_t) * 1e3
        print(f"  req{r.rid}: ttft={ttft:.0f}ms tokens={r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
