"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --steps 200 --reduced --schedule hybrid [--strategy pp_shardmap]

``--reduced`` (the default) runs the smoke-sized config in float32 on local
devices (CPU-feasible); ``--no-reduced`` keeps the published widths in
bf16, for the production mesh (real fleet or the dry-run).
Fault tolerance: checkpoints every --ckpt-every, auto-resume from --ckpt-dir.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, RunConfig, ShapeConfig, get_config,
                           reduced_config)
from repro.data.synthetic import DataConfig, FrontendPipeline, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model, init_params
from repro.optim import adamw
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-8b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke widths in float32 (--no-reduced: published "
                         "widths in bf16)")
    ap.add_argument("--layers", type=int, default=0,
                    help="override n_layers")
    ap.add_argument("--schedule", choices=["gpipe", "hybrid"], default="hybrid")
    ap.add_argument("--strategy", default="single",
                    choices=["single", "pp_shardmap", "gspmd_tp", "gspmd_pp"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--use-kernels", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    dtype = "float32" if args.reduced else "bfloat16"
    rcfg = RunConfig(param_dtype=dtype, compute_dtype=dtype,
                     remat=False, schedule=args.schedule,
                     use_kernels=args.use_kernels)
    model = build_model(cfg, rcfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps, weight_decay=0.01)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init(jax.random.key(0)))))
    print(f"[train] {cfg.arch_id}: {n_params/1e6:.2f}M params, "
          f"strategy={args.strategy}")

    if args.strategy == "single":
        # repro-lint: allow[R001] launcher entry point: one training program per process run, nothing to share
        @jax.jit
        def step_fn(params, opt, batch):
            (loss, m), g = jax.value_and_grad(
                lambda p, b: model.loss(p, b), has_aux=True)(params, batch)
            p2, o2, st = adamw.update(opt_cfg, g, opt, params)
            return p2, o2, dict(loss=loss, **st)

        def init_state():
            p = init_params(model)
            return p, adamw.init(p)
    else:
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_train_step
        mesh = make_host_mesh()
        shape = ShapeConfig("cli", args.seq, args.batch, "train")
        built = make_train_step(cfg, shape, rcfg, mesh, opt_cfg,
                                strategy=args.strategy)
        # repro-lint: allow[R001] launcher entry point: one training program per process run, nothing to share
        jitted = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                         out_shardings=built["out_shardings"])

        def step_fn(params, opt, batch):
            return jitted(params, opt, batch)

        def init_state():
            p = init_params(model)
            if "to_pipeline" in built:
                p = built["to_pipeline"](p)
            return p, adamw.init(p)

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=rcfg.seed)
    if cfg.frontend and cfg.family != "audio":
        pipe = FrontendPipeline(dcfg, cfg.frontend_seq, cfg.d_model)
    elif cfg.family == "audio":
        pipe = FrontendPipeline(dcfg, cfg.frontend_seq, cfg.d_model,
                                key="frames")
    else:
        pipe = TokenPipeline(dcfg)

    def data_iter(start):
        def gen():
            s = start
            while True:
                b = pipe.batch(s)
                yield {k: jnp.asarray(v) for k, v in b.items()}
                s += 1
        return iter(gen())

    tr = Trainer(TrainerConfig(total_steps=args.steps,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir, log_every=10),
                 step_fn, init_state, data_iter)
    out = tr.run()
    losses = out["losses"]
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {len(losses)} steps")
    return out


if __name__ == "__main__":
    main()
