"""Multi-device pipeline correctness check (run in a subprocess with
xla_force_host_platform_device_count set — see test_pipeline.py).

Validates THE paper claim that matters numerically: the hybrid fused-F+B
schedule and GPipe produce gradients identical to each other and to the
non-pipelined single-program reference, for every pp-eligible family.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import RunConfig, ShapeConfig, get_config, reduced_config
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.optim import adamw


def check_arch(arch: str, schedule: str, seed: int = 0) -> float:
    full = get_config(arch)
    import dataclasses
    cfg = dataclasses.replace(reduced_config(full), n_layers=8)
    cfg = dataclasses.replace(cfg, arch_id=cfg.arch_id + f"-{schedule}")
    shape = ShapeConfig("t", seq_len=32 + (cfg.frontend_seq if cfg.frontend else 0),
                        global_batch=8, kind="train")
    rcfg = RunConfig(param_dtype="float32", compute_dtype="float32",
                     remat=False, schedule=schedule, microbatches=4)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))

    opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, schedule="const",
                                weight_decay=0.0)
    built = pp.make_pp_train_step(cfg, shape, rcfg, mesh, opt_cfg)
    model = build_model(cfg, rcfg)
    key = jax.random.key(seed)
    params = model.init(key)
    params_pp = built["to_pipeline"](params)
    opt_pp = adamw.init(params_pp)

    kb = jax.random.key(seed + 1)
    batch = {"tokens": jax.random.randint(
        kb, (shape.global_batch, shape.seq_len -
             (cfg.frontend_seq if cfg.frontend else 0)), 0, cfg.vocab_size)}
    if cfg.frontend:
        batch["frontend"] = 0.1 * jax.random.normal(
            kb, (shape.global_batch, cfg.frontend_seq, cfg.d_model))

    with mesh:
        jitted = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                         out_shardings=built["out_shardings"])
        newp_pp, _, metrics = jitted(params_pp, opt_pp, batch)
    newp = built["from_pipeline"](jax.device_get(newp_pp))

    # reference: single-program loss + same optimizer
    def ref_loss(p, b):
        return model.loss(p, b)[0]

    rloss, rgrads = jax.value_and_grad(ref_loss)(params, batch)
    ref_newp, _, _ = adamw.update(opt_cfg, rgrads, adamw.init(params), params)

    lerr = abs(float(metrics["loss"]) - float(rloss))
    perr = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(newp),
                               jax.tree.leaves(ref_newp)))
    print(f"[pp_check] {arch:22s} {schedule:7s} loss_err={lerr:.2e} "
          f"param_err={perr:.2e} (loss {float(rloss):.4f})")
    assert lerr < 2e-4, (arch, schedule, lerr, float(metrics["loss"]), float(rloss))
    assert perr < 2e-3, (arch, schedule, perr)
    return perr


if __name__ == "__main__":
    archs = sys.argv[1].split(",") if len(sys.argv) > 1 else \
        ["granite-8b", "rwkv6-1.6b", "zamba2-7b", "internvl2-1b"]
    schedules = sys.argv[2].split(",") if len(sys.argv) > 2 else \
        ["gpipe", "hybrid"]
    for a in archs:
        for s in schedules:
            check_arch(a, s)
    print("[pp_check] OK")
