"""gspmd_pp stacked-pipeline correctness (subprocess; see test_pipeline.py)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import RunConfig, ShapeConfig, get_config, reduced_config
from repro.core import pipeline_gspmd as gpp
from repro.models.api import build_model
from repro.optim import adamw


def check(arch):
    full = get_config(arch)
    cfg = dataclasses.replace(reduced_config(full), n_layers=8)
    seq = 64 if cfg.attention == "chunked_local" else 32
    shape = ShapeConfig("t", seq_len=seq, global_batch=8, kind="train")
    rcfg = RunConfig(param_dtype="float32", compute_dtype="float32",
                     remat=False, microbatches=4)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    oc = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, schedule="const",
                           weight_decay=0.0)
    built = gpp.make_gspmd_pp_train_step(cfg, shape, rcfg, mesh, oc)
    model = build_model(cfg, rcfg)
    params = model.init(jax.random.key(0))
    pp = built["to_pipeline"](params)
    opt = adamw.init(pp)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, seq), 0,
                                          cfg.vocab_size)}
    with mesh:
        j = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                    out_shardings=built["out_shardings"])
        newpp, _, metrics = j(pp, opt, batch)

    def ref_loss(p, b):
        toks = b["tokens"].reshape(4, 2, seq)
        return jnp.mean(jax.vmap(
            lambda t: model.loss(p, {"tokens": t})[0])(toks))

    rl, rg = jax.value_and_grad(ref_loss)(params, batch)
    lerr = abs(float(metrics["loss"]) - float(rl))
    newp = built["from_pipeline"](jax.device_get(newpp))
    rnew, _, _ = adamw.update(oc, rg, adamw.init(params), params)
    perr = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(newp), jax.tree.leaves(rnew)))
    print(f"[gpp_check] {arch} loss_err={lerr:.2e} param_err={perr:.2e}")
    assert lerr < 3e-4 and perr < 2.5e-3


if __name__ == "__main__":
    archs = sys.argv[1].split(",") if len(sys.argv) > 1 else ["grok-1-314b"]
    for a in archs:
        check(a)
    print("[gpp_check] OK")
