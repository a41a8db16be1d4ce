#!/usr/bin/env python3
"""Compile a serving configuration's programs for a described TPU v5e,
without the chip, and print what ``memory_analysis()`` says of each.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py granite-8b-18l

Compiles the weight maker, the paged decode step at the configuration's
pool, and the largest batched prefill (``max_prefill_batch`` prompts of
the largest bucket).  Nothing runs, so this says nothing of times; it
refuses what the chip's compiler would refuse and sizes the pool.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def gb(x) -> str:
    return f"{x / 1e9:.3f} GB"


def report(name, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"arguments": m.argument_size_in_bytes, "outputs": m.output_size_in_bytes,
           "temporaries": m.temp_size_in_bytes, "aliased": m.alias_size_in_bytes}
    print(f"{name}: " + ", ".join(f"{k} {gb(v)}" for k, v in out.items()),
          flush=True)
    return out


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import weights
    from harness.serve import model_config
    from repro.configs.base import RunConfig
    from repro.kernels import ops
    from repro.models.api import build_model

    ops._interpret = lambda: False           # compile the kernels for the chip
    config = json.loads((HERE / "configs" / f"{name}.json").read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    dt = config["torch_dtype"]
    s = config["serve"]
    model = build_model(model_config(config),
                        RunConfig(param_dtype=dt, compute_dtype=dt, remat=False,
                                  use_kernels=True))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    maker = weights._maker(json.dumps(config, sort_keys=True))
    key = jax.eval_shape(lambda: jax.random.key(0))
    report("weights", maker.lower(on_chip(key)).compile())
    params = on_chip(jax.eval_shape(maker, key))
    ds = model.decode_state
    pool = on_chip(jax.eval_shape(
        lambda: ds.pool_init(s["max_batch"], s["kv_blocks"], s["kv_block_size"])))
    toks = jax.ShapeDtypeStruct((s["max_batch"], 1), jnp.int32, sharding=one)
    bt = jax.ShapeDtypeStruct((s["max_batch"], -(-s["max_len"] // s["kv_block_size"])),
                              jnp.int32, sharding=one)
    dec = jax.jit(ds.pool_step, donate_argnums=1).lower(params, pool, toks,
                                                        bt).compile()
    if "tpu_custom_call" not in dec.as_text():
        raise SystemExit("decode step compiled without a Pallas kernel")
    report("decode step", dec)
    c, b = s["max_prefill_batch"], max(s["prefill_buckets"])
    ptoks = jax.ShapeDtypeStruct((c, b), jnp.int32, sharding=one)
    lens = jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one)
    pre = jax.jit(lambda p, t, n: ds.batched_prefill(p, {"tokens": t}, n,
                                                     s["max_len"]))
    report(f"prefill {c} x {b}", pre.lower(params, ptoks, lens).compile())


if __name__ == "__main__":
    main(sys.argv[1])
