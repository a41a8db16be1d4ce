#!/usr/bin/env python3
"""Compile a granitemoehybrid serving configuration's programs for a
described TPU v5e, without the chip, and print what
``memory_analysis()`` says of each (``rehearse.py``'s rehearsal, through
``harness/serve_hybrid.py`` and ``weights_hybrid.py``).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_hybrid.py \\
        granite-4.0-h-small-20l

Compiles the weight maker, the paged decode step at the configuration's
pool (attention blocks and one Mamba2 state slot per lane), and the
largest batched prefill, and checks that the decode step holds the paged
attention kernel and the prefill the flash attention and ``mamba2_ssd``
kernels.
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


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import rehearse
    import spec
    import weights_hybrid
    from repro.configs.base import RunConfig
    from repro.kernels import ops
    from repro.models.api import build_model

    harness = spec.module("harness", "serve_hybrid")
    ops._interpret = lambda: False           # compile the kernels for the chip
    config = json.loads((HERE / "configs" / f"{name}.json").read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    dt = config["torch_dtype"]
    s = config["serve"]
    model = build_model(harness.model_config(config),
                        RunConfig(param_dtype=dt, compute_dtype=dt, remat=False,
                                  use_kernels=True))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    maker = weights_hybrid._maker(json.dumps(config, sort_keys=True))
    key = jax.eval_shape(lambda: jax.random.key(0))
    rehearse.report("weights", maker.lower(on_chip(key)).compile())
    params = on_chip(jax.eval_shape(maker, key))
    ds = model.decode_state
    pool = on_chip(jax.eval_shape(
        lambda: ds.pool_init(s["max_batch"], s["kv_blocks"], s["kv_block_size"])))
    toks = jax.ShapeDtypeStruct((s["max_batch"], 1), jnp.int32, sharding=one)
    bt = jax.ShapeDtypeStruct((s["max_batch"], -(-s["max_len"] // s["kv_block_size"])),
                              jnp.int32, sharding=one)
    dec = jax.jit(ds.pool_step, donate_argnums=1).lower(params, pool, toks,
                                                        bt).compile()
    if "paged_attention" not in dec.as_text():
        raise SystemExit("decode step compiled without the paged kernel")
    rehearse.report("decode step", dec)
    c, b = s["max_prefill_batch"], max(s["prefill_buckets"])
    ptoks = jax.ShapeDtypeStruct((c, b), jnp.int32, sharding=one)
    lens = jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one)
    pre = jax.jit(lambda p, t, n: ds.batched_prefill(p, {"tokens": t}, n,
                                                     s["max_len"]))
    pc = pre.lower(params, ptoks, lens).compile()
    for k in ("flash_attention", "mamba2_ssd"):
        if k not in pc.as_text():
            raise SystemExit(f"prefill compiled without the {k} kernel")
    rehearse.report(f"prefill {c} x {b}", pc)


if __name__ == "__main__":
    main(sys.argv[1])
