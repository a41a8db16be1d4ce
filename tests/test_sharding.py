"""Logical-axis sharding rules: validity on the production mesh shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, RunConfig, get_config
from repro.core import sharding as sh
from repro.models.api import build_model

MESH = AbstractMesh((16, 16), ("data", "model"))


def _params_shape(arch):
    cfg = get_config(arch)
    model = build_model(cfg, RunConfig())
    return jax.eval_shape(lambda: model.init(jax.random.key(0)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_specs_divisible(arch):
    """Every emitted PartitionSpec must evenly divide its dim (our rule:
    fall back to replication rather than padding)."""
    ps = _params_shape(arch)
    shard = sh.param_shardings(ps, MESH, "gspmd_tp", fsdp=True)

    def check(leaf, s):
        spec = s.spec
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            size = np.prod([MESH.shape[a] for a in
                            (ax if isinstance(ax, tuple) else (ax,))])
            assert dim % size == 0, (arch, leaf.shape, spec)

    jax.tree.map(check, ps, shard)


def test_tp_shards_big_dims():
    ps = _params_shape("granite-8b")
    shard = sh.param_shardings(ps, MESH, "gspmd_tp")
    mlp_spec = shard["blocks"]["mlp"]["wi"].spec
    assert "model" in jax.tree.leaves(tuple(mlp_spec))
    emb_spec = shard["embed"]["tok"].spec
    assert emb_spec[0] == "model"          # vocab sharded


def test_moe_expert_parallel():
    ps = _params_shape("llama4-scout-17b-a16e")
    shard = sh.param_shardings(ps, MESH, "gspmd_tp")
    wi = shard["blocks"]["moe"]["wi"].spec      # (L, E, D, F)
    assert wi[1] == "model"                     # 16 experts over 16-way axis


def test_moe_fallback_when_not_divisible():
    ps = _params_shape("grok-1-314b")           # 8 experts on 16-way axis
    shard = sh.param_shardings(ps, MESH, "gspmd_tp")
    wi = shard["blocks"]["moe"]["wi"].spec
    assert len(wi) == 4 and wi[1] is None and wi[3] == "model"
