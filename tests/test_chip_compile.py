"""Compile the main path's kernels and steps for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse (tile
alignment, vector layouts, VMEM).  Interpret-mode tests cannot show that.
Shapes are granite-8b's published widths: 32 q heads, 8 KV heads,
head_dim 128, d_model 4096, bf16.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import RunConfig, get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.paged_attention import paged_decode_attention_fwd
from repro.models.api import build_model
from repro.serving.backends import _pool_step_jit
from repro.serving.engine import _shared_prefill_jits

H, G, D = 32, 8, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    b, t = 1, 512
    c = _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
                 _shape(one_chip, (b, t, H, D), BF16),
                 _shape(one_chip, (b, t, G, D), BF16),
                 _shape(one_chip, (b, t, G, D), BF16))
    _assert_kernel(c)


def test_decode_attention_compiles(one_chip):
    b, s = 8, 2048
    c = _compile(lambda q, k, v, m: decode_attention_fwd(q, k, v, m),
                 _shape(one_chip, (b, 1, H, D), BF16),
                 _shape(one_chip, (b, s, G, D), BF16),
                 _shape(one_chip, (b, s, G, D), BF16),
                 _shape(one_chip, (b, s), jnp.bool_))
    _assert_kernel(c)


def test_paged_decode_attention_compiles(one_chip):
    b, n_blocks, bs, max_blocks = 8, 1025, 16, 128
    c = _compile(
        lambda q, kp, vp, bt, pos: paged_decode_attention_fwd(q, kp, vp, bt,
                                                              pos),
        _shape(one_chip, (b, 1, H, D), BF16),
        _shape(one_chip, (n_blocks, bs, G, D), BF16),
        _shape(one_chip, (n_blocks, bs, G, D), BF16),
        _shape(one_chip, (b, max_blocks), jnp.int32),
        _shape(one_chip, (b,), jnp.int32))
    _assert_kernel(c)


@pytest.fixture
def granite_2l(one_chip, monkeypatch):
    """granite-8b at full width, 2 layers, bf16 with kernels; its params
    as shapes on the described chip.  The CPU backend would route the
    kernels to interpret mode: this compiles them for the chip instead."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=2)
    model = build_model(cfg, RunConfig(param_dtype="bfloat16",
                                       compute_dtype="bfloat16", remat=False,
                                       use_kernels=True))
    params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.key(0))))
    return model, params


def test_engine_paged_decode_step_compiles(one_chip, granite_2l):
    """The jitted step PagedBackend.step dispatches (8 lanes, 2048-token
    lanes, 1024 blocks of 16)."""
    model, params = granite_2l
    ds = model.decode_state
    cache = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: ds.pool_init(8, 1024, 16)))
    c = jax.jit(ds.pool_step, donate_argnums=1).lower(
        params, cache, _shape(one_chip, (8, 1), jnp.int32),
        _shape(one_chip, (8, 2048 // 16), jnp.int32)).compile()
    _assert_kernel(c)


def test_engine_batched_prefill_compiles(one_chip, granite_2l):
    """The engine's batched bucketed prefill (4 prompts in a 128 bucket)."""
    model, params = granite_2l
    prefill = model.decode_state.batched_prefill
    c = _compile(lambda p, t, n: prefill(p, {"tokens": t}, n, 2048), params,
                 _shape(one_chip, (4, 128), jnp.int32),
                 _shape(one_chip, (4,), jnp.int32))
    _assert_kernel(c)


SCOPES = ("embed", "attn", "kv_write", "mlp", "head")


def _assert_names(compiled, program, kernel):
    """The program's own name, every model scope in its operations'
    metadata, and each Pallas call named for its kernel (the instruction
    and its op_name)."""
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{program},")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(n.startswith(f"jit({program})/") and f"/{scope}/" in n
                   for n in names), scope
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", ln), ln
        assert f"/attn/{kernel}/pallas_call" in ln


def test_engine_decode_step_carries_its_names(one_chip, granite_2l):
    """The engine's own jitted decode step at the batch cell's engine shapes
    (32 lanes, 2000 blocks of 16 and the sink, 2048-token lanes):
    ``jit_decode_pool_step``, the layer scopes, and one Pallas call, the
    paged attention kernel by name, which takes the engine's s32[32,128]
    block table: the benchmark tells the decode program from the prefill
    by that operand."""
    model, params = granite_2l
    ds = model.decode_state
    cache = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: ds.pool_init(32, 2000, 16)))
    c = _pool_step_jit(ds).lower(
        params, cache, _shape(one_chip, (32, 1), jnp.int32),
        _shape(one_chip, (32, 2048 // 16), jnp.int32)).compile()
    _assert_names(c, "decode_pool_step", "paged_attention")
    calls = [ln for ln in c.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert "s32[32,128]" in calls[0]


def test_engine_batched_prefill_carries_its_names(one_chip, granite_2l):
    """The engine's own batched prefill: ``jit_prefill_batched``, the
    layer scopes, and the flash attention kernel by name."""
    model, params = granite_2l
    _, prefill = _shared_prefill_jits(model, 2048)
    c = prefill.lower(params, _shape(one_chip, (4, 128), jnp.int32),
                      _shape(one_chip, (4,), jnp.int32)).compile()
    _assert_names(c, "prefill_batched", "flash_attention")


def test_mamba2_ssd_compiles_at_granite_4_h_widths(one_chip):
    """The chunked SSD scan at granite-4.0-h-small's Mamba2 widths (128
    heads x 64, state 128, one group, chunk 256) for a batched prefill of
    four 2048-token prompts."""
    from repro.kernels.mamba2_ssd import mamba2_ssd_fwd
    b, t, h, p, n = 4, 2048, 128, 64, 128
    c = _compile(lambda x, dt, a, bb, cc, d: mamba2_ssd_fwd(
        x, dt, a, bb, cc, d, chunk=256),
        _shape(one_chip, (b, t, h, p), BF16),
        _shape(one_chip, (b, t, h), jnp.float32),
        _shape(one_chip, (h,), jnp.float32),
        _shape(one_chip, (b, t, n), BF16), _shape(one_chip, (b, t, n), BF16),
        _shape(one_chip, (h,), jnp.float32))
    _assert_kernel(c)


@pytest.fixture
def granite_h_period(one_chip, monkeypatch):
    """granite-4.0-h-small at published widths, one period of its layer
    pattern (9 Mamba2, 1 attention, each with its 72-expert layer holding
    9), bf16 with kernels; params as shapes on the described chip."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("granite-4.0-h-small"), n_layers=10,
                              held_experts=(0, 9))
    model = build_model(cfg, RunConfig(param_dtype="bfloat16",
                                       compute_dtype="bfloat16", remat=False,
                                       use_kernels=True))
    params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.key(0))))
    return model, params


HYBRID_SCOPES = ("embed", "mamba", "in_proj", "conv", "ssm_state", "out_proj",
                 "attn", "kv_write", "moe", "router", "experts",
                 "shared_expert", "head")


def _kernel_calls(text):
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln
            and "ragged-dot" not in ln]


def test_hybrid_decode_step_compiles(one_chip, granite_h_period):
    """The engine's jitted pool step at the batch cell's engine shapes (32
    lanes, 4096 blocks of 16, 2048-token lanes): named
    ``jit_decode_pool_step``, the hybrid's scopes, and the paged attention
    kernel taking the s32[32,128] block table that the benchmark tells the
    decode program by."""
    model, params = granite_h_period
    ds = model.decode_state
    cache = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: ds.pool_init(32, 4096, 16)))
    c = _pool_step_jit(ds).lower(
        params, cache, _shape(one_chip, (32, 1), jnp.int32),
        _shape(one_chip, (32, 2048 // 16), jnp.int32)).compile()
    text = c.as_text()
    assert text.startswith("HloModule jit_decode_pool_step,")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in HYBRID_SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    calls = _kernel_calls(text)
    assert calls and all(re.match(r"\s*(ROOT )?%paged_attention(\.\d+)? = ", ln)
                         for ln in calls)
    assert any("s32[32,128]" in ln for ln in calls)


def test_hybrid_batched_prefill_compiles(one_chip, granite_h_period):
    """The engine's batched prefill (4 prompts in a 256 bucket): named
    ``jit_prefill_batched``, the hybrid's scopes, and the flash attention
    and ``mamba2_ssd`` kernels by name, which is how the benchmark tells
    the SSD kernel's time from the flash kernel's."""
    model, params = granite_h_period
    _, prefill = _shared_prefill_jits(model, 2048)
    c = prefill.lower(params, _shape(one_chip, (4, 256), jnp.int32),
                      _shape(one_chip, (4,), jnp.int32)).compile()
    text = c.as_text()
    assert text.startswith("HloModule jit_prefill_batched,")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in HYBRID_SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    kinds = {re.match(r"\s*(?:ROOT )?%([a-z0-9_]+?)(?:\.\d+)? = ", ln).group(1)
             for ln in _kernel_calls(text)}
    assert kinds == {"flash_attention", "mamba2_ssd"}, kinds


def test_hybrid_decode_step_scopes_its_state_and_expert_kernels(
        one_chip, granite_h_period):
    """What the benchmark's ``mamba_ms`` and ``moe_ms`` read by op_name:
    each Mamba2 layer's state write-back (conv tail and SSM state, in
    place) carries ``/mamba/``; the grouped expert matmuls, calls the
    compiler makes for ``lax.ragged_dot`` under a name of its own, feed
    ``/moe/experts/`` directly or through instructions with no path of
    their own, which is where the benchmark's look-up attributes them."""
    model, params = granite_h_period
    ds = model.decode_state
    cache = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: ds.pool_init(32, 4096, 16)))
    text = _pool_step_jit(ds).lower(
        params, cache, _shape(one_chip, (32, 1), jnp.int32),
        _shape(one_chip, (32, 2048 // 16), jnp.int32)).compile().as_text()
    lines, users = {}, {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", ln)
        if m:
            lines[m.group(1)] = ln
            for o in re.findall(r"%([\w.\-]+)", ln.split(" = ", 1)[1]):
                users.setdefault(o, []).append(m.group(1))

    def op(name):
        m = re.search(r'op_name="([^"]*)"', lines[name])
        return m.group(1) if m else ""

    conv, ssm = (cache["state"][k].shape for k in ("conv", "ssm"))
    writes = [n for n, ln in lines.items() if "dynamic-update-slice" in n
              and re.search(r" = f32\[(%s|%s)\]" % (
                  ",".join(map(str, conv)), ",".join(map(str, ssm))), ln)]
    assert len(writes) >= 2
    for n in writes:
        assert re.search(r"/mamba/(conv|ssm_state)/", op(n)), lines[n]

    grouped = [n for n in lines if n.startswith("ragged-dot")]
    assert grouped and all('custom_call_target="tpu_custom_call"' in lines[n]
                           for n in grouped)
    for n in grouped:
        near, todo = set(), [n]
        while todo:             # consumers, through those with no path
            for u in users.get(todo.pop(), ()):
                if u not in near:
                    near.add(u)
                    if "/" not in op(u):
                        todo.append(u)
        assert "/moe/experts/" in op(n) or any(
            "/moe/experts/" in op(u) for u in near), lines[n]
