"""Uniform per-layer blocks for each family.

Every family exposes the same entry points so that scan-over-layers, the
GSPMD pipelines and the shard_map pipeline can all treat layers as an opaque
stacked unit:

    init_block(key, cfg, dtype)                       -> bparams (one layer)
    block_train(cfg, bp, x, idx, uk)                  -> (x, aux)
    block_prefill(cfg, bp, x, idx, positions, span, uk) -> (x, cache_layer)
    block_decode(cfg, bp, x, cache_layer, pos, idx, uk) -> (x, cache_layer)

Expert layers: training (``block_train``) keeps GShard capacity dropping;
every serving path (prefill and both decode forms) runs the dropless
layer, so a token's output never depends on what else is in the batch.

zamba2's SHARED attention block (one set of weights fired every
``attn_every`` layers) is handled by the assembly layer (`repro.models.lm`)
with its own compact ``n_attn``-slot cache — per-layer stacking would waste
``attn_every``× KV memory.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import apply_mlp, init_mlp, init_rmsnorm, rmsnorm

ZERO = jnp.float32(0.0)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if cfg.rwkv:
        return {"ln1": init_rmsnorm(d, dtype), "ln2": init_rmsnorm(d, dtype),
                "rwkv": rwkv_mod.init_rwkv6(ks[0], cfg, dtype)}
    if cfg.family in ("ssm", "hybrid"):
        return {"ln": init_rmsnorm(d, dtype),
                "mamba": ssm_mod.init_mamba2(ks[0], cfg, dtype)}
    p = {"ln1": init_rmsnorm(d, dtype), "ln2": init_rmsnorm(d, dtype),
         "attn": attn.init_attention(ks[0], cfg, dtype)}
    if cfg.n_experts:
        p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.glu, dtype)
    return p


def init_shared(key, cfg: ModelConfig, dtype) -> Optional[dict]:
    """zamba2: one shared attention+MLP block applied every ``attn_every``."""
    if cfg.family == "hybrid" and cfg.attn_every:
        ks = jax.random.split(key, 2)
        return {"ln1": init_rmsnorm(cfg.d_model, dtype),
                "attn": attn.init_attention(ks[0], cfg, dtype),
                "ln2": init_rmsnorm(cfg.d_model, dtype),
                "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.glu, dtype)}
    return None


def init_stacked_blocks(key, cfg: ModelConfig, n_layers: int, dtype) -> dict:
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: init_block(k, cfg, dtype))(keys)


def n_attn_applications(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid" and cfg.attn_every:
        return -(-cfg.n_layers // cfg.attn_every)      # ceil
    return 0


# ---------------------------------------------------------------------------
# train (full sequence, no cache)
# ---------------------------------------------------------------------------

def block_train(cfg: ModelConfig, bp: dict, x: jax.Array, idx,
                uk: bool) -> Tuple[jax.Array, jax.Array]:
    aux = ZERO
    if cfg.rwkv:
        h, _ = rwkv_mod.apply_rwkv6_tmix(bp["rwkv"], cfg, rmsnorm(bp["ln1"], x),
                                         use_kernels=uk)
        x = x + h
        h, _ = rwkv_mod.apply_rwkv6_cmix(bp["rwkv"], cfg, rmsnorm(bp["ln2"], x))
        return x + h, aux
    if cfg.family in ("ssm", "hybrid"):
        h, _ = ssm_mod.apply_mamba2(bp["mamba"], cfg, rmsnorm(bp["ln"], x),
                                    use_kernels=uk)
        return x + h, aux
    x = x + attn.attention(bp["attn"], cfg, rmsnorm(bp["ln1"], x),
                           use_rope=True, causal=True, use_kernels=uk)
    h = rmsnorm(bp["ln2"], x)
    if cfg.n_experts:
        y, aux = moe_mod.apply_moe(bp["moe"], cfg, h)
        return x + y, aux
    return x + apply_mlp(bp["mlp"], h, cfg.act), aux


# ---------------------------------------------------------------------------
# caches (one layer; the assembly stacks over layers)
# ---------------------------------------------------------------------------

def init_cache_layer(cfg: ModelConfig, batch: int, span: int, dtype) -> dict:
    if cfg.rwkv:
        d, h = cfg.d_model, cfg.n_heads
        dk = d // h
        return {"S": jnp.zeros((batch, h, dk, dk), jnp.float32),
                "last": jnp.zeros((batch, d), dtype),
                "last_c": jnp.zeros((batch, d), dtype)}
    if cfg.family in ("ssm", "hybrid"):
        d_in, nheads, conv_dim = ssm_mod.dims(cfg)
        return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
                "ssm": jnp.zeros((batch, nheads, cfg.ssm_headdim, cfg.ssm_state),
                                 jnp.float32)}
    return {"k": jnp.zeros((batch, span, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, span, cfg.n_kv_heads, cfg.head_dim), dtype)}


# ---------------------------------------------------------------------------
# prefill (full sequence -> activations + cache layer)
# ---------------------------------------------------------------------------

def block_prefill(cfg: ModelConfig, bp: dict, x: jax.Array, idx,
                  positions: jax.Array, span: int,
                  uk: bool) -> Tuple[jax.Array, dict]:
    b, t, _ = x.shape
    dtype = x.dtype
    if cfg.rwkv:
        d, hn = cfg.d_model, cfg.n_heads
        dk = d // hn
        st0 = {"S": jnp.zeros((b, hn, dk, dk), jnp.float32),
               "last": jnp.zeros((b, d), dtype)}
        h, st = rwkv_mod.apply_rwkv6_tmix(bp["rwkv"], cfg, rmsnorm(bp["ln1"], x),
                                          use_kernels=uk, state=st0)
        x = x + h
        h, last_c = rwkv_mod.apply_rwkv6_cmix(
            bp["rwkv"], cfg, rmsnorm(bp["ln2"], x),
            state={"last_c": jnp.zeros((b, d), dtype)})
        x = x + h
        return x, {"S": st["S"], "last": st["last"].astype(dtype),
                   "last_c": last_c.astype(dtype)}
    if cfg.family in ("ssm", "hybrid"):
        d_in, nheads, conv_dim = ssm_mod.dims(cfg)
        st0 = {"conv": jnp.zeros((b, cfg.ssm_conv - 1, conv_dim), dtype),
               "ssm": jnp.zeros((b, nheads, cfg.ssm_headdim, cfg.ssm_state),
                                jnp.float32)}
        h, st = ssm_mod.apply_mamba2(bp["mamba"], cfg, rmsnorm(bp["ln"], x),
                                     use_kernels=uk, state=st0)
        x = x + h
        return x, {"conv": st["conv"].astype(dtype), "ssm": st["ssm"]}
    # profile scopes: attn and kv_write inside prefill_attn, mlp here
    h, ck, cv = attn.prefill_attn(bp["attn"], cfg, rmsnorm(bp["ln1"], x),
                                  positions, span, use_kernels=uk)
    x = x + h
    with jax.named_scope("mlp"):
        h = rmsnorm(bp["ln2"], x)
        if cfg.n_experts:
            y, _ = moe_mod.apply_moe_dropless(bp["moe"], cfg, h)
            x = x + y
        else:
            x = x + apply_mlp(bp["mlp"], h, cfg.act)
    return x, {"k": ck.astype(dtype), "v": cv.astype(dtype)}


# ---------------------------------------------------------------------------
# decode (one token, stateful)
# ---------------------------------------------------------------------------

def block_decode(cfg: ModelConfig, bp: dict, x: jax.Array, cache: dict,
                 pos: jax.Array, idx, uk: bool) -> Tuple[jax.Array, dict]:
    if cfg.rwkv:
        h, st = rwkv_mod.apply_rwkv6_tmix(
            bp["rwkv"], cfg, rmsnorm(bp["ln1"], x), use_kernels=False,
            state={"S": cache["S"], "last": cache["last"]})
        x = x + h
        h, last_c = rwkv_mod.apply_rwkv6_cmix(
            bp["rwkv"], cfg, rmsnorm(bp["ln2"], x), state={"last_c": cache["last_c"]})
        x = x + h
        return x, {"S": st["S"], "last": st["last"].astype(cache["last"].dtype),
                   "last_c": last_c.astype(cache["last_c"].dtype)}
    if cfg.family in ("ssm", "hybrid"):
        st0 = {"conv": cache["conv"], "ssm": cache["ssm"]}
        h, st = ssm_mod.apply_mamba2(bp["mamba"], cfg, rmsnorm(bp["ln"], x),
                                     use_kernels=False, state=st0)
        x = x + h
        return x, {"conv": st["conv"].astype(cache["conv"].dtype), "ssm": st["ssm"]}
    h, ck, cv = attn.decode_attn(bp["attn"], cfg, rmsnorm(bp["ln1"], x),
                                 cache["k"], cache["v"], pos, use_kernels=uk)
    x = x + h
    h = rmsnorm(bp["ln2"], x)
    if cfg.n_experts:
        y, _ = moe_mod.apply_moe_dropless(bp["moe"], cfg, h)
        x = x + y
    else:
        x = x + apply_mlp(bp["mlp"], h, cfg.act)
    return x, {"k": ck, "v": cv}


def block_decode_paged(cfg: ModelConfig, bp: dict, x: jax.Array,
                       kp: jax.Array, vp: jax.Array, block_tables: jax.Array,
                       pos: jax.Array, idx, uk: bool):
    """One-token decode against a paged KV pool (attention-cache families
    only — the assembly gates ssm/rwkv/hybrid to the dense path).  Profile
    scopes: attn and kv_write inside decode_attn_paged, mlp here."""
    h, kp, vp = attn.decode_attn_paged(bp["attn"], cfg, rmsnorm(bp["ln1"], x),
                                       kp, vp, block_tables, pos,
                                       use_kernels=uk)
    x = x + h
    with jax.named_scope("mlp"):
        h = rmsnorm(bp["ln2"], x)
        if cfg.n_experts:
            y, _ = moe_mod.apply_moe_dropless(bp["moe"], cfg, h)
            x = x + y
        else:
            x = x + apply_mlp(bp["mlp"], h, cfg.act)
    return x, kp, vp


# ---------------------------------------------------------------------------
# zamba2 shared attention block — fired by the assembly every ``attn_every``
# ---------------------------------------------------------------------------

def shared_attn_train(cfg: ModelConfig, shared: dict, x: jax.Array, idx,
                      uk: bool) -> jax.Array:
    def fire(x):
        h = x + attn.attention(shared["attn"], cfg, rmsnorm(shared["ln1"], x),
                               use_rope=True, causal=True, use_kernels=uk)
        return h + apply_mlp(shared["mlp"], rmsnorm(shared["ln2"], h), cfg.act)
    return jax.lax.cond(idx % cfg.attn_every == 0, fire, lambda x: x, x)


def shared_attn_prefill(cfg: ModelConfig, shared: dict, x: jax.Array, idx,
                        positions: jax.Array, ak: jax.Array, av: jax.Array,
                        uk: bool):
    """ak/av: (n_attn, B, span, KVH, Dh) stacked slots; slot = idx//attn_every."""
    span = ak.shape[2]
    slot = idx // cfg.attn_every

    def fire(arg):
        x, ak, av = arg
        h, ck, cv = attn.prefill_attn(shared["attn"], cfg, rmsnorm(shared["ln1"], x),
                                      positions, span, use_kernels=uk)
        y = x + h
        y = y + apply_mlp(shared["mlp"], rmsnorm(shared["ln2"], y), cfg.act)
        ak = jax.lax.dynamic_update_index_in_dim(ak, ck.astype(ak.dtype), slot, 0)
        av = jax.lax.dynamic_update_index_in_dim(av, cv.astype(av.dtype), slot, 0)
        return y, ak, av

    return jax.lax.cond(idx % cfg.attn_every == 0, fire, lambda a: a, (x, ak, av))


def shared_attn_decode(cfg: ModelConfig, shared: dict, x: jax.Array, idx,
                       pos: jax.Array, ak: jax.Array, av: jax.Array, uk: bool):
    slot = idx // cfg.attn_every

    def fire(arg):
        x, ak, av = arg
        ck = jax.lax.dynamic_index_in_dim(ak, slot, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(av, slot, 0, keepdims=False)
        h, nck, ncv = attn.decode_attn(shared["attn"], cfg, rmsnorm(shared["ln1"], x),
                                       ck, cv, pos, use_kernels=uk)
        y = x + h
        y = y + apply_mlp(shared["mlp"], rmsnorm(shared["ln2"], y), cfg.act)
        ak = jax.lax.dynamic_update_index_in_dim(ak, nck, slot, 0)
        av = jax.lax.dynamic_update_index_in_dim(av, ncv, slot, 0)
        return y, ak, av

    return jax.lax.cond(idx % cfg.attn_every == 0, fire, lambda a: a, (x, ak, av))
