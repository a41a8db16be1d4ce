"""Decoder-only LM assembly: dense / MoE / SSM / RWKV / hybrid / VLM-audio-backbone.

Params pytree::

    {"embed": {"tok": (Vp, D)},
     "blocks": <stacked (L, ...) block params>,
     "shared": <zamba2 shared attn block>          (hybrid only)
     "final_ln": {"scale": (D,)},
     "head": {"w": (D, Vp)}}                       (absent when tied)

Cache pytree (decode)::

    {"layers": <stacked (L, ...) per-layer cache>,
     "ak"/"av": (n_attn, B, span, KVH, Dh)         (hybrid only)
     "pos": int32 scalar}

The frontends ([audio]/[vlm]) are STUBS per the assignment: ``input_specs``
exposes precomputed frame/patch embeddings of shape (B, P, D); the first P
sequence positions are those embeddings, the rest are token embeddings.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.models import blocks as B
from repro.models.common import (chunked_xent, embed_tokens, init_embed,
                                 init_head, init_rmsnorm, pad_vocab, rmsnorm)

AUX_COEF = 0.01


def _dt(name: str):
    return jnp.dtype(name)


def maybe_scan(body, carry, xs, length: int, unroll: bool):
    """lax.scan, or an unrolled Python loop (dry-run: exact HLO accounting).

    ``body(carry, x) -> (carry, y)``; xs is a pytree with leading dim
    ``length`` (or None).  Returns (carry, stacked_ys or None).
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs, length=length)
    ys = []
    for i in range(length):
        x = None if xs is None else jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm(cfg: ModelConfig, key, param_dtype) -> dict:
    ks = jax.random.split(key, 5)
    p = {
        "embed": init_embed(ks[0], cfg.vocab_size, cfg.d_model, param_dtype),
        "blocks": B.init_stacked_blocks(ks[1], cfg, cfg.n_layers, param_dtype),
        "final_ln": init_rmsnorm(cfg.d_model, param_dtype),
    }
    shared = B.init_shared(ks[2], cfg, param_dtype)
    if shared is not None:
        p["shared"] = shared
    if not cfg.tie_embeddings:
        p["head"] = init_head(ks[3], cfg.d_model, cfg.vocab_size, param_dtype)
    return p


def head_weight(cfg: ModelConfig, params: dict, dtype) -> jax.Array:
    if cfg.tie_embeddings:
        # embed rows are ~unit-norm; rescale for head use to keep logits O(1)
        return params["embed"]["tok"].T.astype(dtype) * (cfg.d_model ** -0.5)
    return params["head"]["w"].astype(dtype)


# ---------------------------------------------------------------------------
# train loss
# ---------------------------------------------------------------------------

def _scan_train(cfg: ModelConfig, params: dict, x: jax.Array,
                rcfg: RunConfig) -> Tuple[jax.Array, jax.Array]:
    uk = rcfg.use_kernels
    shared = params.get("shared")

    from repro.core.sharding import constrain

    def body(carry, inp):
        x, aux = carry
        bp, idx = inp
        x = constrain("residual", x)
        x, a = B.block_train(cfg, bp, x, idx, uk)
        if shared is not None:
            x = B.shared_attn_train(cfg, shared, x, idx, uk)
        return (x, aux + a), None

    fn = jax.checkpoint(body, prevent_cse=False) if rcfg.remat else body
    (x, aux), _ = maybe_scan(fn, (x, B.ZERO),
                             (params["blocks"], jnp.arange(cfg.n_layers)),
                             cfg.n_layers, rcfg.unroll_layers)
    return x, aux


def lm_loss(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array],
            rcfg: RunConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    cdt = _dt(rcfg.compute_dtype)
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cdt)
    if "frontend" in batch:
        x = jnp.concatenate([batch["frontend"].astype(cdt), x], axis=1)
    p = 0 if "frontend" not in batch else batch["frontend"].shape[1]
    x, aux = _scan_train(cfg, params, x, rcfg)
    x = rmsnorm(params["final_ln"], x)
    w = head_weight(cfg, params, cdt)
    t_tok = tokens.shape[1]
    if p:
        h = x[:, p - 1 : p + t_tok - 1]
        labels = tokens
    else:
        h = x[:, : t_tok - 1]
        labels = tokens[:, 1:]
    ce = chunked_xent(h, w, labels, cfg.vocab_size,
                      unroll=rcfg.unroll_layers)
    loss = ce + AUX_COEF * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _prefill_trunk(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array],
                   rcfg: RunConfig, max_len: int):
    """Shared prompt forward: returns (hidden (B,T,D) post-final-norm,
    layer_caches, (ak, av) or None)."""
    from repro.models.attention import cache_span

    cdt = _dt(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    tokens = batch["tokens"]
    bsz = tokens.shape[0]
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens, cdt)
        if "frontend" in batch:
            x = jnp.concatenate([batch["frontend"].astype(cdt), x], axis=1)
    t = x.shape[1]
    span = cache_span(cfg, max_len)
    positions = jnp.broadcast_to(jnp.arange(t), (bsz, t))
    shared = params.get("shared")
    n_attn = B.n_attn_applications(cfg)
    ak = av = None
    if n_attn:
        ak = jnp.zeros((n_attn, bsz, span, cfg.n_kv_heads, cfg.head_dim), cdt)
        av = jnp.zeros_like(ak)

    def body(carry, inp):
        bp, idx = inp
        if n_attn:
            x, ak, av = carry
            x, cl = B.block_prefill(cfg, bp, x, idx, positions, span, uk)
            x, ak, av = B.shared_attn_prefill(cfg, shared, x, idx, positions,
                                              ak, av, uk)
            return (x, ak, av), cl
        x = carry
        x, cl = B.block_prefill(cfg, bp, x, idx, positions, span, uk)
        return x, cl

    init = (x, ak, av) if n_attn else x
    fn = jax.checkpoint(body, prevent_cse=False) if rcfg.remat else body
    carry, layer_caches = maybe_scan(fn, init,
                                     (params["blocks"], jnp.arange(cfg.n_layers)),
                                     cfg.n_layers, rcfg.unroll_layers)
    if n_attn:
        x, ak, av = carry
    else:
        x = carry
    with jax.named_scope("head"):
        x = rmsnorm(params["final_ln"], x)
    return x, layer_caches, ((ak, av) if n_attn else None)


def lm_prefill(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array],
               rcfg: RunConfig, max_len: int) -> Tuple[jax.Array, dict]:
    """Process a prompt; return (last-token logits (B, Vp), cache)."""
    cdt = _dt(rcfg.compute_dtype)
    x, layer_caches, attn = _prefill_trunk(cfg, params, batch, rcfg, max_len)
    bsz, t = x.shape[:2]
    with jax.named_scope("head"):
        logits = x[:, -1] @ head_weight(cfg, params, cdt)
    cache = {"layers": layer_caches, "pos": jnp.full((bsz,), t, jnp.int32)}
    if attn is not None:
        cache["ak"], cache["av"] = attn
    return logits, cache


def lm_prefill_padded(cfg: ModelConfig, params: dict,
                      batch: Dict[str, jax.Array], lengths: jax.Array,
                      rcfg: RunConfig, max_len: int) -> Tuple[jax.Array, dict]:
    """Batched prefill of right-padded prompts with true ``lengths`` (B,).

    Exact for full causal attention: pad tokens sit strictly AFTER every real
    token, so causality keeps them out of all real hidden states, the logits
    are gathered at each lane's last real position, and the per-lane cache
    ``pos`` masks the pad garbage out of decode until the very step that
    overwrites it.  Recurrent families (ssm / rwkv / hybrid) fold pad tokens
    into their state, so serving must not route them here — build_model
    only wires ``DecodeState.batched_prefill`` for eligible configs.
    """
    cdt = _dt(rcfg.compute_dtype)
    x, layer_caches, attn = _prefill_trunk(cfg, params, batch, rcfg, max_len)
    bsz = x.shape[0]
    lengths = jnp.asarray(lengths, jnp.int32)
    with jax.named_scope("head"):
        h = x[jnp.arange(bsz), lengths - 1]
        logits = h @ head_weight(cfg, params, cdt)
    cache = {"layers": layer_caches, "pos": lengths}
    if attn is not None:
        cache["ak"], cache["av"] = attn
    return logits, cache


# ---------------------------------------------------------------------------
# stage (layer-range) execution — pipeline-split serving
# ---------------------------------------------------------------------------

def lm_stage_prefill(cfg: ModelConfig, params: dict,
                     batch: Dict[str, jax.Array], rcfg: RunConfig,
                     max_len: int, *, first: bool,
                     last: bool) -> Tuple[jax.Array, dict]:
    """Prefill ONE stage of a layer-split model (paper §4.1 topology).

    ``cfg.n_layers`` is the STAGE's layer count and ``params["blocks"]``
    holds only those layers (see ``repro.models.api.split_stage_params``).
    The first stage embeds ``batch["tokens"]``; later stages continue the
    residual stream from ``batch["hidden"]`` — the boundary activation the
    previous stage shipped.  Non-last stages return the FULL hidden
    sequence (B, T, D) so the next stage can prefill from it; the last
    stage returns last-token logits like :func:`lm_prefill`.

    Only wired for families whose layers are self-contained (dense / moe,
    no shared attention block, no frontend) — ``build_model`` gates
    eligibility via ``stage_eligible``.
    """
    cdt = _dt(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    from repro.models.attention import cache_span

    if first:
        x = embed_tokens(params["embed"], batch["tokens"], cdt)
    else:
        x = batch["hidden"].astype(cdt)
    bsz, t = x.shape[:2]
    span = cache_span(cfg, max_len)
    positions = jnp.broadcast_to(jnp.arange(t), (bsz, t))

    def body(carry, inp):
        bp, idx = inp
        x, cl = B.block_prefill(cfg, bp, carry, idx, positions, span, uk)
        return x, cl

    fn = jax.checkpoint(body, prevent_cse=False) if rcfg.remat else body
    x, layer_caches = maybe_scan(fn, x,
                                 (params["blocks"], jnp.arange(cfg.n_layers)),
                                 cfg.n_layers, rcfg.unroll_layers)
    cache = {"layers": layer_caches, "pos": jnp.full((bsz,), t, jnp.int32)}
    if last:
        x = rmsnorm(params["final_ln"], x)
        return x[:, -1] @ head_weight(cfg, params, cdt), cache
    return x, cache


def lm_stage_decode_step(cfg: ModelConfig, params: dict, cache: dict,
                         x_in: jax.Array, rcfg: RunConfig, *, first: bool,
                         last: bool) -> Tuple[jax.Array, dict]:
    """One decode step of ONE stage.  ``x_in`` is tokens (B, 1) int32 on
    the first stage, the previous stage's boundary activations (B, 1, D)
    otherwise.  Returns last-token logits on the last stage, the boundary
    hidden (B, 1, D) to ship onward everywhere else."""
    cdt = _dt(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    x = embed_tokens(params["embed"], x_in, cdt) if first \
        else x_in.astype(cdt)
    pos = cache["pos"]

    def body(carry, inp):
        bp, cl, idx = inp
        x, ncl = B.block_decode(cfg, bp, carry, cl, pos, idx, uk)
        return x, ncl

    x, new_layers = maybe_scan(
        body, x,
        (params["blocks"], cache["layers"], jnp.arange(cfg.n_layers)),
        cfg.n_layers, rcfg.unroll_layers)
    new_cache = {"layers": new_layers, "pos": pos + 1}
    if last:
        x = rmsnorm(params["final_ln"], x)
        return x[:, -1] @ head_weight(cfg, params, cdt), new_cache
    return x, new_cache


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def lm_decode_step(cfg: ModelConfig, params: dict, cache: dict,
                   tokens: jax.Array, rcfg: RunConfig) -> Tuple[jax.Array, dict]:
    """One decode step. tokens: (B, 1) int32.  Returns (logits (B,Vp), cache)."""
    cdt = _dt(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    x = embed_tokens(params["embed"], tokens, cdt)
    pos = cache["pos"]
    shared = params.get("shared")
    n_attn = B.n_attn_applications(cfg)

    def body(carry, inp):
        bp, cl, idx = inp
        if n_attn:
            x, ak, av = carry
            x, ncl = B.block_decode(cfg, bp, x, cl, pos, idx, uk)
            x, ak, av = B.shared_attn_decode(cfg, shared, x, idx, pos, ak, av, uk)
            return (x, ak, av), ncl
        x = carry
        x, ncl = B.block_decode(cfg, bp, x, cl, pos, idx, uk)
        return x, ncl

    init = (x, cache["ak"], cache["av"]) if n_attn else x
    carry, new_layers = maybe_scan(
        body, init, (params["blocks"], cache["layers"], jnp.arange(cfg.n_layers)),
        cfg.n_layers, rcfg.unroll_layers)
    if n_attn:
        x, ak, av = carry
    else:
        x = carry
    x = rmsnorm(params["final_ln"], x)
    logits = x[:, -1] @ head_weight(cfg, params, cdt)
    new_cache = {"layers": new_layers, "pos": pos + 1}
    if n_attn:
        new_cache["ak"], new_cache["av"] = ak, av
    return logits, new_cache


def lm_decode_window(cfg: ModelConfig, params: dict, cache: dict,
                     tokens: jax.Array,
                     rcfg: RunConfig) -> Tuple[jax.Array, dict]:
    """W sequential decode steps in ONE dispatch (speculative verify).

    tokens: (B, W) int32 — W consecutive next-token inputs per lane.
    Returns (logits (B, W, Vp) — the logits AFTER each token — and the
    cache advanced by W positions).

    This is a ``lax.scan`` of :func:`lm_decode_step`'s program, NOT a
    parallel multi-token attention window: a parallel window changes the
    attention reduction shapes, and XLA's reduction order then differs
    from single-token decode at the ~1e-6 level — enough to break the
    bit-for-bit greedy-identity guarantee speculative verification is
    built on.  The scan re-runs the exact single-step body, so its
    logits and cache are bitwise identical to W separate jitted steps
    while still amortising dispatch overhead into one program.
    """

    def body(c, tok):
        lg, c = lm_decode_step(cfg, params, c, tok, rcfg)
        return c, lg

    cache, lgs = jax.lax.scan(
        body, cache, jnp.moveaxis(tokens, 1, 0)[:, :, None])
    return jnp.moveaxis(lgs, 0, 1), cache


def lm_decode_step_pool(cfg: ModelConfig, params: dict, cache: dict,
                        tokens: jax.Array, block_tables: jax.Array,
                        rcfg: RunConfig) -> Tuple[jax.Array, dict]:
    """One decode step against a block-pooled (paged) KV cache.

    cache: {"layers": {"k"/"v": (L, nb, bs, KVH, Dh)}, "pos": (B,)};
    block_tables: (B, max_blocks) int32 physical block ids (0 = sink).
    tokens: (B, 1) int32.  Returns (logits (B, Vp), cache).

    Only wired for pure-attention-cache families (build_model gates
    ssm / rwkv / hybrid / enc-dec off the pooled-KV path).
    """
    cdt = _dt(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens, cdt)
    pos = cache["pos"]

    def body(carry, inp):
        bp, kl, vl = inp
        x = carry
        x, kl, vl = B.block_decode_paged(cfg, bp, x, kl, vl, block_tables,
                                         pos, None, uk)
        return x, {"k": kl, "v": vl}

    x, new_layers = maybe_scan(
        body, x,
        (params["blocks"], cache["layers"]["k"], cache["layers"]["v"]),
        cfg.n_layers, rcfg.unroll_layers)
    with jax.named_scope("head"):
        x = rmsnorm(params["final_ln"], x)
        logits = x[:, -1] @ head_weight(cfg, params, cdt)
    return logits, {"layers": new_layers, "pos": pos + 1}


def lm_decode_window_pool(cfg: ModelConfig, params: dict, cache: dict,
                          tokens: jax.Array, block_tables: jax.Array,
                          rcfg: RunConfig) -> Tuple[jax.Array, dict]:
    """W sequential pooled decode steps in one dispatch (paged verify).

    Same contract and bitwise rationale as :func:`lm_decode_window`,
    scanning :func:`lm_decode_step_pool`.  tokens: (B, W) int32.
    """

    def body(c, tok):
        lg, c = lm_decode_step_pool(cfg, params, c, tok, block_tables, rcfg)
        return c, lg

    cache, lgs = jax.lax.scan(
        body, cache, jnp.moveaxis(tokens, 1, 0)[:, :, None])
    return jnp.moveaxis(lgs, 0, 1), cache


# ---------------------------------------------------------------------------
# cache + input specs
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    from repro.models.attention import cache_span

    span = cache_span(cfg, max_len)
    one = B.init_cache_layer(cfg, batch, span, dtype)
    layers = jax.tree.map(
        lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)
    cache = {"layers": layers, "pos": jnp.zeros((batch,), jnp.int32)}
    n_attn = B.n_attn_applications(cfg)
    if n_attn:
        cache["ak"] = jnp.zeros((n_attn, batch, span, cfg.n_kv_heads, cfg.head_dim),
                                dtype)
        cache["av"] = jnp.zeros_like(cache["ak"])
    return cache


def init_pool_cache(cfg: ModelConfig, n_lanes: int, n_blocks: int,
                    block_size: int, dtype) -> dict:
    """Pooled KV cache: ``n_blocks`` usable blocks + 1 sink (block id 0).

    Unlike :func:`init_cache` the pool is sized by LIVE TOKENS
    (``n_blocks * block_size`` positions per layer), not by
    lanes × worst-case length; per-lane block tables (engine-owned) map
    logical positions to pool slots.
    """
    shape = (cfg.n_layers, n_blocks + 1, block_size, cfg.n_kv_heads, cfg.head_dim)
    layers = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return {"layers": layers, "pos": jnp.zeros((n_lanes,), jnp.int32)}


def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                rcfg: RunConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train/prefill: {"tokens", ["frontend"]}; decode adds {"cache"}.
    """
    cdt = _dt(rcfg.compute_dtype)
    bsz = shape.global_batch
    specs: Dict[str, Any] = {}
    p = cfg.frontend_seq if cfg.frontend else 0
    if shape.kind in ("train", "prefill"):
        t_tok = shape.seq_len - p
        specs["tokens"] = jax.ShapeDtypeStruct((bsz, t_tok), jnp.int32)
        if p:
            specs["frontend"] = jax.ShapeDtypeStruct((bsz, p, cfg.d_model), cdt)
    else:  # decode: one new token against a cache of seq_len
        specs["tokens"] = jax.ShapeDtypeStruct((bsz, 1), jnp.int32)
        specs["cache"] = jax.eval_shape(
            functools.partial(init_cache, cfg, bsz, shape.seq_len, cdt))
    return specs
