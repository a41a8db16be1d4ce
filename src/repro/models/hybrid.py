"""granitemoehybrid: a Mamba2 or NoPE-attention mixer per layer, each layer
followed by its own dropless expert FFN (granite-4.0-h).

Layer ``i`` is of kind ``cfg.layer_types[i]``; with residual multiplier r::

    x = x + r * mixer_i(rmsnorm(x))          mixer: Mamba2 or attention
    x = x + r * moe_i(rmsnorm(x))            routed experts + shared expert

The embedding is multiplied by ``cfg.embedding_multiplier`` and the logits
divided by ``cfg.logits_scaling``.  The layers are scanned by period of
the layer pattern (granite-4.0-h: ``m m m m m A m m m m``), the period's
layers in published order inside the scan body, each layer's weights
indexed from the whole stack of its kind.

Params::

    {"embed": {"tok": (Vp, D)},
     "blocks": {"mamba": {"ln", "mamba"}     stacked (n_mamba, ...),
                "attn":  {"ln", "attn"}      stacked (n_attn, ...),
                "moe":   {"ln", "moe"}       stacked (L, ...)},
     "final_ln": {"scale": (D,)}}

Caches (every state leaf has the lane axis second)::

    {"layers": {"k", "v": (n_attn, B, span, KVH, Dh)},          dense lanes
     "state": {"conv": (n_mamba, B, K-1, C), "ssm": (n_mamba, B, H, P, N)},
     "pos": (B,)}

The pool (``init_pool_cache``) keeps the attention K/V in blocks,
``(n_attn, n_blocks + 1, block, KVH, Dh)``, the same ``state`` with one
fixed slot per lane, and ``counters.held_slots``: token-slots of active
lanes routed to the held experts, summed over decode steps and layers.
The Mamba2 state is float32.  The decode step carries the pool and the
state through its scan and updates them in place (donated by the
backend): each lane's state slot and one K/V position per lane and
attention layer are written, nothing is restacked through the scan's
outputs.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (chunked_xent, embed_tokens, init_embed,
                                 init_rmsnorm, rmsnorm)

KINDS = ("mamba", "attention")


def pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    """The shortest period of the layer pattern that divides the depth."""
    types = tuple(cfg.layer_types[:cfg.n_layers])
    if len(types) != cfg.n_layers or not set(types) <= set(KINDS):
        raise ValueError(f"layer_types must give one of {KINDS} for each of "
                         f"{cfg.n_layers} layers: {types!r}")
    for p in range(1, cfg.n_layers + 1):
        if cfg.n_layers % p == 0 and all(
                types[i] == types[i % p] for i in range(cfg.n_layers)):
            pat = types[:p]
            break
    if set(pat) != set(KINDS):
        raise ValueError(f"a hybrid needs both mixers in its pattern: {pat!r}")
    return pat


def layout(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, int, int]:
    """(period pattern, periods, mamba layers, attention layers)."""
    pat = pattern(cfg)
    periods = cfg.n_layers // len(pat)
    return (pat, periods, periods * pat.count("mamba"),
            periods * pat.count("attention"))


def _residual(cfg: ModelConfig, x, h):
    r = cfg.residual_multiplier
    return x + (h if r == 1.0 else h * jnp.asarray(r, h.dtype))


def _embed(cfg: ModelConfig, params, tokens, cdt):
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens, cdt)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, cdt)
    return x


def head_weight(cfg: ModelConfig, params: dict, dtype) -> jax.Array:
    """The tied head, scaled as :func:`repro.models.lm.head_weight` scales
    it, divided by ``logits_scaling``."""
    from repro.models.lm import head_weight as tied
    w = tied(cfg, params, dtype)
    if cfg.logits_scaling != 1.0:
        w = w / jnp.asarray(cfg.logits_scaling, dtype)
    return w


def _layer(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                        tree)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_hybrid(cfg: ModelConfig, key, dtype) -> dict:
    _, _, n_m, n_a = layout(cfg)
    ks = jax.random.split(key, 4)
    d = cfg.d_model

    def stack(k, n, fn):
        return jax.vmap(fn)(jax.random.split(k, n))

    blocks = {
        "mamba": stack(ks[1], n_m, lambda k: {
            "ln": init_rmsnorm(d, dtype),
            "mamba": ssm_mod.init_mamba2(k, cfg, dtype)}),
        "attn": stack(ks[2], n_a, lambda k: {
            "ln": init_rmsnorm(d, dtype),
            "attn": attn.init_attention(k, cfg, dtype)}),
        "moe": stack(ks[3], cfg.n_layers, lambda k: {
            "ln": init_rmsnorm(d, dtype),
            "moe": moe_mod.init_moe(k, cfg, dtype)}),
    }
    return {"embed": init_embed(ks[0], cfg.vocab_size, d, dtype),
            "blocks": blocks, "final_ln": init_rmsnorm(d, dtype)}


# ---------------------------------------------------------------------------
# one layer's pieces
# ---------------------------------------------------------------------------

def _moe(cfg: ModelConfig, stack, i, x, token_mask=None):
    """Layer ``i``'s expert block, from the stack of every layer's."""
    with jax.named_scope("moe"):
        h = rmsnorm(_layer(stack["ln"], i), x, cfg.norm_eps)
        y, n_held = moe_mod.apply_moe_dropless(stack["moe"], cfg, h,
                                               token_mask, layer=i)
    return _residual(cfg, x, y), n_held


def _mamba(cfg: ModelConfig, w, x, uk: bool, lengths=None):
    """One Mamba2 layer over a prompt from a zero state."""
    with jax.named_scope("mamba"):
        return _mamba_body(cfg, w, x, uk, None, lengths)


def _mamba_body(cfg: ModelConfig, w, x, uk: bool, state, lengths):
    h, st = ssm_mod.apply_mamba2(w["mamba"], cfg,
                                 rmsnorm(w["ln"], x, cfg.norm_eps),
                                 use_kernels=uk, state=state, lengths=lengths)
    return _residual(cfg, x, h), {"conv": st["conv"].astype(jnp.float32),
                                  "ssm": st["ssm"]}


def _mamba_slot(cfg: ModelConfig, w, x, conv, ssm, l):
    """One Mamba2 layer of a decode step on layer ``l``'s state slots,
    read from and written back into the stacks ``conv`` and ``ssm`` in
    place; the reads and writes are the layer's own traffic, so they sit
    under its ``mamba`` scope (``conv``, ``ssm_state``)."""
    with jax.named_scope("mamba"):
        st = {"conv": jax.lax.dynamic_index_in_dim(conv, l, 0, False),
              "ssm": jax.lax.dynamic_index_in_dim(ssm, l, 0, False)}
        x, st = _mamba_body(cfg, w, x, False, st, None)
        with jax.named_scope("conv"):
            conv = jax.lax.dynamic_update_index_in_dim(conv, st["conv"], l, 0)
        with jax.named_scope("ssm_state"):
            ssm = jax.lax.dynamic_update_index_in_dim(ssm, st["ssm"], l, 0)
    return x, conv, ssm


# ---------------------------------------------------------------------------
# full sequence: train and prefill
# ---------------------------------------------------------------------------

def _trunk(cfg: ModelConfig, params: dict, tokens: jax.Array,
           rcfg: RunConfig, span: int, lengths: Optional[jax.Array]):
    """Prompt forward.  Returns (hidden (B,T,D) after the final norm,
    caches in the dense-lane layout with K/V padded to ``span``)."""
    cdt = jnp.dtype(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    pat, periods, _, _ = layout(cfg)
    x = _embed(cfg, params, tokens, cdt)
    bsz, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t), (bsz, t))
    blocks = params["blocks"]
    nm, na = pat.count("mamba"), pat.count("attention")

    def body(x, i):
        # each layer's weights indexed from the whole stack: a slice of the
        # period's stack first would be copied whole
        ks, vs, convs, ssms = [], [], [], []
        for j, kind in enumerate(pat):
            if kind == "mamba":
                x, st = _mamba(cfg, _layer(blocks["mamba"], i * nm + len(convs)),
                               x, uk, lengths=lengths)
                convs.append(st["conv"])
                ssms.append(st["ssm"])
            else:
                wj = _layer(blocks["attn"], i * na + len(ks))
                h, ck, cv = attn.prefill_attn(
                    wj["attn"], cfg, rmsnorm(wj["ln"], x, cfg.norm_eps),
                    positions, span, use_kernels=uk)
                x = _residual(cfg, x, h)
                ks.append(ck.astype(cdt))
                vs.append(cv.astype(cdt))
            x, _ = _moe(cfg, blocks["moe"], i * len(pat) + j, x)
        return x, (jnp.stack(ks), jnp.stack(vs), jnp.stack(convs),
                   jnp.stack(ssms))

    fn = jax.checkpoint(body, prevent_cse=False) if rcfg.remat else body
    x, (k, v, conv, ssm) = jax.lax.scan(fn, x, jnp.arange(periods))
    with jax.named_scope("head"):
        x = rmsnorm(params["final_ln"], x, cfg.norm_eps)

    def flat(a):
        return a.reshape((-1,) + a.shape[2:])
    return x, {"layers": {"k": flat(k), "v": flat(v)},
               "state": {"conv": flat(conv), "ssm": flat(ssm)}}


def hybrid_loss(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array],
                rcfg: RunConfig):
    cdt = jnp.dtype(rcfg.compute_dtype)
    tokens = batch["tokens"]
    x, _ = _trunk(cfg, params, tokens, rcfg, tokens.shape[1], None)
    ce = chunked_xent(x[:, :-1], head_weight(cfg, params, cdt), tokens[:, 1:],
                      cfg.vocab_size)
    return ce, {"ce": ce, "aux": jnp.float32(0.0)}


def hybrid_prefill(cfg: ModelConfig, params: dict,
                   batch: Dict[str, jax.Array], rcfg: RunConfig,
                   max_len: int, lengths: Optional[jax.Array] = None):
    """Prefill of exact-length prompts, or of right-padded ones with true
    ``lengths`` (B,).  Right padding is inert: attention is causal and pads
    sit after every real token, the dropless expert layer routes each
    token alone, ``dt`` is 0 at pads (the SSM state passes through them)
    and the conv tail is gathered at each true length.  Returns
    (logits at each lane's last real position (B, Vp), dense-lane cache)."""
    cdt = jnp.dtype(rcfg.compute_dtype)
    tokens = batch["tokens"]
    bsz, t = tokens.shape
    x, cache = _trunk(cfg, params, tokens, rcfg, max_len, lengths)
    with jax.named_scope("head"):
        if lengths is None:
            h = x[:, -1]
            pos = jnp.full((bsz,), t, jnp.int32)
        else:
            pos = jnp.asarray(lengths, jnp.int32)
            h = x[jnp.arange(bsz), pos - 1]
        logits = h @ head_weight(cfg, params, cdt)
    cache["pos"] = pos
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_layers(cfg: ModelConfig, params: dict, x, carry, attend,
                   token_mask):
    """The layers of one decode step, scanned by period.  ``carry`` holds
    the K/V store and the state leaves, each with its layer axis first;
    ``attend(wj, x, k, v, layer)`` runs one attention layer and returns
    (h, k, v).  The Mamba2 state of layer ``l`` is read from and written
    back into the carry at ``l``; each layer's weights are indexed from
    the whole stack (a slice of the period's stack would be copied)."""
    pat, periods, _, _ = layout(cfg)
    nm, na = pat.count("mamba"), pat.count("attention")
    blocks = params["blocks"]

    def body(c, i):
        x, k, v, conv, ssm, held = c
        im = ia = 0
        for j, kind in enumerate(pat):
            if kind == "mamba":
                l = i * nm + im
                x, conv, ssm = _mamba_slot(cfg, _layer(blocks["mamba"], l), x,
                                           conv, ssm, l)
                im += 1
            else:
                h, k, v = attend(_layer(blocks["attn"], i * na + ia), x, k, v,
                                 i * na + ia)
                x = _residual(cfg, x, h)
                ia += 1
            x, n = _moe(cfg, blocks["moe"], i * len(pat) + j, x, token_mask)
            held = held + n
        return (x, k, v, conv, ssm, held), None

    c, _ = jax.lax.scan(body, (x,) + tuple(carry) + (jnp.int32(0),),
                        jnp.arange(periods))
    return c


def _logits(cfg, params, x, cdt):
    with jax.named_scope("head"):
        x = rmsnorm(params["final_ln"], x, cfg.norm_eps)
        return x[:, -1] @ head_weight(cfg, params, cdt)


def hybrid_decode_step(cfg: ModelConfig, params: dict, cache: dict,
                       tokens: jax.Array, rcfg: RunConfig):
    """One decode step on dense lanes.  tokens (B, 1)."""
    cdt = jnp.dtype(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    x = _embed(cfg, params, tokens, cdt)
    pos = cache["pos"]

    def attend(wj, x, k, v, l):
        with jax.named_scope("attn"):
            h, ck, cv = attn.decode_attn(
                wj["attn"], cfg, rmsnorm(wj["ln"], x, cfg.norm_eps),
                jax.lax.dynamic_index_in_dim(k, l, 0, False),
                jax.lax.dynamic_index_in_dim(v, l, 0, False), pos,
                use_kernels=uk)
        return (h, jax.lax.dynamic_update_index_in_dim(k, ck, l, 0),
                jax.lax.dynamic_update_index_in_dim(v, cv, l, 0))

    st = cache["state"]
    x, k, v, conv, ssm, _ = _decode_layers(
        cfg, params, x, (cache["layers"]["k"], cache["layers"]["v"],
                         st["conv"], st["ssm"]), attend, None)
    return _logits(cfg, params, x, cdt), {
        "layers": {"k": k, "v": v}, "state": {"conv": conv, "ssm": ssm},
        "pos": pos + 1}


def hybrid_decode_step_pool(cfg: ModelConfig, params: dict, cache: dict,
                            tokens: jax.Array, block_tables: jax.Array,
                            rcfg: RunConfig):
    """One decode step against the pool: attention K/V in blocks (block 0
    the sink), one state slot per lane.  A lane whose table starts with
    the sink is idle: its tokens are left out of the expert-load counter.
    tokens (B, 1); block_tables (B, max_blocks) int32."""
    cdt = jnp.dtype(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    x = _embed(cfg, params, tokens, cdt)
    pos = cache["pos"]
    b = tokens.shape[0]
    kp, vp = cache["layers"]["k"], cache["layers"]["v"]
    n_a, nb, bs = kp.shape[:3]
    span_l = block_tables.shape[1] * bs
    p_eff = jnp.minimum(pos, span_l - 1)
    lane = jnp.arange(b)
    dest = block_tables[lane, p_eff // bs] * bs + p_eff % bs        # (B,)
    active = (block_tables[:, 0] > 0)[:, None]                      # (B, 1)

    def attend(wj, x, k, v, l):
        with jax.named_scope("attn"):
            hin = rmsnorm(wj["ln"], x, cfg.norm_eps)
            q, kn, vn = attn._proj_qkv(wj["attn"], hin, hin, cfg)
        with jax.named_scope("kv_write"):
            flat = (n_a, nb * bs) + k.shape[3:]
            k = k.reshape(flat).at[l, dest].set(
                kn[:, 0].astype(k.dtype)).reshape(k.shape)
            v = v.reshape(flat).at[l, dest].set(
                vn[:, 0].astype(v.dtype)).reshape(v.shape)
        with jax.named_scope("attn"):
            out = attn.paged_attend(
                cfg, q, jax.lax.dynamic_index_in_dim(k, l, 0, False),
                jax.lax.dynamic_index_in_dim(v, l, 0, False), block_tables,
                p_eff, use_kernels=uk)
            h = out.reshape(b, 1, cfg.q_dim) @ wj["attn"]["wo"]
        return h, k, v

    st = cache["state"]
    x, k, v, conv, ssm, held = _decode_layers(
        cfg, params, x, (kp, vp, st["conv"], st["ssm"]), attend, active)
    return _logits(cfg, params, x, cdt), {
        "layers": {"k": k, "v": v}, "state": {"conv": conv, "ssm": ssm},
        "counters": {"held_slots": cache["counters"]["held_slots"] + held},
        "pos": pos + 1}


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _state(cfg: ModelConfig, n_m: int, lanes: int) -> dict:
    _, nheads, conv_dim = ssm_mod.dims(cfg)
    return {"conv": jnp.zeros((n_m, lanes, cfg.ssm_conv - 1, conv_dim),
                              jnp.float32),
            "ssm": jnp.zeros((n_m, lanes, nheads, cfg.ssm_headdim,
                              cfg.ssm_state), jnp.float32)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    _, _, n_m, n_a = layout(cfg)
    shape = (n_a, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"layers": {"k": jnp.zeros(shape, dtype),
                       "v": jnp.zeros(shape, dtype)},
            "state": _state(cfg, n_m, batch),
            "pos": jnp.zeros((batch,), jnp.int32)}


def init_pool_cache(cfg: ModelConfig, n_lanes: int, n_blocks: int,
                    block_size: int, dtype) -> dict:
    _, _, n_m, n_a = layout(cfg)
    shape = (n_a, n_blocks + 1, block_size, cfg.n_kv_heads, cfg.head_dim)
    return {"layers": {"k": jnp.zeros(shape, dtype),
                       "v": jnp.zeros(shape, dtype)},
            "state": _state(cfg, n_m, n_lanes),
            "counters": {"held_slots": jnp.zeros((), jnp.int32)},
            "pos": jnp.zeros((n_lanes,), jnp.int32)}


def input_specs(cfg: ModelConfig, shape, rcfg: RunConfig) -> Dict:
    cdt = jnp.dtype(rcfg.compute_dtype)
    bsz = shape.global_batch
    if shape.kind in ("train", "prefill"):
        return {"tokens": jax.ShapeDtypeStruct((bsz, shape.seq_len),
                                               jnp.int32)}
    return {"tokens": jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
            "cache": jax.eval_shape(
                lambda: init_cache(cfg, bsz, shape.seq_len, cdt))}

