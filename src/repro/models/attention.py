"""Attention: GQA (full causal / chunked-local / cross), with KV-cache decode.

Reference path is pure jnp (memory-safe blockwise softmax for long seqs via
the flash oracle in :mod:`repro.kernels.ref`); the Pallas kernels in
:mod:`repro.kernels` are routed in when ``use_kernels`` is on.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import apply_rope, truncated_normal

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    s = d ** -0.5
    p = {
        "wq": truncated_normal(ks[0], (d, qd), s, dtype),
        "wk": truncated_normal(ks[1], (d, kvd), s, dtype),
        "wv": truncated_normal(ks[2], (d, kvd), s, dtype),
        "wo": truncated_normal(ks[3], (qd, d), qd ** -0.5, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    return p


def _proj_qkv(params: dict, x: jax.Array, xkv: jax.Array, cfg: ModelConfig):
    q = x @ params["wq"]
    k = xkv @ params["wk"]
    v = xkv @ params["wv"]
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    b, t = x.shape[:2]
    tk = xkv.shape[1]
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, tk, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, tk, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, t, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, n_rep, d)).reshape(b, t, h * n_rep, d)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array], scale: float) -> jax.Array:
    """q (B,Tq,H,Dh), k/v (B,Tk,H,Dh) [already GQA-expanded]; mask broadcastable
    to (B,H,Tq,Tk) boolean (True = attend)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_mask(tq: int, tk: int, offset: int = 0) -> jax.Array:
    """True where kv position <= query position. offset = tk - tq alignment."""
    qpos = jnp.arange(tq)[:, None] + offset
    kpos = jnp.arange(tk)[None, :]
    return kpos <= qpos


def chunk_mask(tq: int, tk: int, chunk: int, offset: int = 0) -> jax.Array:
    """Causal AND same-chunk (llama4 iRoPE-style chunked attention)."""
    qpos = jnp.arange(tq)[:, None] + offset
    kpos = jnp.arange(tk)[None, :]
    return (kpos <= qpos) & (qpos // chunk == kpos // chunk)


def attention(params: dict, cfg: ModelConfig, x: jax.Array, *,
              positions: Optional[jax.Array] = None,
              use_rope: bool = True,
              causal: bool = True,
              use_kernels: bool = False) -> jax.Array:
    """Self-attention over full sequence (training / prefill)."""
    b, t, _ = x.shape
    q, k, v = _proj_qkv(params, x, x, cfg)
    if use_rope and cfg.position_embedding == "rope":
        if positions is None:
            positions = jnp.arange(t)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    scale = cfg.attn_scale
    local_chunk = cfg.chunk_size if cfg.attention == "chunked_local" else 0
    if use_kernels:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, scale=scale,
                                   chunk=local_chunk)
    elif t > 1024:
        # blockwise online-softmax path: the (T,T) score matrix would not fit
        from repro.models.flash_ref import flash_attention_ref
        out = flash_attention_ref(q, k, v, causal=causal, scale=scale,
                                  chunk=local_chunk)
    else:
        nrep = cfg.n_heads // cfg.n_kv_heads
        kk, vv = _repeat_kv(k, nrep), _repeat_kv(v, nrep)
        if local_chunk:
            mask = chunk_mask(t, t, local_chunk)[None, None]
        elif causal:
            mask = causal_mask(t, t)[None, None]
        else:
            mask = None
        out = sdpa(q, kk, vv, mask, scale)
    return out.reshape(b, t, cfg.q_dim) @ params["wo"]


def cross_attention(params: dict, cfg: ModelConfig, x: jax.Array,
                    enc_out: jax.Array) -> jax.Array:
    b, t, _ = x.shape
    q, k, v = _proj_qkv(params, x, enc_out, cfg)
    nrep = cfg.n_heads // cfg.n_kv_heads
    out = sdpa(q, _repeat_kv(k, nrep), _repeat_kv(v, nrep), None, cfg.attn_scale)
    return out.reshape(b, t, cfg.q_dim) @ params["wo"]


# ---------------------------------------------------------------------------
# KV-cache decode (layer-local API: caches are scanned over layers)
# ---------------------------------------------------------------------------

def cache_span(cfg: ModelConfig, max_len: int) -> int:
    """chunked_local archs only need the last ``chunk_size`` positions."""
    return max_len if cfg.attention != "chunked_local" else min(max_len, cfg.chunk_size)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  n_layers: Optional[int] = None) -> dict:
    L = cfg.n_layers if n_layers is None else n_layers
    span = cache_span(cfg, max_len)
    shape = (L, batch, span, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attn(params: dict, cfg: ModelConfig, x: jax.Array,
                ck: jax.Array, cv: jax.Array, pos: jax.Array, *,
                use_rope: bool = True,
                use_kernels: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode for one layer.

    x: (B,1,D); ck/cv: (B,span,KVH,Dh); pos: (B,) int32 per-lane positions
    (tokens seen) — per-lane so the serving engine can continuously batch.
    Returns (out (B,1,D), new ck, new cv).
    """
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k, v = _proj_qkv(params, x, x, cfg)
    if use_rope and cfg.position_embedding == "rope":
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    span = ck.shape[1]
    if cfg.attention == "chunked_local":
        slot = pos % span      # ring buffer: sliding-window approximation of
        #                        chunked attention at decode time (DESIGN §8)
    else:
        slot = jnp.minimum(pos, span - 1)
    lane = jnp.arange(b)
    ck = ck.at[lane, slot].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[lane, slot].set(v[:, 0].astype(cv.dtype))

    # valid positions: everything written so far (ring keeps only the window
    # for chunked_local, so "written" == "within window" by construction)
    kidx = jnp.arange(span)[None, :]
    valid = kidx <= jnp.minimum(pos, span - 1)[:, None]   # (B, span)
    if use_kernels:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                    valid, scale=cfg.attn_scale)
    else:
        nrep = cfg.n_heads // cfg.n_kv_heads
        kk = _repeat_kv(ck.astype(q.dtype), nrep)
        vv = _repeat_kv(cv.astype(q.dtype), nrep)
        mask = valid[:, None, None, :]                # -> (B,H,1,span)
        out = sdpa(q, kk, vv, mask, cfg.attn_scale)
    return out.reshape(b, 1, cfg.q_dim) @ params["wo"], ck, cv


def decode_attn_paged(params: dict, cfg: ModelConfig, x: jax.Array,
                      kp: jax.Array, vp: jax.Array, block_tables: jax.Array,
                      pos: jax.Array, *, use_rope: bool = True,
                      use_kernels: bool = False):
    """One-token decode for one layer against a PAGED KV pool.

    x: (B,1,D); kp/vp: (nb, bs, KVH, Dh) — the shared block pool for this
    layer (block 0 is the garbage sink); block_tables: (B, max_blocks)
    int32 physical block ids per lane; pos: (B,) int32 tokens seen.
    Returns (out (B,1,D), new kp, new vp).

    Token ``pos`` of a lane lives at physical slot
    ``block_tables[lane, pos // bs] * bs + pos % bs`` of the flattened
    pool; lanes own disjoint blocks so the scatter below cannot collide
    (idle lanes all point at the sink, whose content is never read).
    """
    b = x.shape[0]
    nb, bs = kp.shape[0], kp.shape[1]
    span_l = block_tables.shape[1] * bs           # per-lane logical capacity
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with jax.named_scope("attn"):
        q, k, v = _proj_qkv(params, x, x, cfg)
        if use_rope and cfg.position_embedding == "rope":
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
    p_eff = jnp.minimum(pos, span_l - 1)          # saturate like the dense path
    with jax.named_scope("kv_write"):
        lane = jnp.arange(b)
        dest = block_tables[lane, p_eff // bs] * bs + p_eff % bs   # (B,) flat
        kp = kp.reshape((nb * bs,) + kp.shape[2:]).at[dest].set(
            k[:, 0].astype(kp.dtype)).reshape(kp.shape)
        vp = vp.reshape((nb * bs,) + vp.shape[2:]).at[dest].set(
            v[:, 0].astype(vp.dtype)).reshape(vp.shape)
    with jax.named_scope("attn"):
        out = paged_attend(cfg, q, kp, vp, block_tables, p_eff,
                           use_kernels=use_kernels)
        out = out.reshape(b, 1, cfg.q_dim) @ params["wo"]
    return out, kp, vp


def paged_attend(cfg: ModelConfig, q: jax.Array, kp: jax.Array,
                 vp: jax.Array, block_tables: jax.Array, p_eff: jax.Array, *,
                 use_kernels: bool = False) -> jax.Array:
    """One query per lane (B,1,H,Dh) against one layer's block pool, lane
    positions 0..p_eff; (B,1,H,Dh) out.  The paged kernel, or the gather
    reference that materialises each lane's logical KV view."""
    b = q.shape[0]
    span_l = block_tables.shape[1] * kp.shape[1]
    if use_kernels:
        from repro.kernels import ops as kops
        return kops.paged_decode_attention(q, kp.astype(q.dtype),
                                           vp.astype(q.dtype), block_tables,
                                           p_eff, scale=cfg.attn_scale)
    ck = kp[block_tables].reshape(b, span_l, cfg.n_kv_heads, cfg.head_dim)
    cv = vp[block_tables].reshape(b, span_l, cfg.n_kv_heads, cfg.head_dim)
    valid = jnp.arange(span_l)[None, :] <= p_eff[:, None]  # (B, span_l)
    nrep = cfg.n_heads // cfg.n_kv_heads
    kk = _repeat_kv(ck.astype(q.dtype), nrep)
    vv = _repeat_kv(cv.astype(q.dtype), nrep)
    return sdpa(q, kk, vv, valid[:, None, None, :], cfg.attn_scale)


def prefill_attn(params: dict, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, span: int, *,
                 use_rope: bool = True,
                 use_kernels: bool = False):
    """Full self-attention AND the K/V cache content for one layer.

    Returns (out (B,T,D), ck (B,span,KVH,Dh), cv)."""
    b, t, _ = x.shape
    scale = cfg.attn_scale
    local_chunk = cfg.chunk_size if cfg.attention == "chunked_local" else 0
    with jax.named_scope("attn"):
        q, k, v = _proj_qkv(params, x, x, cfg)
        if use_rope and cfg.position_embedding == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if use_kernels:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=True, scale=scale,
                                       chunk=local_chunk)
        elif t > 1024:
            from repro.models.flash_ref import flash_attention_ref
            out = flash_attention_ref(q, k, v, causal=True, scale=scale,
                                      chunk=local_chunk)
        else:
            nrep = cfg.n_heads // cfg.n_kv_heads
            if local_chunk:
                mask = chunk_mask(t, t, local_chunk)[None, None]
            else:
                mask = causal_mask(t, t)[None, None]
            out = sdpa(q, _repeat_kv(k, nrep), _repeat_kv(v, nrep), mask,
                       scale)
        out = out.reshape(b, t, cfg.q_dim) @ params["wo"]
    with jax.named_scope("kv_write"):
        if t >= span:                                 # chunked_local: keep tail
            ck, cv = k[:, t - span:], v[:, t - span:]
        else:
            pad = jnp.zeros((b, span - t) + k.shape[2:], k.dtype)
            ck, cv = jnp.concatenate([k, pad], 1), jnp.concatenate([v, pad], 1)
    return out, ck, cv
