"""Plain reference of the granitemoehybrid forward pass
(:mod:`repro.models.hybrid`'s function, written out straight).

It follows the published description of Granite 4.0-H
(ibm-granite/granite-4.0-h-small, ``model_type`` ``granitemoehybrid``):
embedding times ``embedding_multiplier``; per layer a pre-norm Mamba2 or
attention mixer and then a pre-norm expert block, each branch times
``residual_multiplier`` into the stream; Mamba2 with a depthwise causal
conv (bias) and SiLU, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
the exact sequential recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
B_t^T``, ``y_t = S_t C_t + D x_t``, the gated RMSNorm ``rmsnorm(y *
silu(z))``; attention with full causal masking, no position embedding,
scores scaled by ``attention_multiplier``; the router's top-k logits
softmaxed over those k alone, each expert a SwiGLU, plus the shared
SwiGLU expert; a final RMSNorm and the tied head, logits divided by
``logits_scaling``.

Departures: only the experts the configuration holds are computed (the
absent ones add nothing, as on one chip of an expert-parallel
deployment), and the tied head is scaled by ``1 / sqrt(d_model)`` as the
program scales it.  One sequence at a time, no cache, no batching, no
kernels; every matrix product at ``HIGHEST`` in ``dtype`` (float32; a
lower type shows what the tests' tolerances catch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mamba(cfg: ModelConfig, p, x):
    d_in = cfg.ssm_expand * cfg.d_model
    H, P, N, K = d_in // cfg.ssm_headdim, cfg.ssm_headdim, cfg.ssm_state, \
        cfg.ssm_conv
    t = x.shape[0]
    proj = _mm(x, p["in_proj"])
    z, xbc, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * N], \
        proj[:, 2 * d_in + 2 * N:]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), x.dtype), xbc], 0)
    conv = jax.nn.silu(sum(xp[i:i + t] * p["conv_w"][i] for i in range(K))
                       + p["conv_b"])
    xs, B, C = conv[:, :d_in].reshape(t, H, P), conv[:, d_in:d_in + N], \
        conv[:, d_in + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(x.dtype))
    A = -jnp.exp(p["A_log"].astype(x.dtype))
    S = jnp.zeros((H, P, N), x.dtype)
    ys = []
    for i in range(t):
        S = jnp.exp(dt[i] * A)[:, None, None] * S \
            + (dt[i][:, None] * xs[i])[:, :, None] * B[i][None, None, :]
        ys.append(jnp.einsum("hpn,n->hp", S, C[i], precision=HI))
    y = (jnp.stack(ys) + p["D"].astype(x.dtype)[None, :, None] * xs)
    y = _norm(y.reshape(t, d_in) * jax.nn.silu(z), p["norm_scale"],
              cfg.norm_eps)
    return _mm(y, p["out_proj"])


def _attention(cfg: ModelConfig, p, x):
    t = x.shape[0]
    h, g, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(x, p["wq"]).reshape(t, g, h // g, dh)
    k = _mm(x, p["wk"]).reshape(t, g, dh)
    v = _mm(x, p["wv"]).reshape(t, g, dh)
    s = jnp.einsum("tgrd,sgd->grts", q, k, precision=HI) * cfg.attn_scale
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], s,
                  -jnp.inf)
    o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(t, h * dh), p["wo"])


def _swiglu(x, wi, wg, wo):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wi), wo)


def moe(cfg: ModelConfig, p, x):
    """The held experts' part of the routed sum, and the shared expert."""
    logits = _mm(x, p["router"].astype(x.dtype))
    if cfg.router == "topk_softmax":
        vals, idx = jax.lax.top_k(logits, cfg.top_k)
        gates = jax.nn.softmax(vals, -1)
    else:
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    lo, hi = cfg.held_range
    y = _swiglu(x, p["shared"]["wi"], p["shared"]["wg"], p["shared"]["wo"]) \
        if "shared" in p else jnp.zeros_like(x)
    for e in range(hi - lo):
        w = jnp.sum(jnp.where(idx == lo + e, gates, 0.0), -1)
        y = y + w[:, None] * _swiglu(x, p["wi"][e], p["wg"][e], p["wo"][e])
    return y


def forward(cfg: ModelConfig, params: dict, tokens,
            dtype=jnp.float32) -> jax.Array:
    """Logits (T, Vp) of one sequence ``tokens`` (T,) at every position."""
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
    r = cfg.residual_multiplier
    x = cast(params["embed"]["tok"])[jnp.asarray(tokens)] \
        * cfg.embedding_multiplier
    blocks = params["blocks"]
    im = ia = 0
    for i, kind in enumerate(cfg.layer_types[:cfg.n_layers]):
        if kind == "mamba":
            p = cast(jax.tree.map(lambda a: a[im], blocks["mamba"]))
            x = x + r * _mamba(cfg, p["mamba"],
                               _norm(x, p["ln"]["scale"], cfg.norm_eps))
            im += 1
        else:
            p = cast(jax.tree.map(lambda a: a[ia], blocks["attn"]))
            x = x + r * _attention(cfg, p["attn"],
                                   _norm(x, p["ln"]["scale"], cfg.norm_eps))
            ia += 1
        p = cast(jax.tree.map(lambda a: a[i], blocks["moe"]))
        x = x + r * moe(cfg, p["moe"], _norm(x, p["ln"]["scale"],
                                             cfg.norm_eps))
    x = _norm(x, params["final_ln"]["scale"], cfg.norm_eps)
    head = cast(params["embed"]["tok"]).T * (cfg.d_model ** -0.5) \
        / cfg.logits_scaling
    return _mm(x, head)
