"""Plain float32 reference of a granitemoehybrid decoder (granite-4.0-h).

Granite 4.0-H (ibm-granite/granite-4.0-h-small, ``model_type``
``granitemoehybrid``), as the published description gives it:

* the token embedding times ``embedding_multiplier``;
* per layer, pre-norm (RMSNorm, ``rms_norm_eps``): the mixer of
  ``layer_types[i]``, its output times ``residual_multiplier`` added to
  the stream; then the expert block on the normed stream, its output times
  ``residual_multiplier`` added;
* Mamba2 mixer: ``in_proj`` (no bias) into z, xBC, dt; a depthwise causal
  conv of width ``mamba_d_conv`` with bias on xBC, then SiLU; x, B, C split
  (one group); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
  exact sequential recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``; the gated RMSNorm ``rmsnorm(y * silu(z))``;
  ``out_proj`` (no bias);
* attention mixer: grouped-query attention with full causal masking, no
  position embedding, scores scaled by ``attention_multiplier``;
* expert block: router logits over all ``published.num_local_experts``,
  the top ``num_experts_per_tok`` kept and softmaxed over those alone; each
  expert a SwiGLU ``(silu(x Wg) * (x Wi)) Wo`` of width
  ``intermediate_size``, weighted by its gate; plus the shared SwiGLU
  expert of width ``shared_intermediate_size``;
* a final RMSNorm and a head tied to the token embedding, the logits
  divided by ``logits_scaling``.

Departures, each the configuration's cut or the program's convention:
only the experts this chip holds (``held_experts``) are computed, the
absent ones add nothing, as on one chip of the stated expert-parallel
deployment; the tied head is scaled by ``1 / sqrt(hidden_size)`` as the
program scales it.

Everything runs in float32 with every matrix product at ``HIGHEST``, one
layer at a time: the weights are made again from the seed by
:mod:`weights_hybrid` and each layer is upcast on its own.  Nothing of the
program is imported.  ``quant="int8"`` is the control: every matrix
product of the layers, the router and the head takes int8 weights
(symmetric, per output column) and int8 activations (symmetric, per row),
as a W8A8 path would.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights_hybrid

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant: Optional[str]):
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _static(config: dict) -> Tuple:
    n = weights_hybrid.dims(config)
    return (n["d_in"], n["mh"], n["mp"], n["ns"], n["conv"], n["h"], n["g"],
            n["dh"], n["topk"], n["held"][0],
            float(config["rms_norm_eps"]),
            float(config["residual_multiplier"]),
            float(config["attention_multiplier"]))


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def mamba_layer(p, x, *, s: Tuple, quant=None):
    """One Mamba2 layer on one sequence x (T, d), float32."""
    d_in, H, P, N, K = s[:5]
    eps, r = s[10], s[11]
    t = x.shape[0]
    m = p["mamba"]
    proj = _mm(rmsnorm(x, p["ln"]["scale"], eps), m["in_proj"], quant)
    z, xbc, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * N], \
        proj[:, 2 * d_in + 2 * N:]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = sum(xp[i:i + t] * m["conv_w"][i] for i in range(K)) + m["conv_b"]
    conv = jax.nn.silu(conv)
    xs, B, C = conv[:, :d_in].reshape(t, H, P), conv[:, d_in:d_in + N], \
        conv[:, d_in + N:]
    dt = jax.nn.softplus(dt + m["dt_bias"])                     # (T, H)
    A = -jnp.exp(m["A_log"])

    def step(S, inp):
        xt, dtt, bt, ct = inp
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, ct, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, dt, B, C))
    y = (y + m["D"][None, :, None] * xs).reshape(t, d_in)
    y = rmsnorm(y * jax.nn.silu(z), m["norm_scale"], eps)
    return x + r * _mm(y, m["out_proj"], quant)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def attn_layer(p, x, *, s: Tuple, quant=None):
    """One NoPE attention layer on one sequence x (T, d), float32."""
    h_, g_, dh = s[5:8]
    eps, r, scale = s[10], s[11], s[12]
    t = x.shape[0]
    a = p["attn"]
    h = rmsnorm(x, p["ln"]["scale"], eps)
    q = _mm(h, a["wq"], quant).reshape(t, g_, h_ // g_, dh)
    k = _mm(h, a["wk"], quant).reshape(t, g_, dh)
    v = _mm(h, a["wv"], quant).reshape(t, g_, dh)
    sc = jnp.einsum("tgrd,sgd->grts", q, k, precision=HI) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(sc, -1), v, precision=HI)
    return x + r * _mm(o.reshape(t, h_ * dh), a["wo"], quant)


def _swiglu(h, wi, wg, wo, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wi, quant), wo, quant)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def moe_layer(p, x, *, s: Tuple, quant=None):
    """One expert block on one sequence x (T, d), float32: the held
    experts' part of the routed sum, and the shared expert."""
    top_k, lo = s[8], s[9]
    eps, r = s[10], s[11]
    m = p["moe"]
    h = rmsnorm(x, p["ln"]["scale"], eps)
    logits = _mm(h, m["router"], quant)                       # (T, E)
    vals, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(vals, -1)
    y = _swiglu(h, m["shared"]["wi"], m["shared"]["wg"], m["shared"]["wo"],
                quant)
    for e in range(m["wi"].shape[0]):
        w = jnp.sum(jnp.where(idx == lo + e, gates, 0.0), -1)  # (T,)
        y = y + w[:, None] * _swiglu(h, m["wi"][e], m["wg"][e], m["wo"][e],
                                     quant)
    return x + r * y


@jax.jit
def _take(tree, i):
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), tree)


def _layers(config: dict, params, xs: List, quant=None) -> List:
    n = weights_hybrid.dims(config)
    s = _static(config)
    blocks = params["blocks"]
    im = ia = 0
    for i, kind in enumerate(n["kinds"]):
        if kind == "mamba":
            lp = _take(blocks["mamba"], im)
            xs = [mamba_layer(lp, x, s=s, quant=quant) for x in xs]
            im += 1
        else:
            lp = _take(blocks["attn"], ia)
            xs = [attn_layer(lp, x, s=s, quant=quant) for x in xs]
            ia += 1
        del lp
        lp = _take(blocks["moe"], i)
        xs = [moe_layer(lp, x, s=s, quant=quant) for x in xs]
        del lp
    return xs


def _head(config: dict, params) -> jax.Array:
    n = weights_hybrid.dims(config)
    e = params["embed"]["tok"][:n["vocab"]].astype(jnp.float32)
    return e * (n["d"] ** -0.5 / float(config["logits_scaling"]))


def _embed(config: dict, params, toks) -> jax.Array:
    return params["embed"]["tok"][jnp.asarray(toks)].astype(jnp.float32) \
        * float(config["embedding_multiplier"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _gaps(final, head, x, xc, nxt, *, eps, quant):
    """Per position: the reference's best logit less the logit of the
    served next token, and less the logit of the control's first choice."""
    fin = final.astype(jnp.float32)
    logits = jnp.matmul(rmsnorm(x, fin, eps), head.T, precision=HI)
    best = jnp.max(logits, -1)
    served = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
    if xc is None:
        return best - served, None
    lc = _mm(rmsnorm(xc, fin, eps), head.T, quant)
    pick = jnp.argmax(lc, -1)
    return best - served, best - jnp.take_along_axis(logits, pick[:, None],
                                                     -1)[:, 0]


def logit_gaps(config: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int]],
               pad_to: int, control: Optional[str] = None
               ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """As ``reference/granite.py``: per sequence, the gap of every served
    token, and with ``control`` the gap of the control's first choice."""
    eps = float(config["rms_norm_eps"])
    params = weights_hybrid.make(config, seed)
    padded = []
    for toks, _ in seqs:
        buf = np.zeros((pad_to,), np.int32)
        buf[:len(toks)] = toks
        padded.append(buf)
    xs = [_embed(config, params, b) for b in padded]
    xcs = _layers(config, params, list(xs), control) if control else None
    xs = _layers(config, params, xs)
    head, final = _head(config, params), params["final_ln"]["scale"]
    del params
    served, ctl = [], [] if control else None
    for j, ((toks, first), b) in enumerate(zip(seqs, padded)):
        nxt = np.zeros((pad_to,), np.int32)
        nxt[:len(toks) - 1] = b[1:len(toks)]
        g, gc = _gaps(final, head, xs[j], None if xcs is None else xcs[j],
                      jnp.asarray(nxt), eps=eps, quant=control)
        sl = slice(first - 1, len(toks) - 1)
        served.append(np.asarray(g)[sl])
        if control:
            ctl.append(np.asarray(gc)[sl])
    return served, ctl


def logits(config: dict, seed: int, tokens: np.ndarray,
           quant: Optional[str] = None) -> np.ndarray:
    """Logits (T, vocab) of one short sequence at every position."""
    params = weights_hybrid.make(config, seed)
    x, = _layers(config, params, [_embed(config, params, tokens)], quant)
    h = rmsnorm(x, params["final_ln"]["scale"].astype(jnp.float32),
                float(config["rms_norm_eps"]))
    return np.asarray(jnp.matmul(h, _head(config, params).T, precision=HI))
