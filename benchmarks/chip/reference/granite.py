"""Plain float32 reference of the granite decoder (llama architecture).

Granite-8B-Code (arXiv:2405.04324; ibm-granite/granite-8b-code-base):
pre-norm decoder, RMSNorm (eps from the configuration), rotary position
embeddings on the two halves of each head (theta from the
configuration), grouped-query attention with full causal masking, a
SwiGLU MLP ``(silu(x Wg) * (x Wi)) Wo``, a final RMSNorm and an output
head tied to the token embedding.  The program scales its tied head by
``1 / sqrt(hidden_size)``; the reference does the same, as the one
departure from the published description, so that both compute the same
function of the same weights.

Everything runs in float32 with every matrix product at ``HIGHEST``
precision, one layer at a time: the weights are made again from the seed
by :mod:`weights` (in the served type, as the program gets them) and each
layer is upcast on its own.  Nothing of the program is imported.

``quant="int8"`` is the control: every matrix product of the layers and
the head takes int8 weights (symmetric, per output column) and int8
activations (symmetric, per row), as a W8A8 path would.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

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


def rope(x, theta):
    """x (T, heads, dh); position of row t is t."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("n", "theta", "eps", "quant"))
def layer(p, x, *, n: Tuple, theta: float, eps: float, quant=None):
    """One decoder layer on one sequence x (T, d), float32."""
    h_, g_, dh = n
    t = x.shape[0]
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    q = rope(_mm(h, p["attn"]["wq"], quant).reshape(t, h_, dh), theta)
    k = rope(_mm(h, p["attn"]["wk"], quant).reshape(t, g_, dh), theta)
    v = _mm(h, p["attn"]["wv"], quant).reshape(t, g_, dh)
    q = q.reshape(t, g_, h_ // g_, dh)
    s = jnp.einsum("tgrd,sgd->grts", q, k, precision=HI) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + _mm(o.reshape(t, h_ * dh), p["attn"]["wo"], quant)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    m = jax.nn.silu(_mm(h, p["mlp"]["wg"], quant)) * _mm(h, p["mlp"]["wi"], quant)
    return x + _mm(m, p["mlp"]["wo"], quant)


@jax.jit
def _take(blocks, i):
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "vocab", "quant"))
def _gaps(final, embed, x, xc, nxt, *, eps, scale, vocab, quant):
    """Per position: the reference's best logit less the logit of the
    served next token, and less the logit of the control's first choice."""
    head = embed[:vocab].astype(jnp.float32) * scale          # (V, d)
    fin = final.astype(jnp.float32)
    logits = jnp.matmul(rmsnorm(x, fin, eps), head.T, precision=HI)
    best = jnp.max(logits, -1)
    served = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
    if xc is None:
        return best - served, None
    hc = rmsnorm(xc, fin, eps)
    lc = _mm(hc, head.T, quant)
    pick = jnp.argmax(lc, -1)
    return best - served, best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]


def logit_gaps(config: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int]],
               pad_to: int, control: Optional[str] = None
               ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """``seqs``: (tokens, first) pairs, ``tokens`` the prompt and its served
    tokens, ``first`` the index of the first served token.  Returns, per
    sequence, the gap of every served token (positions first-1 .. end-2
    predict tokens first .. end-1), and with ``control`` the gap of the
    token the control would put first at the same positions."""
    n = weights.dims(config)
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    shape = (n["h"], n["g"], n["dh"])
    params = weights.make(config, seed)
    embed, blocks = params["embed"]["tok"], params["blocks"]
    final = params["final_ln"]["scale"]
    del params
    padded = []
    for toks, _ in seqs:
        buf = np.zeros((pad_to,), np.int32)
        buf[:len(toks)] = toks
        padded.append(buf)
    xs = [embed[jnp.asarray(b)].astype(jnp.float32) for b in padded]
    xcs = list(xs) if control else None
    for i in range(n["layers"]):
        lp = _take(blocks, i)
        xs = [layer(lp, x, n=shape, theta=theta, eps=eps) for x in xs]
        if control:
            xcs = [layer(lp, x, n=shape, theta=theta, eps=eps, quant=control)
                   for x in xcs]
        del lp
    del blocks
    served, ctl = [], [] if control else None
    for j, ((toks, first), b) in enumerate(zip(seqs, padded)):
        nxt = np.zeros((pad_to,), np.int32)
        nxt[:len(toks) - 1] = b[1:len(toks)]
        g, gc = _gaps(final, embed, xs[j], None if xcs is None else xcs[j],
                      jnp.asarray(nxt), eps=eps, scale=n["d"] ** -0.5,
                      vocab=n["vocab"], quant=control)
        sl = slice(first - 1, len(toks) - 1)
        served.append(np.asarray(g)[sl])
        if control:
            ctl.append(np.asarray(gc)[sl])
    return served, ctl


def logits(config: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """Logits (T, vocab) of one short sequence at every position."""
    n = weights.dims(config)
    eps = float(config["rms_norm_eps"])
    params = weights.make(config, seed)
    x = params["embed"]["tok"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(n["layers"]):
        x = layer(_take(params["blocks"], i), x,
                  n=(n["h"], n["g"], n["dh"]),
                  theta=float(config["rope_theta"]), eps=eps)
    head = params["embed"]["tok"][:n["vocab"]].astype(jnp.float32) * n["d"] ** -0.5
    h = rmsnorm(x, params["final_ln"]["scale"].astype(jnp.float32), eps)
    return np.asarray(jnp.matmul(h, head.T, precision=HI))
