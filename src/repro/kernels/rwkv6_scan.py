"""RWKV-6 WKV recurrence — chunked Pallas TPU kernel.

The token-recurrent form (models/rwkv.py) is a T-step serial scan — latency
-bound on any accelerator.  This kernel uses the chunked decomposition: with
log-decay ld_t = log w_t and prefix sums La_t = Σ_{s<=t} ld_s, for a chunk
of length c

    out_t  = (r_t ⊙ e^{La_{t-1}}) S_0
           + Σ_{s<t} [(r_t ⊙ e^{La_{t-1}-La_s}) · k_s] v_s     (intra, (c,c) matmul)
           + (r_t ⊙ u ⊙ k_t) · v_t                             (bonus diagonal)
    S_c    = diag(e^{La_c}) S_0 + Σ_s (k_s ⊙ e^{La_c-La_s}) v_sᵀ

i.e. three MXU matmuls per chunk instead of c sequential rank-1 updates.
Ratios are formed in log space (safe: La is monotonically decreasing).

Grid (B*H, nC), chunk dim sequential with the (K,V) state in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _rwkv_kernel(r_ref, k_ref, v_ref, ld_ref, u_ref, o_ref, s_ref, *,
                 chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)        # (c, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)        # (c, V)
    ld = ld_ref[0].astype(jnp.float32)      # (c, K) log decay (<= 0)
    u = u_ref[0].astype(jnp.float32)        # (K,)

    la = jnp.cumsum(ld, axis=0)             # inclusive prefix (c, K)
    la_prev = la - ld                       # exclusive prefix La_{t-1}
    la_end = la[-1]                         # La_c

    S0 = s_ref[...]                         # (K, V)
    # inter-chunk: r_t e^{La_{t-1}} @ S0
    rin = r * jnp.exp(la_prev)
    out = jax.lax.dot_general(rin, S0, (((1,), (0,)), ((), ())))
    # intra-chunk: P[t,s] = Σ_kdim r_t e^{La_{t-1}-La_s} k_s  (s < t)
    qt = r * jnp.exp(la_prev)
    ks = k * jnp.exp(-la)
    p = jax.lax.dot_general(qt, ks, (((1,), (1,)), ((), ())))   # (c, c)
    c = p.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    p = jnp.where(si < ti, p, 0.0)
    out = out + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
    # bonus diagonal
    out = out + ((r * u[None, :] * k).sum(-1, keepdims=True)) * v
    o_ref[0] = out.astype(o_ref.dtype)
    # state update
    kd = k * jnp.exp(la_end[None, :] - la)
    s_ref[...] = jnp.exp(la_end)[:, None] * S0 + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())))


def rwkv6_chunked_fwd(r: jax.Array, k: jax.Array, v: jax.Array,
                      log_w: jax.Array, u: jax.Array, *,
                      chunk: int = DEFAULT_CHUNK,
                      interpret: bool = False) -> jax.Array:
    """r/k/v (B,T,H,K); log_w (B,T,H,K) = log decay (<=0); u (H,K).
    Returns out (B,T,H,K)."""
    b, t, h, dk = r.shape
    chunk = min(chunk, t)
    pad = (-t) % chunk
    def prep(a):
        a = a.transpose(0, 2, 1, 3).reshape(b * h, t, dk)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return a
    rr, kk, vv = prep(r), prep(k), prep(v)
    ld = prep(log_w)  # pad rows get ld=0 (decay 1) — harmless, outputs dropped
    uu = jnp.tile(u, (b, 1))                 # (b*h, K), b-major
    n_c = rr.shape[1] // chunk

    out = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=(b * h, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, dk), lambda g, i: (g, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dk), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct(rr.shape, r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret,
        name="rwkv6_scan",
    )(rr, kk, vv, ld, uu)
    out = out[:, :t].reshape(b, h, t, dk).transpose(0, 2, 1, 3)
    return out
