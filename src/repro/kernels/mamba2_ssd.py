"""Mamba2 SSD chunk scan — Pallas TPU kernel (zamba2's backbone hot path).

Chunked SSD decomposition per head (state S ∈ R^{P×N}, scalar decay per
step da_t = dt_t·A ≤ 0, La = prefix sum):

    intra:  Y[t] = Σ_{s<=t} e^{La_t - La_s} (C_t·B_s) dt_s x_s   ((c,c) matmuls)
    inter:  Y[t] += e^{La_t} (C_t · S_0ᵀ)
    state:  S_c   = e^{La_c} S_0 + Σ_s e^{La_c - La_s} dt_s (x_s ⊗ B_s)

Grid (B*H, nC), chunk-sequential with S in VMEM scratch ((P,N) fp32).
B/C are shared across heads (n_groups=1) — their index_map drops the head
coordinate, so they are DMA'd once per (batch, chunk) regardless of H.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


HI = jax.lax.Precision.HIGHEST


def _prefix_sum(row):
    """Inclusive prefix sum of a (1, c) row, as a product with the upper
    triangle of ones at full float32 precision (Mosaic has no cumsum)."""
    c = row.shape[1]
    upper = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    return jax.lax.dot_general(row, upper.astype(jnp.float32),
                               (((1,), (0,)), ((), ())), precision=HI)


def _column(row):
    """(1, c) -> (c, 1) by a lane-broadcast and a 2-D transpose (Mosaic
    transposes tiles, not vectors)."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _ssd_kernel(x_ref, dtr_ref, a_ref, b_ref, c_ref, dD_ref, o_ref,
                sout_ref, s_ref):
    ic = pl.program_id(1)
    n_c = pl.num_programs(1)

    @pl.when(ic == 0)
    def init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)          # (c, P)
    dt_row = dtr_ref[0].astype(jnp.float32)   # (1, c)
    dt_col = _column(dt_row)                  # (c, 1)
    a = a_ref[0]                              # (1, 1) A (negative)
    bmat = b_ref[0].astype(jnp.float32)       # (c, N)
    cmat = c_ref[0].astype(jnp.float32)       # (c, N)
    dcoef = dD_ref[0]                         # (1, 1) D

    # log decay's inclusive prefix sum, as a row and as a column; La is
    # non-increasing (dt >= 0, A < 0), so its last value is its minimum
    la_row = _prefix_sum(dt_row * a)          # (1, c)
    la_col = _column(la_row)                  # (c, 1)
    la_end = jnp.min(la_row, axis=1, keepdims=True)   # (1, 1)

    S0 = s_ref[...]                           # (P, N)
    # inter-chunk
    y = jnp.exp(la_col) * jax.lax.dot_general(
        cmat, S0, (((1,), (1,)), ((), ())), precision=HI)   # (c, P)
    # intra-chunk: G[t,s] = e^{La_t - La_s} (C_t · B_s) dt_s, s <= t
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             precision=HI)    # (c, c)
    c = cb.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    ratio = jnp.exp(jnp.where(si <= ti, la_col - la_row, 0.0))
    g = jnp.where(si <= ti, cb * ratio * dt_row, 0.0)
    y = y + jax.lax.dot_general(g, x, (((1,), (0,)), ((), ())), precision=HI)
    y = y + dcoef * x
    o_ref[0] = y.astype(o_ref.dtype)
    # state update: S_c = e^{La_c} S0 + Σ_s e^{La_c-La_s} dt_s x_s ⊗ B_s
    w = jnp.exp(la_end - la_col) * dt_col     # (c, 1)
    s_ref[...] = jnp.exp(la_end) * S0 + jax.lax.dot_general(
        x * w, bmat, (((0,), (0,)), ((), ())), precision=HI)

    @pl.when(ic == n_c - 1)
    def emit_state():
        sout_ref[0] = s_ref[...]


def mamba2_ssd_fwd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array, *,
                   chunk: int = DEFAULT_CHUNK,
                   interpret: bool = False):
    """x (B,T,H,P); dt (B,T,H); A (H,); B/C (B,T,N) [n_groups=1]; D (H,).
    Returns (y (B,T,H,P), final state (B,H,P,N))."""
    bsz, t, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, t)
    pad = (-t) % chunk

    xx = x.transpose(0, 2, 1, 3).reshape(bsz * h, t, p)
    dtt = dt.transpose(0, 2, 1).reshape(bsz * h, t)
    if pad:
        xx = jnp.pad(xx, ((0, 0), (0, pad), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, pad)))   # dt=0 -> decay 1, no update
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    tp = xx.shape[1]
    dt_row = dtt.reshape(bsz * h, 1, tp)
    aa = jnp.tile(A[None, :], (bsz, 1)).reshape(bsz * h, 1, 1)
    dd = jnp.tile(D[None, :], (bsz, 1)).reshape(bsz * h, 1, 1)
    n_c = tp // chunk

    y, s_out = pl.pallas_call(
        _ssd_kernel,
        grid=(bsz * h, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, 1, chunk), lambda g, i: (g, 0, i)),
            pl.BlockSpec((1, 1, 1), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, i, h=h: (g // h, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, i, h=h: (g // h, i, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, i: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, p, n), lambda g, i: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xx.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="mamba2_ssd",
    )(xx, dt_row, aa, B, C, dd)
    y = y[:, :t].reshape(bsz, h, t, p).transpose(0, 2, 1, 3)
    return y, s_out.reshape(bsz, h, p, n)
