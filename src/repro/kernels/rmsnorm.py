"""Fused RMSNorm — Pallas TPU kernel (memory-bound fusion: one HBM read,
one write; mean-square + rsqrt + scale fused in VMEM).

Grid: rows/block_rows; each step loads a (block_rows, D) tile.  D stays
whole (norms reduce over it) — fine up to D=8192 (command-r): tile
128×8192×4 B = 4 MB in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_fwd(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
                block_rows: int = 128, interpret: bool = False) -> jax.Array:
    """x (..., D); scale (D,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    n = xr.shape[0]
    pad = (-n) % block_rows
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(xr.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xr, scale)
    return out[:n].reshape(orig_shape)
