"""Flash-decode — Pallas TPU kernel for the HBM-bound decode step.

One new token attends to a (span,)-long KV cache: the op is a pure KV
stream (arithmetic intensity ~1 flop/byte), so the kernel's job is to
stream K/V tiles through VMEM exactly once with online softmax.  Grid
(B, nS) with the span dimension sequential; all H q-heads ride in the tile
(q is tiny).  ``valid`` masks unwritten cache slots (per-lane positions —
continuous batching).

GQA without vector reshapes: the (B, S, G, D) cache is viewed as
(B, S*G, D) — a free row-major reshape in HBM — so a K tile is a plain 2-D
(tokens*G, D) matrix whose row r holds token r // G of KV group r % G.
Every q head scores every row and keeps only its own group's
(:func:`online_softmax_update`).  That costs the MXU G times the flops of
an exact grouping, which a decode step bound by the K/V stream does not
notice, and it keeps every value in the kernel 2-D, which is what the TPU
compiler's layout inference accepts.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def online_softmax_update(q, k, v, live, m_ref, l_ref, acc_ref, *,
                          scale: float, g: int):
    """Fold one K/V tile into the running (m, l, acc) of every q head.

    q (H, D); k/v (N, D) with row r = token r // g of KV group r % g;
    live (1, N) or (H, N) bool marks readable rows.  m/l scratch are
    (H, 1), acc (H, D), all float32.  Rows a head may not read score
    ``NEG_INF``; a tile whose rows are all masked for a head is wiped by
    the correction factor of the first live tile that follows it."""
    h = q.shape[0]
    n = k.shape[0]
    nrep = h // g
    s = jax.lax.dot_general(q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    head_group = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0) // nrep
    row_group = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1) % g
    s = jnp.where((head_group == row_group) & live, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def finish_scratch(o_ref, l_ref, acc_ref):
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def scratch_shapes(h: int, d: int):
    return [pltpu.VMEM((h, 1), jnp.float32),      # m
            pltpu.VMEM((h, 1), jnp.float32),      # l
            pltpu.VMEM((h, d), jnp.float32)]      # acc


def _dec_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref,
                *, scale: float, n_s: int, g: int):
    i_s = pl.program_id(1)

    @pl.when(i_s == 0)
    def init():
        init_scratch(m_ref, l_ref, acc_ref)

    online_softmax_update(q_ref[0], k_ref[0], v_ref[0], valid_ref[0] != 0,
                          m_ref, l_ref, acc_ref, scale=scale, g=g)

    @pl.when(i_s == n_s - 1)
    def finish():
        finish_scratch(o_ref, l_ref, acc_ref)


def decode_attention_fwd(q: jax.Array, ck: jax.Array, cv: jax.Array,
                         valid: jax.Array, *, scale: Optional[float] = None,
                         block_s: int = DEFAULT_BLOCK_S,
                         interpret: bool = False) -> jax.Array:
    """q (B,1,H,D); ck/cv (B,S,G,D); valid (B,S) bool.  Returns (B,1,H,D)."""
    b, _, h, d = q.shape
    s_len, g = ck.shape[1], ck.shape[2]
    scale = d ** -0.5 if scale is None else scale
    block_s = min(block_s, s_len)
    pad = (-s_len) % block_s
    if pad:
        ck = jnp.pad(ck, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    s_pad = ck.shape[1]
    n_s = s_pad // block_s
    rows = block_s * g
    # one mask entry per (token, group) row, as int32 with a unit middle
    # axis so the (1, rows) tile meets the TPU block-shape rule
    live = jnp.repeat(valid.astype(jnp.int32), g, axis=1)[:, None, :]
    out = pl.pallas_call(
        functools.partial(_dec_kernel, scale=scale, n_s=n_s, g=g),
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, i: (b_, 0, 0)),
            pl.BlockSpec((1, rows, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, rows, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, 1, rows), lambda b_, i: (b_, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, i: (b_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=scratch_shapes(h, d),
        interpret=interpret,
        name="decode_attention",
    )(q[:, 0], ck.reshape(b, s_pad * g, d), cv.reshape(b, s_pad * g, d), live)
    return out[:, None]
