"""Paged flash-decode — Pallas TPU kernel over a block-pooled KV cache.

Same online-softmax streaming structure and reshape-free GQA scoring as
:mod:`decode_attention`, but K/V live in a shared pool of fixed-size
blocks and each lane's logical cache is the row of physical block ids in
its block table.  The table and the per-lane positions ride in
scalar-prefetch memory so the BlockSpec index_map can translate (lane,
logical block) -> physical block before the DMA is issued: K/V tiles
stream straight from the pool, with no gathered (B, span)
materialisation in HBM.  Block 0 is the sink written by idle
lanes; its positions always sit past every live ``pos`` and are masked.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (finish_scratch, init_scratch,
                                            online_softmax_update,
                                            scratch_shapes)


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale, bs, g):
    b_, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def init():
        init_scratch(m_ref, l_ref, acc_ref)

    # logical block i of this lane covers token positions [i*bs, (i+1)*bs);
    # tile row r holds token r // g of that block
    rows = bs * g
    kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) // g
    online_softmax_update(q_ref[0], k_ref[0], v_ref[0], kpos <= pos_ref[b_],
                          m_ref, l_ref, acc_ref, scale=scale, g=g)

    @pl.when(i == pl.num_programs(1) - 1)
    def finish():
        finish_scratch(o_ref, l_ref, acc_ref)


def paged_decode_attention_fwd(
    q: jax.Array,
    kp: jax.Array,
    vp: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q (B,1,H,D); kp/vp (nb,bs,G,D) block pool; block_tables (B,max_blocks)
    int32; pos (B,) int32 last-written position.  Returns (B,1,H,D)."""
    b, _, h, d = q.shape
    nb, bs, g = kp.shape[0], kp.shape[1], kp.shape[2]
    rows = bs * g
    scale = d**-0.5 if scale is None else scale
    max_blocks = block_tables.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, i, bt, ps: (b_, 0, 0)),
            pl.BlockSpec((1, rows, d), lambda b_, i, bt, ps: (bt[b_, i], 0, 0)),
            pl.BlockSpec((1, rows, d), lambda b_, i, bt, ps: (bt[b_, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, i, bt, ps: (b_, 0, 0)),
        scratch_shapes=scratch_shapes(h, d),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, pos, q[:, 0], kp.reshape(nb, rows, d),
      vp.reshape(nb, rows, d))
    return out[:, None]
