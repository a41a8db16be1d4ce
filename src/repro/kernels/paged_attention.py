"""Paged flash-decode — Pallas TPU kernel over a block-pooled KV cache.

Same online-softmax math and reshape-free GQA scoring as
:mod:`decode_attention`, but K/V live in a shared pool of fixed-size
blocks (pages) and each lane's logical cache is the row of physical block
ids in its block table.

The grid runs one step per lane.  The pools stay in HBM; a lane's pages
are gathered ``pages_per_step`` at a time into a VMEM buffer, one
contiguous (bs*G, D) DMA per page of the free (nb, bs*G, D) view, and each
such gather is scored as one (pages_per_step*bs*G, D) tile.  The buffer is
double-buffered: while one gather is scored the next is in flight, the
lane's next pages or, at its last gather, the first pages of the next lane
that has any, so the stream crosses lane boundaries without a stall.
``pages_per_step`` follows from the shapes: as many pages as make up about
:data:`STEP_BYTES` of K, no more than the table holds, and no more than
:data:`VMEM_BUDGET` affords.

Only live blocks are streamed.  A lane holds ``pos // bs + 1`` live
blocks (``pos`` is its last written position), or none when its table
row starts with the sink, block 0: the block manager never hands block 0
out, a released lane's row is zeroed, and a rollback keeps at least one
block, so such a row is an idle or released lane.  Pages past a lane's live
count issue no DMA and no work, and a lane with none returns zeros (no
caller reads an idle lane's output).  Positions past ``pos`` inside the
last live block are masked as before.
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

# K bytes one gather should stream: large enough that the DMA, not the
# fixed cost of issuing it and of the step, sets the time
STEP_BYTES = 512 * 1024
# VMEM a step's buffers and f32 temporaries may take, well inside the
# default scoped limit of a v5e
VMEM_BUDGET = 8 * 1024 * 1024


def pages_per_step(bs: int, g: int, d: int, h: int, itemsize: int,
                   max_blocks: int) -> int:
    """Pages gathered per step for a (bs, G, D) page of ``itemsize`` bytes,
    H query heads and a table of ``max_blocks`` entries."""
    rows = bs * g
    page = rows * d * itemsize
    # per page: K and V in two slots, V and the scores in f32 beside them
    vmem = 4 * page + rows * d * 4 + 4 * h * rows * 4
    return max(1, min(max_blocks, STEP_BYTES // page, VMEM_BUDGET // vmem))


def _paged_kernel(bt_ref, pos_ref, live_ref, nxt_ref, q_ref, k_hbm, v_hbm,
                  o_ref, kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
                  scale, bs, g, pps):
    lane = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    max_blocks = bt_ref.shape[1]
    rows = bs * g

    def copies(ln, step, slot):
        """(condition, K copy, V copy) of each page of gather ``step`` of
        lane ``ln`` into buffer ``slot``."""
        out = []
        for j in range(pps):
            blk = step * pps + j
            # read whether or not the page is live: stay inside the table
            page = bt_ref[ln, jnp.minimum(blk, max_blocks - 1)]
            dst = pl.ds(j * rows, rows)
            out.append((blk < live_ref[ln],
                        pltpu.make_async_copy(k_hbm.at[page],
                                              kbuf.at[slot, dst],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[page],
                                              vbuf.at[slot, dst],
                                              sems.at[1, slot])))
        return out

    def start(ln, step, slot):
        for live, kc, vc in copies(ln, step, slot):
            @pl.when(live)
            def _():
                kc.start()
                vc.start()

    def wait(ln, step, slot):
        for live, kc, vc in copies(ln, step, slot):
            @pl.when(live)
            def _():
                kc.wait()
                vc.wait()

    @pl.when(lane == 0)
    def first():
        # rows of a partial gather keep what the slot held before; their
        # p is 0, but 0 * NaN is not, so V starts out finite
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        slot_ref[0] = 0

        @pl.when(nxt_ref[0] < n_lanes)
        def _():
            start(nxt_ref[0], 0, 0)

    init_scratch(m_ref, l_ref, acc_ref)
    n_steps = pl.cdiv(live_ref[lane], pps)

    def body(step, slot):
        nxt_slot = 1 - slot

        @pl.when(step + 1 < n_steps)
        def _():
            start(lane, step + 1, nxt_slot)

        @pl.when((step + 1 == n_steps) & (nxt_ref[lane + 1] < n_lanes))
        def _():
            start(nxt_ref[lane + 1], 0, nxt_slot)

        wait(lane, step, slot)
        # gather row r holds token r // g of page r // rows of this step
        n = pps * rows
        kpos = (step * pps * bs
                + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // g)
        online_softmax_update(q_ref[0], kbuf[slot], vbuf[slot],
                              kpos <= pos_ref[lane], m_ref, l_ref, acc_ref,
                              scale=scale, g=g)
        return nxt_slot

    slot_ref[0] = jax.lax.fori_loop(0, n_steps, body, slot_ref[0])
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
    pps = pages_per_step(bs, g, d, h, kp.dtype.itemsize, max_blocks)
    live = jnp.where(block_tables[:, 0] == 0, 0, pos // bs + 1)
    # nxt[i]: the first lane at or after i with live blocks, else b
    nxt = jax.lax.cummin(jnp.where(live > 0, jnp.arange(b), b), reverse=True)
    nxt = jnp.concatenate([nxt, jnp.full((1,), b, nxt.dtype)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, *_: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, pps * rows, d), kp.dtype),
                        pltpu.VMEM((2, pps * rows, d), vp.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        *scratch_shapes(h, d)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, g=g, pps=pps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # a lane's last gather prefetches the next lane's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, pos, live.astype(jnp.int32), nxt.astype(jnp.int32),
      q[:, 0], kp.reshape(nb, rows, d), vp.reshape(nb, rows, d))
    return out[:, None]
