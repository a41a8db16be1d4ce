"""Flash attention forward — Pallas TPU kernel.

Tiling: grid (B, H, nQ, nK) with the K dimension iterated sequentially
(innermost); online-softmax running (m, l, acc) live in VMEM scratch across
the nK steps and the normalised tile is written once at ik == nK-1.  GQA is
free: the K/V BlockSpec index_map folds the q-head -> kv-head mapping, so
repeated heads are never materialised.  Causal and chunked-local (llama4)
masks are applied in-tile; fully-masked tiles are skipped via a cheap
mask-aware branch (pl.when) that leaves the accumulators untouched.

Block shapes default to (128, 512): q tile rows hit the MXU 128-lane dim,
K tile of 512 keeps the (bq, bk) f32 score tile at 256 KB and the whole
working set (q + k + v + scores + acc) ~1.3 MB << 64 MB VMEM while long
enough to amortise the HBM -> VMEM DMA.

Backward is recompute-based (custom_vjp in ops.py: the blockwise jnp oracle
is AD-differentiated under remat) — fwd-kernel-only is the deliberate
scope: training hot-path fwd runs the kernel, bwd reuses XLA fusion.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, chunk: int, block_q: int,
               block_k: int, n_k: int, t_q: int, t_k: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    live = (qpos < t_q) & (kpos < t_k)
    if causal:
        live &= kpos <= qpos
    if chunk:
        live &= (qpos // chunk) == (kpos // chunk)

    # whole-tile skip: cheapest necessary-condition checks (static per tile)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(live, s, NEG_INF)
        # m starts at -inf and every score is >= NEG_INF, so m_new is
        # finite and corr = exp(-inf) = 0 on the first computed tile; a
        # fully masked tile is wiped by the next live tile's corr
        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    if causal:
        pl.when(ik * block_k <= (iq + 1) * block_q - 1)(compute)
    elif chunk:
        # tiles fully outside the chunk band contribute nothing
        pl.when((ik * block_k) // chunk <= ((iq + 1) * block_q - 1) // chunk)(compute)
    else:
        compute()

    @pl.when(ik == n_k - 1)
    def finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, scale: Optional[float] = None,
                        chunk: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False) -> jax.Array:
    """q (B,T,H,D); k/v (B,Tk,G,D), H % G == 0.  Returns (B,T,H,D)."""
    b, t, h, d = q.shape
    tk, g = k.shape[1], k.shape[2]
    nrep = h // g
    scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, max(8, t))
    block_k = min(block_k, max(128, tk)) if tk >= 128 else tk
    # kernel-friendly layout (B,H,T,D)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    pq = (-t) % block_q
    pk = (-tk) % block_k
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    n_q = qt.shape[2] // block_q
    n_k = kt.shape[2] // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, chunk=chunk,
        block_q=block_q, block_k=block_k, n_k=n_k, t_q=t, t_k=tk)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, nrep=nrep: (b_, h_ // nrep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, nrep=nrep: (b_, h_ // nrep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, qt.shape[2], d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m
            pltpu.VMEM((block_q, 1), jnp.float32),    # l
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out[:, :, :t].transpose(0, 2, 1, 3)
