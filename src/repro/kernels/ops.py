"""Jit'd public entry points for the Pallas kernels.

Routing: on TPU the kernels run compiled; on the CPU (the test suite) they
run in ``interpret=True`` mode — same kernel body, evaluated by XLA:CPU —
so correctness is exercised without a chip.  Any other backend is an
error: a kernel never silently gives way to interpret mode or to the jnp
reference.

Gradients: ``flash_attention`` carries a custom VJP whose backward is the
AD of the blockwise oracle under remat (recompute-based flash backward).
The rwkv6/mamba2 chunked kernels get the same treatment (oracle-AD bwd).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.mamba2_ssd import mamba2_ssd_fwd
from repro.kernels.paged_attention import paged_decode_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.rwkv6_scan import rwkv6_chunked_fwd


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on tpu or interpreted on cpu; the "
        f"default backend is {backend!r}")


# ---------------------------------------------------------------------------
# flash attention (custom VJP: kernel fwd, oracle-AD bwd)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, chunk: int = 0):
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               chunk=chunk, interpret=_interpret())


def _fa_fwd(q, k, v, causal, scale, chunk):
    out = flash_attention(q, k, v, causal, scale, chunk)
    return out, (q, k, v)


def _fa_bwd(causal, scale, chunk, res, g):
    q, k, v = res
    f = lambda q, k, v: kref.flash_attention_ref(
        q, k, v, causal=causal, scale=scale, chunk=chunk)
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# decode attention (no grad needed — serving only)
# ---------------------------------------------------------------------------

def decode_attention(q, ck, cv, valid, scale: float):
    return decode_attention_fwd(q, ck, cv, valid, scale=scale,
                                interpret=_interpret())


def paged_decode_attention(q, kp, vp, block_tables, pos, scale: float):
    """Flash-decode over a block-pooled KV cache (serving only, no grad)."""
    return paged_decode_attention_fwd(q, kp, vp, block_tables, pos,
                                      scale=scale, interpret=_interpret())


# ---------------------------------------------------------------------------
# rwkv6 chunked scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _rwkv6(r, k, v, log_w, u):
    return rwkv6_chunked_fwd(r, k, v, log_w, u, interpret=_interpret())


def _rwkv6_f(r, k, v, log_w, u):
    return _rwkv6(r, k, v, log_w, u), (r, k, v, log_w, u)


def _rwkv6_b(res, g):
    r, k, v, log_w, u = res
    _, vjp = jax.vjp(lambda *a: kref.rwkv6_chunked_ref(*a), r, k, v, log_w, u)
    return vjp(g)


_rwkv6.defvjp(_rwkv6_f, _rwkv6_b)


def rwkv6_scan(r, k, v, w, u):
    """Model-facing signature: w is the DECAY in (0,1) (models/rwkv.py);
    the kernel wants log-decay.  Returns (out, final_state=None marker)."""
    log_w = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))
    out = _rwkv6(r, k, v, log_w, u)
    return out, None


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    return mamba2_ssd_fwd(x, dt, A, B, C, D, chunk=chunk,
                          interpret=_interpret())


def _ssd_f(x, dt, A, B, C, D, chunk):
    return _ssd(x, dt, A, B, C, D, chunk), (x, dt, A, B, C, D)


def _ssd_b(chunk, res, g):
    # gradient flows through y only; the final state is consumed at decode
    # time (no training path) — its cotangent is dropped
    gy, _gs = g
    x, dt, A, B, C, D = res
    _, vjp = jax.vjp(lambda *a: kref.mamba2_scan_ref(*a)[0], x, dt, A, B, C, D)
    return vjp(gy)


_ssd.defvjp(_ssd_f, _ssd_b)


def mamba2_ssd(x, dt, A, B, C, D, chunk: int = 64):
    """Returns (y, final_state)."""
    return _ssd(x, dt, A, B, C, D, chunk)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-5):
    return rmsnorm_fwd(x, scale, eps, interpret=_interpret())
