"""Mamba2 (SSD) block — zamba2's backbone.

State-space recurrence per head h: for step t
    S_t = exp(dt_t * A) * S_{t-1} + dt_t * (B_t ⊗ x_t)        S: (dh, n)
    y_t = S_t @ C_t + D * x_t
with data-dependent dt (softplus), scalar A per head, depthwise causal conv
on (x, B, C), and a gated RMSNorm output (SiLU(z) gate).

Reference path: `lax.scan` over time (exact).  Training perf path: the
chunked SSD Pallas kernel (`repro.kernels.mamba2_ssd`).  Decode carries
(conv_state, ssm_state) explicitly — O(1) per token, which is why the
``long_500k`` cell is trivial for this family.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import rmsnorm, truncated_normal

N_GROUPS = 1  # B/C shared across heads (mamba2 default n_groups=1)


def dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_headdim
    conv_dim = d_in + 2 * N_GROUPS * cfg.ssm_state
    return d_in, nheads, conv_dim


def init_mamba2(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    d_in, nheads, conv_dim = dims(cfg)
    ks = jax.random.split(key, 4)
    in_dim = 2 * d_in + 2 * N_GROUPS * cfg.ssm_state + nheads  # z, x, B, C, dt
    return {
        "in_proj": truncated_normal(ks[0], (d, in_dim), d ** -0.5, dtype),
        "conv_w": truncated_normal(ks[1], (cfg.ssm_conv, conv_dim), 0.3, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((nheads,), 0.01))).astype(jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": truncated_normal(ks[2], (d_in, d), d_in ** -0.5, dtype),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    d_in, nheads, _ = dims(cfg)
    n = N_GROUPS * cfg.ssm_state
    z, xbc_dt = jnp.split(proj, [d_in], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [d_in + 2 * n], axis=-1)
    return z, xbc, dt            # (…,d_in), (…,d_in+2n), (…,nheads)


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv along T. xbc (B,T,C), w (K,C).  Returns (y, new_state)
    where state is the last K-1 inputs (for decode)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)          # (B, T+K-1, C)
    y = sum(xp[:, i : i + xbc.shape[1]] * w[i] for i in range(k))
    y = jax.nn.silu(y + b.astype(y.dtype))
    new_state = xp[:, -(k - 1):] if k > 1 else xp[:, :0]
    return y, new_state


def mamba2_scan_ref(x_h, dt, A, B, C, D, ssm_state=None):
    """Exact recurrence.  x_h (B,T,H,P); dt (B,T,H); A (H,); B/C (B,T,N);
    D (H,).  Returns (y (B,T,H,P), final_state (B,H,P,N))."""
    bsz, t, h, p = x_h.shape
    n = B.shape[-1]
    if ssm_state is None:
        ssm_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(S, inp):
        xt, dtt, Bt, Ct = inp                         # (B,H,P),(B,H),(B,N),(B,N)
        decay = jnp.exp(dtt * A)[..., None, None]     # (B,H,1,1)
        upd = (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]  # (B,H,P,N)
        S = decay * S + upd
        y = jnp.einsum("bhpn,bn->bhp", S, Ct)
        return S, y

    xs = (x_h.swapaxes(0, 1).astype(jnp.float32), dt.swapaxes(0, 1),
          B.swapaxes(0, 1).astype(jnp.float32), C.swapaxes(0, 1).astype(jnp.float32))
    S, ys = jax.lax.scan(step, ssm_state, xs)
    y = ys.swapaxes(0, 1) + D[None, None, :, None] * x_h.astype(jnp.float32)
    return y.astype(x_h.dtype), S


def apply_mamba2(params: dict, cfg: ModelConfig, x: jax.Array, *,
                 use_kernels: bool = False,
                 state: Optional[dict] = None,
                 lengths: Optional[jax.Array] = None) -> Tuple[jax.Array, dict]:
    """Full-sequence (train/prefill) when state is None; single/multi-token
    stateful otherwise.  x: (B,T,D).  Returns (out, {"conv", "ssm"}): the
    conv tail (last K-1 conv inputs) and the SSM state after the sequence.

    ``lengths`` (B,) marks a right-padded batch (prefill from a zero
    state): ``dt`` is 0 at every pad position, so the state passes through
    pads unchanged (decay e^0 = 1, update 0), and the conv tail is
    gathered at each lane's true length.  Profile scopes: ``in_proj``,
    ``conv``, ``ssm_state``, ``out_proj``."""
    d_in, nheads, conv_dim = dims(cfg)
    n = N_GROUPS * cfg.ssm_state
    bsz, t = x.shape[:2]
    with jax.named_scope("in_proj"):
        proj = x @ params["in_proj"]
        z, xbc, dt = _split_proj(cfg, proj)
    with jax.named_scope("conv"):
        conv_state = None if state is None else state["conv"]
        xbc_in = xbc
        xbc, new_conv = _causal_conv(xbc, params["conv_w"], params["conv_b"],
                                     conv_state)
        if lengths is not None:
            k = cfg.ssm_conv
            xin = jnp.concatenate(
                [jnp.zeros((bsz, k - 1, conv_dim), xbc_in.dtype), xbc_in],
                axis=1)                                    # (B, K-1+T, C)
            at = lengths[:, None] + jnp.arange(k - 1)[None, :]
            new_conv = jnp.take_along_axis(xin, at[:, :, None], axis=1)
    with jax.named_scope("ssm_state"):
        xh, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)
        xh = xh.reshape(bsz, t, nheads, cfg.ssm_headdim)
        A = -jnp.exp(params["A_log"])
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        if lengths is not None:
            dt = jnp.where(jnp.arange(t)[None, :, None] < lengths[:, None, None],
                           dt, 0.0)
        ssm_state = None if state is None else state["ssm"]
        if use_kernels and state is None:
            from repro.kernels import ops as kops
            y, S = kops.mamba2_ssd(xh, dt, A, B, C, params["D"],
                                   chunk=cfg.ssm_chunk)
        else:
            y, S = mamba2_scan_ref(xh, dt, A, B, C, params["D"], ssm_state)
    with jax.named_scope("out_proj"):
        y = y.reshape(bsz, t, d_in)
        y = rmsnorm({"scale": params["norm_scale"]},
                    y * jax.nn.silu(z.astype(y.dtype)))
        out = y @ params["out_proj"]
    conv_dt = proj.dtype if state is None else state["conv"].dtype
    return out, {"conv": new_conv.astype(conv_dt), "ssm": S}


def init_mamba2_state(cfg: ModelConfig, batch: int, n_layers: int, dtype) -> dict:
    d_in, nheads, conv_dim = dims(cfg)
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((n_layers, batch, nheads, cfg.ssm_headdim, cfg.ssm_state),
                         jnp.float32),
    }
