"""Mixture-of-Experts: two layers over one parameter tree.

* :func:`apply_moe` (training) — GShard/Switch-style grouped top-k
  dispatch: tokens are split into groups; within each group a
  capacity-bounded one-hot dispatch tensor routes tokens to experts via
  einsum, expert FFNs run batched over the expert dim (shardable over the
  "model" mesh axis = expert parallelism), and a combine einsum returns
  outputs.  Tokens beyond capacity are dropped (standard); a
  load-balancing auxiliary loss keeps routing spread.
* :func:`apply_moe_dropless` (serving) — every routed token-slot is
  computed: slots are sorted by expert and run through a ragged grouped
  matmul, so no routing imbalance drops a token and pad tokens of a
  batched prefill cannot take capacity from real ones.  It routes over all
  ``n_experts`` and computes only the experts this device holds
  (``cfg.held_experts``); absent experts add nothing, as on one chip of an
  expert-parallel deployment without its exchange.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import _act, init_mlp, apply_mlp, truncated_normal


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 8)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held_experts
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": truncated_normal(ks[0], (d, cfg.n_experts), s_in, jnp.float32),
        "wi": truncated_normal(ks[1], (e, d, f), s_in, dtype),
        "wo": truncated_normal(ks[2], (e, f, d), s_out, dtype),
    }
    if cfg.glu:
        p["wg"] = truncated_normal(ks[3], (e, d, f), s_in, dtype)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, cfg.shared_ff, cfg.glu, dtype)
    return p


def _top_k_gating(probs: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """probs (G,N,E) -> (gates (G,N,E) zero except chosen, mask (G,N,E) bool)."""
    gates = jnp.zeros_like(probs)
    mask = jnp.zeros(probs.shape, bool)
    p = probs
    for _ in range(top_k):
        idx = jnp.argmax(p, axis=-1)
        onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
        gates = gates + onehot * probs
        mask = mask | onehot.astype(bool)
        p = p * (1.0 - onehot)
    return gates, mask


def apply_moe(params: dict, cfg: ModelConfig, x: jax.Array,
              capacity_factor: float = 1.25,
              group_size: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (out (B,T,D), aux_loss scalar)."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_tok = b * t
    g = max(1, n_tok // group_size)
    while n_tok % g:
        g -= 1
    n = n_tok // g
    xg = x.reshape(g, n, d)

    logits = (xg.astype(jnp.float32) @ params["router"])          # (G,N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, mask = _top_k_gating(probs, k)

    # load-balance loss (Switch): E * mean_e(frac_tokens_e * mean_prob_e)
    frac = jnp.mean(mask.astype(jnp.float32), axis=1)             # (G,E)
    mean_p = jnp.mean(probs, axis=1)                              # (G,E)
    aux = e * jnp.mean(jnp.sum(frac * mean_p, axis=-1))

    cap = int(max(k, capacity_factor * n * k / e))
    cap = min(cap, n)
    # position of each token within its expert queue
    pos_in_e = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1     # (G,N,E)
    keep = mask & (pos_in_e < cap)
    disp = jax.nn.one_hot(jnp.where(keep, pos_in_e, cap), cap + 1,
                          dtype=xg.dtype)[..., :cap]              # (G,N,E,C)
    disp = disp * keep[..., None].astype(xg.dtype)

    xe = jnp.einsum("gnec,gnd->gecd", disp, xg)                   # (G,E,C,D)
    h = jnp.einsum("gecd,edf->gecf", xe, params["wi"].astype(xg.dtype))
    if "wg" in params:
        gate_h = jnp.einsum("gecd,edf->gecf", xe, params["wg"].astype(xg.dtype))
        h = _act(cfg.act)(gate_h) * h
    else:
        h = _act(cfg.act)(h)
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(xg.dtype))
    combine = disp * gates.astype(xg.dtype)[..., None]            # (G,N,E,C)
    y = jnp.einsum("gnec,gecd->gnd", combine, ye).reshape(b, t, d)

    if "shared" in params:
        y = y + apply_mlp(params["shared"], x, cfg.act)
    return y, aux


def route(params: dict, cfg: ModelConfig,
          x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (N, D) -> (gates (N, k) float32, expert ids (N, k)); the router's
    matmul and softmax in float32."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))   # (N, E)
    if cfg.router == "topk_softmax":
        vals, idx = jax.lax.top_k(logits, cfg.top_k)
        return jax.nn.softmax(vals, axis=-1), idx
    if cfg.router != "softmax_all":
        raise ValueError(f"unknown router {cfg.router!r}")
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)


# tokens of a dropless expert layer computed together: bounds the
# worst-case row buffers of a long batched prefill (4 x 2048 tokens at
# granite-4.0-h widths: 1.1 GB of temporaries instead of 6)
CHUNK_TOKENS = 2048


def apply_moe_dropless(params: dict, cfg: ModelConfig, x: jax.Array,
                       token_mask: Optional[jax.Array] = None, layer=None
                       ) -> Tuple[jax.Array, jax.Array]:
    """x (B, T, D) -> (out (B, T, D), held slots).

    Every token-slot routed to a held expert is computed, grouped by
    expert (a stable sort of the slots, then ``lax.ragged_dot``); the row
    budget is the worst case, ``min(top_k, held)`` slots per token, so
    nothing is ever dropped.  Tokens go through in equal chunks of at
    most ``CHUNK_TOKENS`` (a long batched prefill), the last padded with
    rows the mask leaves out, which bounds that budget's buffers.  The shared expert runs on every token in full.  ``held
    slots`` counts the token-slots routed to held experts among the tokens
    ``token_mask`` (B, T) keeps (all where None): the engine's expert-load
    counter.

    ``layer`` (an int or a traced index) says that ``params`` is the stack
    of every layer's expert block (leading layer axis) and which layer to
    run: the grouped matmuls then take the whole stack's experts as groups
    of their own, every other layer's group empty, so no layer's experts
    are sliced out and copied."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    experts = {k: params[k] for k in ("wi", "wg", "wo") if k in params}
    first = 0
    if layer is not None:
        e_h = cfg.n_held_experts
        first = layer * e_h
        experts = {k: v.reshape((-1,) + v.shape[2:])
                   for k, v in experts.items()}
        params = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False),
            {k: v for k, v in params.items() if k not in experts})
    mask = jnp.ones((n,), bool) if token_mask is None \
        else token_mask.reshape(n)
    with jax.named_scope("router"):
        gates, idx = route(params, cfg, xf)                        # (N, k)
    with jax.named_scope("experts"):
        if n <= CHUNK_TOKENS:
            y, n_held = _routed(experts, first, cfg, xf, gates, idx, mask)
        else:
            # equal chunks, the last padded with rows the mask leaves out
            q = -(-n // CHUNK_TOKENS)
            c = -(-n // q)
            pad = q * c - n

            def chunks(a):
                a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                return a.reshape((q, c) + a.shape[1:])

            def one(args):
                return _routed(experts, first, cfg, *args)
            ys, held = jax.lax.map(one, (chunks(xf), chunks(gates),
                                         chunks(idx), chunks(mask)))
            y, n_held = ys.reshape(q * c, d)[:n], jnp.sum(held)
        y = y.astype(x.dtype)
    if "shared" in params:
        with jax.named_scope("shared_expert"):
            y = y + apply_mlp(params["shared"], xf, cfg.act)
    return y.reshape(b, t, d), n_held


def _routed(params: dict, first, cfg: ModelConfig, xf, gates, idx, mask):
    """The held experts' weighted outputs (N, D) float32 for the tokens
    ``xf`` (N, D) and their routing, and the held slots ``mask`` keeps.
    ``params`` holds the experts' matrices, the held ones from group
    ``first`` on."""
    n, d = xf.shape
    k = idx.shape[1]
    lo, hi = cfg.held_range
    e_h = hi - lo
    local = idx - lo
    held = (local >= 0) & (local < e_h)
    key = jnp.where(held, local, e_h).reshape(-1)                  # (N*k,)
    m = n * min(k, e_h)
    order = jnp.argsort(key, stable=True)[:m]
    tok = order // k
    sizes = jnp.bincount(key, length=e_h + 1)[:e_h].astype(jnp.int32)
    n_groups = params["wi"].shape[0]
    if n_groups != e_h:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_groups,), jnp.int32), sizes,
            (jnp.asarray(first, jnp.int32),))
    wdt = params["wi"].dtype
    xs = xf[tok].astype(wdt)                                       # (m, D)
    h = jax.lax.ragged_dot(xs, params["wi"], sizes)
    if "wg" in params:
        h = _act(cfg.act)(jax.lax.ragged_dot(xs, params["wg"], sizes)) * h
    else:
        h = _act(cfg.act)(h)
    ys = jax.lax.ragged_dot(h, params["wo"], sizes)                # (m, D)
    # rows past the held slots are left undefined by a grouped matmul on
    # the TPU (not computed, possibly not finite): select, don't multiply
    live = held.reshape(-1)[order]
    w = jnp.where(live, gates.reshape(-1)[order], 0.0)
    y = jnp.zeros((n, d), jnp.float32).at[tok].add(
        jnp.where(live[:, None], ys.astype(jnp.float32) * w[:, None], 0.0))
    return y, jnp.sum(held & mask[:, None], dtype=jnp.int32)
