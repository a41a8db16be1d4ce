"""Model FLOPs and least bytes of a granitemoehybrid decoder, from its
shapes (``weights_hybrid.dims``).

A matrix product of (m, k) by (k, n) is 2mkn FLOPs.  Per token:

* a Mamba2 layer: ``in_proj`` (d -> 2 d_in + 2 N + H), the depthwise conv
  (2 K per channel), the recurrence (the state's outer-product update,
  2 H P N, and its read-out, 2 H P N) and ``out_proj`` (d_in -> d);
* an attention layer: Wq, Wk, Wv, Wo, and 4 * ctx * h * dh for scores and
  weighted sum over ``ctx`` positions (exact grouped-query sharing);
* every layer's expert block: the router (d -> E), the shared SwiGLU
  (3 d fs) and, per token-slot routed to a held expert, one SwiGLU
  (3 d f); the slots are counted by the program (``held_slots``);
* the head once (d -> vocab).

Norms, gates, softmax and the conv's SiLU are not counted.  Least bytes
of a decode step: every weight read once in its stored type, each active
lane's Mamba2 state (float32) read and written once, the K/V of the
positions each lane holds read, and one new K/V position per lane and
attention layer written.  Least bytes of a prompt: every weight read once,
the prompt's K/V written once per attention layer and its final Mamba2
state written once.
"""


def _mamba(n: dict) -> float:
    d, d_in, H, P, N = n["d"], n["d_in"], n["mh"], n["mp"], n["ns"]
    in_proj = 2.0 * d * (2 * d_in + 2 * n["ng"] * N + H)
    return (in_proj + 2.0 * n["conv"] * n["conv_dim"] + 4.0 * H * P * N
            + 2.0 * d_in * d)


def _attn_proj(n: dict) -> float:
    d, h, g, dh = n["d"], n["h"], n["g"], n["dh"]
    return 2.0 * (2 * d * h * dh + 2 * d * g * dh)


def _moe_base(n: dict) -> float:
    return 2.0 * n["d"] * n["experts"] + 6.0 * n["d"] * n["fs"]


def expert_slot_flops(n: dict) -> float:
    """One token-slot through one routed SwiGLU expert."""
    return 6.0 * n["d"] * n["f"]


def decode_token_flops(n: dict, ctx: int) -> float:
    """One generated token of a lane that attends to ``ctx`` positions,
    without its routed experts."""
    return (n["n_mamba"] * _mamba(n)
            + n["n_attn"] * (_attn_proj(n) + 4.0 * ctx * n["h"] * n["dh"])
            + n["layers"] * _moe_base(n) + 2.0 * n["d"] * n["vocab"])


def prompt_flops(n: dict, length: int, held_slots: float) -> float:
    """One prompt of ``length`` real tokens, causal, its ``held_slots``
    routed token-slots on held experts, the head at its last position."""
    return (length * (n["n_mamba"] * _mamba(n) + n["n_attn"] * _attn_proj(n)
                      + n["layers"] * _moe_base(n))
            + 2.0 * n["n_attn"] * length * (length + 1) * n["h"] * n["dh"]
            + held_slots * expert_slot_flops(n) + 2.0 * n["d"] * n["vocab"])


def weight_bytes(n: dict, dtype_bytes: int) -> float:
    """Every weight in its stored type: the router and the Mamba2
    constants (A_log, D, dt_bias) in float32, the rest in the served type."""
    d, d_in, H = n["d"], n["d_in"], n["mh"]
    mamba = (d * (2 * d_in + 2 * n["ng"] * n["ns"] + H) + d_in * d
             + n["conv_dim"] * (n["conv"] + 1) + d_in + d) * dtype_bytes \
        + 3 * H * 4
    attn = (2 * d * n["h"] * n["dh"] + 2 * d * n["g"] * n["dh"] + d) \
        * dtype_bytes
    moe = (3 * d * (n["n_held"] * n["f"] + n["fs"]) + d) * dtype_bytes \
        + d * n["experts"] * 4
    return (n["n_mamba"] * mamba + n["n_attn"] * attn + n["layers"] * moe
            + (n["vp"] * d + d) * dtype_bytes)


def lane_state_bytes(n: dict) -> float:
    """One lane's Mamba2 state over all Mamba2 layers, float32."""
    return 4.0 * n["n_mamba"] * ((n["conv"] - 1) * n["conv_dim"]
                                 + n["mh"] * n["mp"] * n["ns"])


def decode_step_work(n: dict, ctxs, held_slots: float, dtype_bytes: int):
    """(FLOPs, least bytes) of one decode step; ``ctxs`` are the active
    lanes' contexts including the new token, ``held_slots`` the step's
    token-slots on held experts over all layers."""
    flops = sum(decode_token_flops(n, c) for c in ctxs) \
        + held_slots * expert_slot_flops(n)
    kv = 2.0 * n["g"] * n["dh"] * dtype_bytes * n["n_attn"]
    byts = (weight_bytes(n, dtype_bytes) + 2.0 * len(ctxs) * lane_state_bytes(n)
            + kv * (sum(ctxs) + len(ctxs)))
    return flops, byts


def prompt_work(n: dict, length: int, held_slots: float, dtype_bytes: int):
    """(FLOPs, least bytes) of one prompt of ``length`` real tokens."""
    kv = 2.0 * n["g"] * n["dh"] * dtype_bytes * n["n_attn"] * length
    return (prompt_flops(n, length, held_slots),
            weight_bytes(n, dtype_bytes) + kv + lane_state_bytes(n))
