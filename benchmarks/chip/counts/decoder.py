"""Model FLOPs of a dense decoder (granite / llama layout), from its shapes.

A matrix product of (m, k) by (k, n) is 2mkn FLOPs.  Per layer the
matrices are Wq (d, h*dh), Wk and Wv (d, g*dh), Wo (h*dh, d) and the
SwiGLU MLP's Wi, Wg (d, f) and Wo (f, d).  Attention of one query over
``ctx`` keys is 4 * ctx * h * dh FLOPs (scores and weighted sum), with
exact grouped-query sharing of keys and values.  Norms, rotary
embeddings and softmax are not counted.
"""


def layer_matmul_params(n: dict) -> int:
    d, h, g, dh, f = n["d"], n["h"], n["g"], n["dh"], n["f"]
    return d * h * dh + 2 * d * g * dh + h * dh * d + 3 * d * f


def decode_token_flops(n: dict, ctx: int) -> float:
    """One generated token of a lane that attends to ``ctx`` positions."""
    return (2.0 * n["layers"] * layer_matmul_params(n)
            + 2.0 * n["d"] * n["vocab"]
            + 4.0 * n["layers"] * ctx * n["h"] * n["dh"])


def prefill_flops(n: dict, length: int) -> float:
    """One prompt of ``length`` real tokens, causal, with the head applied
    at its last position (the first generated token's logits)."""
    return (2.0 * n["layers"] * layer_matmul_params(n) * length
            + 2.0 * n["d"] * n["vocab"]
            + 2.0 * n["layers"] * length * (length + 1) * n["h"] * n["dh"])
