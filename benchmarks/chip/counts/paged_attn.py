"""Least work of one paged decode-attention call per layer: each active
lane's query (h heads of dh) against the keys and values of its true
context, with exact grouped-query sharing.  The yardstick is the same
whatever implements the kernel: bytes are the K/V of the positions the
lanes really hold (not the blocks a kernel fetches) plus the queries and
outputs, in the served dtype.
"""


def work(n: dict, ctxs, dtype_bytes: int):
    """(FLOPs, bytes) of one decode step over all layers; ``ctxs`` are
    the active lanes' context lengths, including the new token."""
    total = sum(ctxs)
    flops = 4.0 * n["layers"] * total * n["h"] * n["dh"]
    kv = 2.0 * total * n["g"] * n["dh"] * dtype_bytes
    qo = 2.0 * len(ctxs) * n["h"] * n["dh"] * dtype_bytes
    return flops, n["layers"] * (kv + qo)
