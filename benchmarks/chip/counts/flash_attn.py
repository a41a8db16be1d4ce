"""Least work of causal prefill attention per layer at real prompt
lengths: for a prompt of t tokens, scores and weighted sums over the
causal triangle, 2 * t * (t + 1) * h * dh FLOPs, and one read of Q, K,
V and one write of O in the served dtype.  Padding to a bucket is not
work.
"""


def work(n: dict, lengths, dtype_bytes: int):
    """(FLOPs, bytes) over all layers for prompts of ``lengths`` tokens."""
    flops = sum(2.0 * t * (t + 1) * n["h"] * n["dh"] for t in lengths)
    byts = sum(2.0 * t * (n["h"] + n["g"]) * n["dh"] * dtype_bytes
               for t in lengths)
    return n["layers"] * flops, n["layers"] * byts
