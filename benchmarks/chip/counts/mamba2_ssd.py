"""Least work of the Mamba2 chunked scan (SSD) of a prompt at its real
length, per Mamba2 layer, the same whatever implements it: per token and
head the state's outer-product update (2 P N FLOPs) and its read-out
(2 P N); bytes, one read of x (T, H, P) and of B and C (T, N per group)
in the served type and of dt (T, H) in float32, one write of y (T, H, P)
in the served type and of the final state (H, P, N) in float32.  Padding
to a bucket is not work.
"""


def work(n: dict, lengths, dtype_bytes: int):
    """(FLOPs, bytes) over all Mamba2 layers for prompts of ``lengths``."""
    H, P, N, ng = n["mh"], n["mp"], n["ns"], n["ng"]
    flops = sum(4.0 * t * H * P * N for t in lengths)
    byts = sum(2.0 * t * H * P * dtype_bytes + 2.0 * t * ng * N * dtype_bytes
               + 4.0 * t * H + 4.0 * H * P * N for t in lengths)
    return n["n_mamba"] * flops, n["n_mamba"] * byts
