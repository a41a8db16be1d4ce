"""Least time of the paged decode-attention work (true lane contexts,
exact GQA; ``counts/paged_attn.py``), the larger of FLOPs over peak and
bytes over HBM bandwidth per call, over the device time of the Pallas
kernel inside the decode-step program (the paged attention kernel), in
%."""

import weights


def read(run):
    if run.trace is None or not run.window.decode_ctx:
        return None
    kt = run.kernel_s("decode")
    if kt <= 0:
        return None
    n = weights.dims(run.config)
    cnt = run.counts("paged_attn")
    pk = run.peaks
    least = 0.0
    for ctxs in run.window.decode_ctx:
        f, b = cnt.work(n, ctxs, run.config["dtype_bytes"])
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_s"])
    return 100.0 * least / kt
