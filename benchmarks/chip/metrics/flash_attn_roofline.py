"""Least time of causal prefill attention at the real prompt lengths
(``counts/flash_attn.py``), the larger of FLOPs over peak and bytes over
HBM bandwidth per call, over the device time of the Pallas kernel inside
the batched-prefill programs (the flash attention kernel), in %."""

import weights


def read(run):
    if run.trace is None or not run.window.prefills:
        return None
    kt = run.kernel_s("prefill")
    if kt <= 0:
        return None
    n = weights.dims(run.config)
    cnt = run.counts("flash_attn")
    pk = run.peaks
    least = 0.0
    for p in run.window.prefills:
        f, b = cnt.work(n, p[:-1], run.config["dtype_bytes"])
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_s"])
    return 100.0 * least / kt
