"""Least time of the Mamba2 chunked scan at the real prompt lengths of
each batched prefill (``counts/mamba2_ssd.py``), the larger of FLOPs over
peak and bytes over HBM bandwidth per call, over the device time of the
``mamba2_ssd`` kernel (told from the flash kernel by its name) inside the
batched-prefill programs, in %."""

import scopes
import weights_hybrid


def read(run):
    if run.trace is None or not run.window.prefills:
        return None
    kt = scopes.kernel_named_s(run, "prefill", "mamba2_ssd")
    if not kt:
        return None
    n = weights_hybrid.dims(run.config)
    cnt = run.counts("mamba2_ssd")
    pk = run.peaks
    least = 0.0
    for p in run.window.prefills:
        f, b = cnt.work(n, p[:-1], run.config["dtype_bytes"])
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_s"])
    return 100.0 * least / kt
