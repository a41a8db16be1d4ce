"""Least time of the window's decode steps, per step the larger of its
FLOPs over the peak and its least bytes over HBM bandwidth
(``counts/granitemoehybrid.py``: weights once, each active lane's Mamba2
state read and written, K/V of the true contexts), over the decode
program's device time, in %.  A step's held-expert token-slots are the
window's mean (``EngineSnapshot.expert_load_mean``) scaled by its active
lanes."""

import weights_hybrid


def read(run):
    if run.trace is None or not run.window.decode_ctx:
        return None
    t = run.program_s("decode")
    load = getattr(run.snapshot, "expert_load_mean", 0.0)
    busy = getattr(run.snapshot, "busy_lanes_mean", 0.0)
    if t <= 0 or not busy:
        return None
    n = weights_hybrid.dims(run.config)
    cnt = run.counts("granitemoehybrid")
    pk = run.peaks
    per_lane = load * n["layers"] * n["n_held"] / busy
    least = 0.0
    for ctxs in run.window.decode_ctx:
        f, b = cnt.decode_step_work(n, ctxs, per_lane * len(ctxs),
                                    run.config["dtype_bytes"])
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_s"])
    return 100.0 * least / t
