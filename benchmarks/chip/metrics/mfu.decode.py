"""Model FLOPs of the traced window's generated tokens (every decode
lane-step at its lane's true context) over the window's seconds times the
chip's peak bf16 FLOP/s, in %."""

import weights


def read(run):
    if run.trace is None or not run.window.decode_ctx:
        return None
    n = weights.dims(run.config)
    dec = run.counts("decoder")
    flops = sum(dec.decode_token_flops(n, c)
                for step in run.window.decode_ctx for c in step)
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops"])
