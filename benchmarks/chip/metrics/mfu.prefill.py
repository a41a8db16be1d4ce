"""Model FLOPs of the real prompt tokens prefilled in the traced window
over the prefill programs' device time times the peak bf16 FLOP/s, in %."""

import weights


def read(run):
    if run.trace is None or not run.window.prefills:
        return None
    t = run.program_s("prefill")
    if t <= 0:
        return None
    n = weights.dims(run.config)
    dec = run.counts("decoder")
    flops = sum(dec.prefill_flops(n, x)
                for p in run.window.prefills for x in p[:-1])
    return 100.0 * flops / (t * run.peaks["bf16_flops"])
