"""Device time of the sampling programs (and of any program the
configuration marks as sampling, such as an eager slice of the logits)
per decode step (trace)."""


def read(run):
    steps = len(run.window.decode_ctx)
    t = run.program_s("sample") if run.trace else 0.0
    return 1e3 * t / steps if steps and t > 0 else None
