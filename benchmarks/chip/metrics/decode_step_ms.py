"""Device time of the decode-step program per decode step (trace)."""


def read(run):
    steps = len(run.window.decode_ctx)
    t = run.program_s("decode") if run.trace else 0.0
    return 1e3 * t / steps if steps and t > 0 else None
