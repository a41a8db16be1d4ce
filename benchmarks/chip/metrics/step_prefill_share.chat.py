"""Prefill programs' device time over prefill plus decode-step device
time in the traced window, in %."""


def read(run):
    if run.trace is None:
        return None
    p, d = run.program_s("prefill"), run.program_s("decode")
    return 100.0 * p / (p + d) if p + d > 0 else None
