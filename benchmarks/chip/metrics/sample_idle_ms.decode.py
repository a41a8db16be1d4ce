"""Device idle in the traced window while the host is inside the
program's ``serve.sample`` span or one of its children (upload,
dispatch, wait, download), by the innermost program span open at each
gap, per decode step (``serve.decode`` spans in the window), in ms."""

import program_spans

SAMPLE = "serve.sample"


def read(run):
    idle = program_spans.idle(run)
    steps = program_spans.count(run, "serve.decode")
    if not idle or not steps:
        return None
    s = sum(v for k, v in idle.items()
            if k == SAMPLE or k.startswith(SAMPLE + "."))
    return 1e3 * s / steps
