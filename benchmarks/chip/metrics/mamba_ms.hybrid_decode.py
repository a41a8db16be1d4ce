"""Device time of the decode program's operations under the program's
``mamba`` scope (its ``in_proj``, ``conv``, ``ssm_state``, ``out_proj``;
the state slots' read and in-place write-back included), by each
operation's op_name in the program's HLO (``scopes.py``), per decode
step, in ms."""

import scopes


def read(run):
    steps = len(run.window.decode_ctx)
    t = scopes.scope_s(run, "decode", "mamba") if steps else None
    return 1e3 * t / steps if t else None
