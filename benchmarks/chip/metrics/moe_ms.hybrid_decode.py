"""Device time of the decode program's operations under the program's
``moe`` scope (its ``router``, ``experts``, ``shared_expert``; the
grouped matmuls' calls, which the compiler names itself, by the
instructions that consume them), by each operation's op_name in the
program's HLO (``scopes.py``), per decode step, in ms."""

import scopes


def read(run):
    steps = len(run.window.decode_ctx)
    t = scopes.scope_s(run, "decode", "moe") if steps else None
    return 1e3 * t / steps if t else None
