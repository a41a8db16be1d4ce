"""Tokens generated inside the window, those of requests still running at
its end included, over the window's seconds (host clock)."""


def read(run):
    w = run.window
    return w.tokens / w.seconds if w.seconds > 0 else None
