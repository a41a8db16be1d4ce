"""95th percentile of the wait from the start of the admission round
that first admitted a request to its first token sampled
(``EngineSnapshot.prefill_wait``, on the engine's clock), in ms."""


def read(run):
    if run.trace is None:
        return None
    w = getattr(run.snapshot, "prefill_wait", None)
    return 1e3 * w.p95 if w is not None and w.count else None
