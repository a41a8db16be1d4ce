"""Real prompt tokens over the padded tokens prefilled in the window
(``EngineSnapshot.prefill_tokens``), in %."""


def read(run):
    padded = run.snapshot.prefill_tokens
    real = sum(t for p in run.window.prefills for t in p[:-1])
    return 100.0 * real / padded if padded and real else None
