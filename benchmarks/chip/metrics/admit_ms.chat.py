"""Mean duration of an admission round that admitted a request (the
program's ``serve.admit`` span: scheduler pop, block allocation, prefill
dispatches, first-token sample, paste), on the engine's clock, from
``EngineSnapshot.phases``, in ms."""


def read(run):
    if run.trace is None:
        return None
    ph = getattr(run.snapshot, "phases", {}).get("serve.admit")
    return 1e3 * ph.total_s / ph.count if ph is not None and ph.count \
        else None
