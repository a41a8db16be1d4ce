"""Model FLOPs of the traced window's generated tokens over the window's
seconds times the chip's peak bf16 FLOP/s, in %: each decode lane-step at
its lane's true context through the mixers, the router, the shared
expert and the head, and the window's token-slots on held experts
(``EngineSnapshot.expert_load_mean``) through one expert each
(``counts/granitemoehybrid.py``)."""

import weights_hybrid


def held_slots(run) -> float:
    """Token-slots routed to held experts over the window's decode steps,
    all layers."""
    n = weights_hybrid.dims(run.config)
    load = getattr(run.snapshot, "expert_load_mean", 0.0)
    return load * len(run.window.decode_ctx) * n["layers"] * n["n_held"]


def read(run):
    if run.trace is None or not run.window.decode_ctx:
        return None
    n = weights_hybrid.dims(run.config)
    cnt = run.counts("granitemoehybrid")
    flops = sum(cnt.decode_token_flops(n, c)
                for step in run.window.decode_ctx for c in step)
    flops += held_slots(run) * cnt.expert_slot_flops(n)
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops"])
