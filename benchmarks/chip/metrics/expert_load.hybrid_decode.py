"""Token-slots of active lanes routed to the experts this chip holds, per
held expert, per expert layer and decode step, over the window
(``EngineSnapshot.expert_load_mean``, a counter of the decode program)."""


def read(run):
    if run.trace is None:
        return None
    v = getattr(run.snapshot, "expert_load_mean", None)
    return v if v else None
