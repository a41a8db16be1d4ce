"""Mean busy decode lanes per engine step over the window
(``EngineSnapshot.busy_lanes_mean``)."""


def read(run):
    s = run.snapshot
    return s.busy_lanes_mean if s.steps else None
