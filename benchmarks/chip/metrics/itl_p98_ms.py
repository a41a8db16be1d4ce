"""98th percentile of every gap between consecutive tokens of a request
in the window, each token timed at the return of the step that made it."""

import numpy as np

import stats


def read(run):
    gaps = [g for t in run.window.tracks if len(t.times) > 1
            for g in np.diff(t.times).tolist()]
    return 1e3 * stats.pct(gaps, 98) if gaps else None
