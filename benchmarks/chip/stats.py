"""Order statistics of the end-to-end metrics."""

import numpy as np


def pct(values, q: float) -> float:
    """The q-th percentile (linear interpolation between order statistics)
    of every value; NaN for none."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else float("nan")
