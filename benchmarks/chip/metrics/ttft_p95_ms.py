"""95th percentile of the time from each request's due time to its first
token, over every request due in the window; a request with no first
token by the end counts with its wait so far."""

import stats


def read(run):
    w = run.window
    waits = []
    for t in w.tracks:
        if t.due is None or t.due >= w.seconds:
            continue
        first = t.first if t.first is not None and t.first <= w.seconds \
            else w.seconds
        waits.append(first - t.due)
    return 1e3 * stats.pct(waits, 95) if waits else None
