"""End-to-end arithmetic: latencies from due times, tails over all
requests with the unfinished ones counted at their wait so far, rates
over the whole window."""

import types

import numpy as np
import pytest

import spec


def track(due, times):
    return types.SimpleNamespace(due=due, times=list(times),
                                 first=times[0] if times else None)


def run_of(tracks, seconds, tokens=0):
    win = types.SimpleNamespace(tracks=tracks, seconds=seconds, tokens=tokens)
    return types.SimpleNamespace(window=win, setup_s=12.5)


def test_ttft_counts_from_the_due_time_and_keeps_the_unfinished():
    # 19 requests served 0.1 s after they were due; one due at 9.0 s got
    # no token before the window closed at 10 s: it counts 1.0 s.
    tracks = [track(0.5 * i, [0.5 * i + 0.1, 0.5 * i + 0.2]) for i in range(19)]
    tracks.append(track(9.0, []))
    got = spec.reader("ttft_p95_ms")(run_of(tracks, 10.0))
    want = 1e3 * np.percentile([0.1] * 19 + [1.0], 95)
    assert got == pytest.approx(want)
    assert got > 100.0


def test_ttft_leaves_out_requests_due_after_the_window():
    tracks = [track(1.0, [1.25]), track(12.0, [])]
    assert spec.reader("ttft_p95_ms")(run_of(tracks, 10.0)) == pytest.approx(250.0)


def test_itl_takes_every_gap_of_every_request():
    tracks = [track(0.0, [0.1, 0.15, 0.2, 0.6]),     # gaps .05 .05 .4
              track(0.0, [0.3, 0.35])]               # gap .05
    got = spec.reader("itl_p98_ms")(run_of(tracks, 1.0))
    assert got == pytest.approx(1e3 * np.percentile([.05, .05, .4, .05], 98))


def test_rate_is_all_tokens_over_the_whole_window():
    assert spec.reader("decode_tok_s")(run_of([], 20.0, tokens=1000)) == 50.0
    assert spec.reader("setup_s")(run_of([], 1.0)) == 12.5
