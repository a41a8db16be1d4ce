"""The spread tool on made-up readings: the spreads, the check's verdict,
the bound the rule gives, and the reading of run output."""

import json
import statistics

import pytest

import spread
from conftest import CHIP


def bench_bound(metric):
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    return {m["name"]: m["bound"] for m in bench["end_to_end"]}[metric]


def test_quartile_spread_is_the_distance_between_quartiles_over_the_median():
    v = [100.0, 101.0, 102.0, 103.0, 104.0, 120.0]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert spread.quartile_spread(v) == pytest.approx((q3 - q1) / 102.5)
    assert spread.quartile_spread([5.0]) == 0.0


def test_trimmed_leaves_out_the_run_farthest_from_the_median():
    assert spread.trimmed([100.0, 101.0, 102.0, 103.0, 104.0, 120.0]) == [
        100.0, 101.0, 102.0, 103.0, 104.0]
    assert spread.trimmed([80.0, 101.0, 102.0, 103.0]) == [101.0, 102.0, 103.0]


def test_check_spread_takes_the_trimmed_interquartile_distance():
    v = [150.0, 151.0, 152.0, 153.0, 154.0, 190.0]
    # without 190 the quartiles of five runs lie 3 apart
    assert spread.check_spread(v) == pytest.approx(3.0 / 152.5)
    # a far-off run does not widen the check's reading, only the untrimmed one
    assert spread.check_spread(v) < spread.quartile_spread(v)
    # set E below: 158.115 to 158.49 once its 161.05 is left out
    assert spread.check_spread(P98_SETS[5]) == pytest.approx(0.375 / 158.415)


def test_the_last_chat_check_is_unresolved_under_one_percent():
    # itl_p95_ms at the parent of the last check: runs spread by 3.32346 ms
    # around 155.032 ms against a bound of 1%
    assert spread.verdict(3.32346, 155.032, 0.01) == "unresolved"
    assert spread.verdict(2.26369, 155.86, 0.01) == "unresolved"


# itl_p98_ms: what the check read in its two sets, around 159.328 ms
REPORTED = [2.18426 / 159.328, 1.18723 / 159.328]


def test_the_check_refused_two_percent_and_takes_the_bound_now():
    mean = sum(REPORTED) / 2
    assert mean > 0.02 / 2
    assert mean < bench_bound("itl_p98_ms") / 2
    # and no reading of it was over eight times looser
    assert bench_bound("itl_p98_ms") < 8 * max(REPORTED)


# itl_p98_ms of eight sets of six chat runs on a TPU v5 lite, in seed order,
# made in pairs on the same seeds (the third pair: one seed six times, and
# six seeds)
P98_SETS = [
    [158.45, 158.47, 158.54, 158.56, 157.68, 159.21],
    [158.05, 158.49, 159.17, 158.76, 158.23, 159.15],
    [157.96, 158.26, 158.00, 158.55, 158.64, 158.31],
    [159.75, 158.01, 159.05, 158.18, 158.66, 158.28],
    [160.37, 159.86, 160.29, 159.61, 159.33, 160.20],
    [158.40, 158.03, 161.05, 158.20, 158.55, 158.43],
    [159.39, 157.66, 158.42, 158.85, 159.13, 159.26],
    [158.19, 158.36, 158.11, 158.42, 159.18, 159.55],
]


def test_the_chat_tail_sets_are_resolved_at_their_bound():
    bound = bench_bound("itl_p98_ms")
    for v in P98_SETS:
        med = statistics.median(v)
        assert spread.verdict(spread.check_spread(v) * med, med,
                              bound) == "resolved"
    assert spread.bound_for(P98_SETS, REPORTED)[0] == bound


@pytest.mark.parametrize("sets, want", [
    # steady sets: 1% is the least bound
    ([[100.0, 100.1, 100.2, 100.1, 100.0, 100.2]] * 2, 0.01),
    # quartiles 3% apart: five times that is 15%
    ([[97.0, 99.0, 99.5, 100.5, 101.0, 103.0]] * 2, 0.15),
    # a narrow pair holds the bound under eight times its spread
    ([[150.0, 150.3, 150.5], [151.0, 151.3, 151.5]], 0.015),
    # spread past what any step holds: the cap
    ([[50.0, 80.0, 100.0, 120.0, 150.0, 200.0]] * 2, 0.25),
])
def test_bound_for_takes_the_step_nearest_the_aim(sets, want):
    b, asks = spread.bound_for(sets)
    assert b == want
    assert b >= min(max(asks["2 x check"], asks["floor"]), spread.STEPS[-1])


def test_bound_for_lets_twice_the_check_spread_win_over_looseness():
    # steady sets of my own, but the check read 5%: twice that binds
    steady = [[100.0, 100.1, 100.2, 100.1, 100.0, 100.2]] * 2
    b, asks = spread.bound_for(steady, [0.05])
    assert asks["2 x check"] > asks["8 x narrowest pair"]
    assert b == 0.10


def _run_output(tmp_path, name, cell, seed, value, correct=True):
    result = {"correct": correct, "attempted": 10, "failed": 0,
              "metrics": {"itl_p98_ms": {"value": value, "unit": "ms"},
                          "setup_s": {"value": 30.0 + seed % 7, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 1}}
    (tmp_path / name).write_text(
        f"[bench] {cell} seed {seed}: window 51.0 s, 1000 steps\n"
        f"{json.dumps(result)}\n"
        f"[bench] compared logit_gap 0.1 limit 0.3\n")


def test_collect_reads_cell_seed_and_result_and_flags_wrong_runs(tmp_path,
                                                                 capsys):
    a, b = tmp_path / "A", tmp_path / "B"
    a.mkdir()
    b.mkdir()
    for i, (seed, v) in enumerate([(2**31 + 5, 150.0), (7, 150.3),
                                   (9, 150.5)]):
        _run_output(a, f"0{i}.out", "m.chat", seed, v)
        _run_output(b, f"0{i}.out", "m.chat", seed, v + 1, correct=i != 2)
    (b / "09.out").write_text("Traceback: the run failed\n")
    table, wrong = spread.collect([a, b])
    sets = table["m.chat"]["itl_p98_ms"]
    assert [s for s, _ in sets] == [str(a), str(b)]
    assert sets[0][1] == [(2**31 + 5, 150.0), (7, 150.3), (9, 150.5)]
    assert [v for _, v in sets[1][1]] == [151.0, 151.3, 151.5]
    assert len(wrong) == 2
    assert spread.main([str(a), str(b)]) == 1
    out = capsys.readouterr().out
    assert "m.chat itl_p98_ms" in out and "bound by the rule 0.015" in out
    assert spread.main([str(a), str(b),
                        "--reported", "itl_p98_ms=3.0/150.0"]) == 1
    assert "bound by the rule 0.04" in capsys.readouterr().out
    assert "bound 0.25 (fixed)" in out


def test_two_runs_in_the_lower_cluster_put_the_p95_past_half_the_cap():
    # itl_p95_ms of one set of six chat runs on a TPU v5 lite: two runs read
    # the 1024-bucket cluster (102 ms), four the 2048-bucket one (157 ms)
    v = [156.30, 156.85, 157.10, 101.75, 101.97, 157.00]
    assert spread.quartile_spread(v) == pytest.approx(0.352, abs=1e-3)
    assert spread.check_spread(v) == pytest.approx(0.178, abs=1e-3)
    assert spread.verdict(spread.check_spread(v) * statistics.median(v),
                          statistics.median(v), 0.25) == "warned"
