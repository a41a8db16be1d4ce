"""Traffic: the same seed gives the same requests; every seed gets the
same set of sizes and gaps in another order; any whole-number seed."""

import json

import numpy as np

import gen
from conftest import CHIP


def mix(name):
    return json.loads((CHIP / "traffic" / f"{name}.json").read_text())


def sizes(reqs):
    return sorted((len(r.prompt), r.max_new) for r in reqs)


def test_backlog_same_seed_same_requests_and_seeds_share_sizes():
    m = mix("batch")

    def take(seed):
        it = gen.backlog(m, seed, 49152)
        return [next(it) for _ in range(m["set_size"])]
    a, b, c = take(7), take(7), take(2**33 + 1)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert sum(r.greedy for r in a) == sum(r.greedy for r in c) == m["set_size"] // 2
    for r in a:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert len(r.prompt) + r.max_new <= m["max_total"]


def test_open_loop_gaps_are_one_set_in_seeded_order():
    m = mix("chat")
    a = gen.open_loop(m, 1, 49152, 40.0)
    b = gen.open_loop(m, 2**31 + 99, 49152, 40.0)
    gaps = lambda rs: np.diff([0.0] + [r.due for r in rs])
    assert abs(len(a) - len(b)) <= 1
    n = min(len(a), len(b)) - 1
    assert np.allclose(np.sort(gaps(a))[:n], np.sort(gaps(b))[:n])
    assert all(r.greedy for r in a)
    assert all(x.due < 40.0 for x in a)
    assert abs(len(a) - m["rate_rps"] * 40.0) <= 2


def test_lognormal_set_has_the_median():
    v = gen.lognormal_set(101, {"median": 512, "sigma": 0.7, "min": 1, "max": 10**6})
    assert v[50] == 512


def test_mmpp_arrivals_and_shared_prefixes():
    m = {**mix("chat"), "arrivals": "mmpp", "calm_rps": 1.0, "burst_rps": 20.0,
         "calm_dwell_s": 5.0, "burst_dwell_s": 1.0,
         "prefix": {"tokens": 64, "count": 2}}
    a, b = gen.open_loop(m, 9, 49152, 30.0), gen.open_loop(m, 9, 49152, 30.0)
    due = [r.due for r in a]
    assert due == [r.due for r in b] and due == sorted(due)
    assert 0 < len(a) and all(0 <= t < 30.0 for t in due)
    heads = {tuple(r.prompt[:64].tolist()) for r in a}
    assert len(heads) <= 2
    for r in a:
        assert len(r.prompt) >= 64
