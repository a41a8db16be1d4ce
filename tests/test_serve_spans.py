"""The serving path's host spans (``repro.serving.spans``): their counters
in ``EngineSnapshot`` on the engine's clock, and their events in a
profiler trace."""
import dataclasses
import glob
import itertools

import jax
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, reduced_config
from repro.models.api import build_model
from repro.runtime.guard import seeded_replay_check
from repro.serving.engine import EngineConfig, ServeEngine

RCFG = RunConfig(param_dtype="float32", compute_dtype="float32", remat=False)
SAMPLE_PARTS = ("serve.sample.upload", "serve.sample.dispatch",
                "serve.sample.wait", "serve.sample.download")
SPANS = ("serve.step", "serve.prepare", "serve.admit", "serve.prefill",
         "serve.sample", "serve.paste", "serve.decode", "serve.finish"
         ) + SAMPLE_PARTS
STEP_CHILDREN = {"serve.prepare", "serve.admit", "serve.decode",
                 "serve.sample", "serve.finish"}


@pytest.fixture(scope="module")
def small_lm():
    cfg = dataclasses.replace(reduced_config(get_config("granite-8b")),
                              n_layers=2)
    model = build_model(cfg, RCFG)
    return model, model.init(jax.random.key(0))


def _engine(small_lm, clock=None):
    model, params = small_lm
    return ServeEngine(model, params, max_batch=3, max_len=64,
                       max_prefill_batch=2, clock=clock,
                       config=EngineConfig(kv_blocks=24, kv_block_size=8))


def _submit(eng, seed, n=5):
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(rng.integers(0, 100, 6 + 3 * i), max_new=3 + i % 3)


def test_engine_counts_every_phase(small_lm):
    eng = _engine(small_lm)
    _submit(eng, 0)
    done = eng.run_until_drained()
    snap = eng.metrics_snapshot()
    assert set(snap.phases) == set(SPANS)
    assert snap.phases["serve.step"].count == snap.steps == eng.steps
    # two passes of _prepare_lanes per step
    assert snap.phases["serve.prepare"].count == 2 * snap.steps
    assert snap.phases["serve.decode"].count == snap.steps
    for name, ph in snap.phases.items():
        assert ph.count > 0 and 0 <= ph.max_s <= ph.total_s
    # every sampler call opens each of its four parts once, inside it
    sample = snap.phases["serve.sample"]
    assert all(snap.phases[p].count == sample.count for p in SAMPLE_PARTS)
    assert sum(snap.phases[p].total_s for p in SAMPLE_PARTS) <= sample.total_s
    # the longest step's split is by its direct children and fits in it
    assert snap.step_max_s == snap.phases["serve.step"].max_s
    assert set(snap.step_max_phases) <= STEP_CHILDREN
    assert sum(snap.step_max_phases.values()) <= snap.step_max_s
    # one prefill wait per request admitted, each no longer than its TTFT
    assert snap.prefill_wait.count == len(done) == 5
    assert 0 <= snap.prefill_wait.max <= snap.ttft.max


def test_reset_stats_starts_the_counters_again(small_lm):
    eng = _engine(small_lm)
    _submit(eng, 1, n=2)
    eng.run_until_drained()
    eng.reset_stats()
    snap = eng.metrics_snapshot()
    assert snap.phases == {} and snap.step_max_s == 0.0
    assert snap.step_max_phases == {} and snap.prefill_wait.count == 0
    eng.submit(np.arange(5), max_new=2)
    eng.step()
    snap = eng.metrics_snapshot()
    assert snap.phases["serve.step"].count == 1
    assert snap.prefill_wait.count == 1


def test_sim_clock_engine_replays_from_its_seed(small_lm):
    """Every duration is read on the engine's clock: a clock that ticks
    once per read makes each span's length a count of the engine's own
    reads, so two runs of a seed give identical snapshots."""
    def run(seed):
        ticks = itertools.count()
        eng = _engine(small_lm, clock=lambda: next(ticks) * 1e-3)
        _submit(eng, seed)
        eng.run_until_drained()
        return eng.metrics_snapshot()

    snap = run(4)
    assert snap.phases["serve.step"].total_s > 0
    ok, diffs = seeded_replay_check(run, seed=4)
    assert ok, diffs


def _events(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(
        sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))[-1])
    return [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]


def _parent(evs, e):
    """The shortest other span enclosing ``e``."""
    outer = [o for o in evs if o is not e and o[1] <= e[1] and e[2] <= o[2]]
    return min(outer, key=lambda o: o[2] - o[1])[0] if outer else None


def test_profiler_trace_holds_the_nested_spans(small_lm, tmp_path):
    eng = _engine(small_lm)
    _submit(eng, 2, n=2)
    eng.step()                     # compile outside the trace
    _submit(eng, 3, n=2)
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    evs = _events(tmp_path)
    names = [e[0] for e in evs]
    assert names.count("serve.step") == 3
    assert {"serve.admit", "serve.prefill", "serve.paste"} <= set(names)
    want = {"serve.prepare": "serve.step", "serve.admit": "serve.step",
            "serve.decode": "serve.step", "serve.finish": "serve.step",
            "serve.prefill": "serve.admit", "serve.paste": "serve.admit"}
    want.update({p: "serve.sample" for p in SAMPLE_PARTS})
    for e in evs:
        if e[0] in want:
            assert _parent(evs, e) == want[e[0]], e
        elif e[0] == "serve.sample":
            assert _parent(evs, e) in ("serve.step", "serve.admit")
    steps = sorted(e[3]["step_num"] for e in evs if e[0] == "serve.step")
    assert steps == [1, 2, 3]
    # an admission's spans carry the rids it admitted
    admitted = {r.rid for r in eng.finished} | {
        r.rid for r in eng.slots if r is not None}
    for e in evs:
        if e[0] in ("serve.admit", "serve.prefill", "serve.paste") or (
                e[0] == "serve.sample" and _parent(evs, e) == "serve.admit"):
            rids = {int(x) for x in str(e[3]["rids"]).split()}
            assert rids and rids <= admitted and min(rids) >= 2
    prefill = [e for e in evs if e[0] == "serve.prefill"]
    assert all(e[3]["rows"] >= 1 and e[3]["bucket"] >= 1 for e in prefill)
