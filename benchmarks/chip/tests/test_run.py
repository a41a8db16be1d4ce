"""A whole run of a cell past the look for the chip, on the CPU at a
small size: a sound run is correct, and each fault a serving cell can
have, planted under the timed path, makes ``correct`` come out false."""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import runner
import tiny

STAND_IN_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}


def run(traffic="batch", seconds=3.0, traced=False, control=None):
    return runner.run_cell(tiny.cell(traffic), 2**31 + 12345, seconds, traced,
                           jax.devices()[:1], time.perf_counter(),
                           lambda m: print(m, file=sys.stderr),
                           control=control, peak_table=STAND_IN_PEAKS)


def test_sound_run_is_correct_and_reports_its_metrics():
    res = run()
    assert res["correct"] is True
    assert list(res)[0] == "correct" and list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    gap = res["compared"]["logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_chat_run_reports_tails():
    res = run("chat")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p98_ms", "setup_s"}


def _stale_step(monkeypatch):
    """The decode step returns the cache it was given: no K/V is written."""
    from repro.serving.backends import PagedBackend
    orig = PagedBackend.step

    def step(self, params, tokens, active):
        keep = jax.tree.map(jnp.copy, self.cache)
        out = orig(self, params, tokens, active)
        self.cache = keep
        return out
    monkeypatch.setattr(PagedBackend, "step", step)


def _half_batch(monkeypatch):
    """The decode step computes the first half of the lanes only."""
    from repro.serving.backends import PagedBackend
    orig = PagedBackend.step

    def step(self, params, tokens, active):
        logits = orig(self, params, tokens, active)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:1])
    monkeypatch.setattr(PagedBackend, "step", step)


def _altered_token(monkeypatch):
    """Each sampled token is replaced by its neighbour id."""
    from repro.serving.sampling import Sampler
    orig = Sampler.sample

    def sample(self, logits, *a, **k):
        toks = orig(self, logits, *a, **k)
        return (np.asarray(toks) + 1) % logits.shape[-1]
    monkeypatch.setattr(Sampler, "sample", sample)


@pytest.mark.parametrize("fault", [_stale_step, _half_batch, _altered_token],
                         ids=["state_unchanged", "half_batch", "token_altered"])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run()
    assert res["correct"] is False
    gap = res["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _run_py(cwd, env_over):
    import os
    import subprocess
    env = {**os.environ, **env_over}
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                           "granite-8b-18l.batch", "--seed", "1", "--seconds",
                           "1", "--trace", "0"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    from conftest import CHIP
    p = _run_py(CHIP.parents[1], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""
    assert "runs on the chip only" in p.stderr


def test_refuses_without_the_program(tmp_path):
    import shutil
    from conftest import CHIP
    root = CHIP.parents[1]
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"JAX_PLATFORMS": "tpu"})
    assert p.returncode != 0 and p.stdout == ""
    assert "the program (src/repro) is not in" in p.stderr
