"""A whole run of the granitemoehybrid cell past the look for the chip, on
the CPU at a small size: a sound run is correct and reports the cell's
end-to-end metrics; a fault planted under the timed path (the Mamba2 state
left unchanged by the decode step, each token altered) makes ``correct``
come out false; and the int8 control, put in the program's place, is not
correct under a limit the bf16 program meets at a middle size (on the
chip the same readings set the cell's limit, see PERF.md)."""

import sys
import time

import jax
import numpy as np
import pytest

import runner
import tiny_hybrid

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}
MID = dict(hidden_size=512, num_attention_heads=4, num_key_value_heads=2,
           vocab_size=4096, intermediate_size=256,
           shared_intermediate_size=512, mamba_n_heads=16, mamba_d_head=64,
           mamba_d_state=32)
# at this size (CPU, seeds 1-3): program 0.004-0.037, control 0.057-0.148
LIMIT = 0.045


def run(cell, seed=2**31 + 4321, seconds=3.0, control=None):
    return runner.run_cell(cell, seed, seconds, False, jax.devices()[:1],
                           time.perf_counter(),
                           lambda m: print(m, file=sys.stderr),
                           control=control, peak_table=PEAKS)


def test_sound_run_is_correct_and_reports_its_metrics():
    res = run(tiny_hybrid.cell())
    assert res["correct"] is True
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def _state_unchanged(monkeypatch):
    """The decode step writes K/V but leaves every lane's Mamba2 state as
    the prefill left it."""
    from repro.serving.backends import PagedBackend
    orig = PagedBackend.step

    def step(self, params, tokens, active):
        keep = jax.tree.map(np.asarray, self.cache["state"])
        out = orig(self, params, tokens, active)
        self.cache = {**self.cache,
                      "state": jax.tree.map(jax.numpy.asarray, keep)}
        return out
    monkeypatch.setattr(PagedBackend, "step", step)


def _altered_token(monkeypatch):
    """Each sampled token is replaced by its neighbour id."""
    from repro.serving.sampling import Sampler
    orig = Sampler.sample

    def sample(self, logits, *a, **k):
        toks = orig(self, logits, *a, **k)
        return (np.asarray(toks) + 1) % logits.shape[-1]
    monkeypatch.setattr(Sampler, "sample", sample)


@pytest.mark.parametrize("fault", [_state_unchanged, _altered_token],
                         ids=["state_unchanged", "token_altered"])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run(tiny_hybrid.cell())
    assert res["correct"] is False
    gap = res["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _mid():
    c = tiny_hybrid.cell(**MID)
    c.config["check"] = dict(c.config["check"], min_tokens=250, max_seqs=12,
                             logit_gap_limit=LIMIT)
    return c


@pytest.mark.parametrize("seed", [1, 3])
def test_control_in_the_programs_place_is_not_correct(seed):
    # a window long enough that a loaded CPU still finishes the 250 tokens
    # the comparison takes (in 5 s under four test workers it fell short)
    res = run(_mid(), seed, 8.0, control="int8")
    assert res["correct"] is False
    assert res["compared"]["logit_gap"]["value"] > LIMIT
    assert res["control"]["program_logit_gap"] <= LIMIT
