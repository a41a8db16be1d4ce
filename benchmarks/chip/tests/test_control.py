"""The control (the reference computed with int8 weights and
activations, W8A8), put in the program's place, makes a run come out not
correct under a limit the bf16 program meets on the same served tokens,
at a size a CPU test holds.  On the chip, at the cells' own size, the
same readings set the limit (see PERF.md)."""

import sys
import time

import jax
import pytest

import runner
import tiny

MID = dict(hidden_size=512, intermediate_size=1024, num_attention_heads=4,
           num_key_value_heads=2, num_hidden_layers=3, vocab_size=4096)
# at this size (CPU, seeds 1-6): program 0.020-0.068, control
# 0.18-0.35
LIMIT = 0.12


def run(seed, control):
    c = tiny.cell("batch", **MID)
    c.config["check"] = dict(c.config["check"], min_tokens=250, max_seqs=12,
                             logit_gap_limit=LIMIT)
    return runner.run_cell(c, seed, 5.0, False, jax.devices()[:1],
                           time.perf_counter(),
                           lambda m: print(m, file=sys.stderr),
                           control=control,
                           peak_table={"bf16_flops": 1e12, "hbm_bytes_s": 1e11})


@pytest.mark.parametrize("seed", [1, 2])
def test_program_meets_the_limit(seed):
    res = run(seed, None)
    assert res["correct"] is True
    assert res["compared"]["logit_gap"]["value"] <= LIMIT


@pytest.mark.parametrize("seed", [1, 2])
def test_control_in_the_programs_place_is_not_correct(seed):
    res = run(seed, "int8")
    assert res["correct"] is False
    assert res["compared"]["logit_gap"]["value"] > LIMIT
    assert res["control"]["program_logit_gap"] <= LIMIT
