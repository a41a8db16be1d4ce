"""FLOP and byte counts of granite-4.0-h-small-20l at its published widths
against hand-worked values."""

import json

import spec
import weights_hybrid
from conftest import CHIP

CONFIG = json.loads((CHIP / "configs" / "granite-4.0-h-small-20l.json").read_text())
N = weights_hybrid.dims(CONFIG)


def test_dims_are_the_published_widths_and_the_cut():
    assert (N["d"], N["h"], N["g"], N["dh"]) == (4096, 32, 8, 128)
    assert (N["d_in"], N["mh"], N["mp"], N["ns"], N["conv"]) == \
        (8192, 128, 64, 128, 4)
    assert (N["f"], N["fs"], N["experts"], N["topk"]) == (768, 1536, 72, 10)
    assert N["held"] == (0, 9) and N["n_held"] == 9
    assert (N["layers"], N["n_mamba"], N["n_attn"]) == (20, 18, 2)
    assert N["kinds"][5] == N["kinds"][15] == "attention"
    assert N["vocab"] == N["vp"] == 100352


def test_weight_bytes_match_the_program_tree():
    from repro.configs.base import RunConfig
    from repro.models.api import build_model
    import jax
    harness = spec.module("harness", "serve_hybrid")
    model = build_model(harness.model_config(CONFIG),
                        RunConfig(param_dtype="bfloat16",
                                  compute_dtype="bfloat16", remat=False))
    tree = jax.eval_shape(model.init, jax.random.key(0))
    want = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    cnt = spec.module("counts", "granitemoehybrid")
    assert cnt.weight_bytes(N, 2) == want
    assert 8.84e9 < want < 8.86e9


def test_decode_flops_by_hand():
    cnt = spec.module("counts", "granitemoehybrid")
    # Mamba2: in_proj 2*4096*(16384+256+128), conv 2*4*8448, recurrence
    # 4*128*64*128, out_proj 2*8192*4096
    mamba = 2 * 4096 * 16768 + 2 * 4 * 8448 + 4 * 128 * 64 * 128 + 2 * 8192 * 4096
    # attention projections 2*(2*4096*4096 + 2*4096*1024), 1000 positions
    attn = 2 * (2 * 4096 * 4096 + 2 * 4096 * 1024) + 4 * 1000 * 32 * 128
    # router 2*4096*72, shared expert 6*4096*1536; head 2*4096*100352
    moe = 2 * 4096 * 72 + 6 * 4096 * 1536
    want = 18 * mamba + 2 * attn + 20 * moe + 2 * 4096 * 100352
    assert cnt.decode_token_flops(N, 1000) == want
    assert cnt.expert_slot_flops(N) == 6 * 4096 * 768
    f, b = cnt.decode_step_work(N, [1000, 24], 50, 2)
    assert f == cnt.decode_token_flops(N, 1000) + cnt.decode_token_flops(N, 24) \
        + 50 * 6 * 4096 * 768
    # state of one lane: 18 layers x (3 x 8448 + 128 x 64 x 128) float32
    assert cnt.lane_state_bytes(N) == 4 * 18 * (3 * 8448 + 128 * 64 * 128)
    kv = 2 * 8 * 128 * 2 * 2 * (1024 + 2)
    assert b == cnt.weight_bytes(N, 2) + 2 * 2 * cnt.lane_state_bytes(N) + kv


def test_prompt_flops_are_causal_at_real_length():
    cnt = spec.module("counts", "granitemoehybrid")
    one = cnt.prompt_flops(N, 1, 0)
    two = cnt.prompt_flops(N, 2, 0)
    # the second token adds its mixers and expert blocks, and attention
    # over one more position pair in each of the 2 attention layers
    per_tok = two - one
    assert per_tok == cnt.decode_token_flops(N, 0) - 2 * 4096 * 100352 \
        + 2 * 2 * (2 * 3 - 1 * 2) * 32 * 128
    assert cnt.prompt_flops(N, 512, 100) - cnt.prompt_flops(N, 512, 0) == \
        100 * cnt.expert_slot_flops(N)


def test_prompt_bytes_write_its_cache_once():
    cnt = spec.module("counts", "granitemoehybrid")
    f, b = cnt.prompt_work(N, 512, 100, 2)
    assert f == cnt.prompt_flops(N, 512, 100)
    assert b == cnt.weight_bytes(N, 2) + 2 * 2 * 8 * 128 * 2 * 512 \
        + cnt.lane_state_bytes(N)


def test_ssd_work_at_real_lengths():
    ssd = spec.module("counts", "mamba2_ssd")
    f, b = ssd.work(N, [512, 100], 2)
    assert f == 18 * 4 * 612 * 128 * 64 * 128
    per = lambda t: (2 * t * 128 * 64 * 2 + 2 * t * 128 * 2 + 4 * t * 128
                     + 4 * 128 * 64 * 128)
    assert b == 18 * (per(512) + per(100))
