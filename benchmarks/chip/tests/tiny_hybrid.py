"""A granitemoehybrid cell at a size the CPU runs in seconds: the
granite-4.0-h-small file at small widths (both kinds of mixer in a short
period, 3 of 16 experts held), driven through the same harness,
reference and readers."""

from __future__ import annotations

import copy
import json

import spec
import tiny

CONFIG = "granite-4.0-h-small-20l"
CELL = "granite-4.0-h-small-20l.batch"

TINY = {
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "layer_types": ["mamba", "attention"] * 2,
    "vocab_size": 512, "intermediate_size": 64,
    "shared_intermediate_size": 128, "mamba_n_heads": 8, "mamba_d_head": 32,
    "mamba_d_state": 16, "mamba_chunk_size": 16, "num_experts_per_tok": 4,
    "num_local_experts": 3, "held_experts": [0, 3],
    "published": {"num_hidden_layers": 40, "num_local_experts": 16},
    "attention_multiplier": 32 ** -1.0,
    "init": {"gain": 1.0, "out_gain": 4.0, "router_gain": 1.0,
             "embed_std": 0.02, "final_norm_scale": 400.0, "conv_std": 0.3,
             "dt_min": 0.001, "dt_max": 0.1},
    "serve": {"max_batch": 4, "max_len": 128, "kv_blocks": 40,
              "kv_block_size": 16, "prefill_buckets": [32, 64, 128],
              "max_prefill_batch": 2, "use_kernels": True},
    "check": {"logit_gap_limit": 1.0, "min_tokens": 60, "max_seqs": 4},
}


def config(**over) -> dict:
    c = json.loads((tiny.CHIP / "configs" / f"{CONFIG}.json").read_text())
    c = {**c, **copy.deepcopy(TINY), **over}
    c["name"] = "granite-hybrid-tiny"
    return c


def cell(**config_over) -> spec.Cell:
    mix = json.loads((tiny.CHIP / "traffic" / "batch.json").read_text())
    mix.update(copy.deepcopy(tiny.TRAFFIC["batch"]))
    bench = json.loads((tiny.CHIP.parents[1] / "BENCHMARK.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    per = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    return spec.Cell(name=CELL, config_name=CONFIG, traffic_name="batch",
                     chips=1, config=config(**config_over), traffic=mix,
                     end_to_end=e2e, per_layer=per)
