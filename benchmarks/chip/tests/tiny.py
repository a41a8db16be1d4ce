"""A cell at a size the CPU runs in seconds: the granite layout at small
widths, driven through the same harness, reference and readers."""

from __future__ import annotations

import copy
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent

import spec  # noqa: E402

TINY = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "serve": {"max_batch": 4, "max_len": 128, "kv_blocks": 40,
              "kv_block_size": 16, "prefill_buckets": [32, 64, 128],
              "max_prefill_batch": 2, "use_kernels": True},
    "check": {"logit_gap_limit": 1.0, "min_tokens": 60, "max_seqs": 4},
}

TRAFFIC = {
    "batch": {"prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 96},
              "output": {"median": 16, "sigma": 0.5, "min": 4, "max": 30},
              "max_total": 128, "queue_min": 4, "set_size": 16},
    "chat": {"rate_rps": 3.0,
             "prompt": {"median": 48, "sigma": 0.5, "min": 8, "max": 100},
             "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
             "max_total": 128},
}


def cell(traffic: str = "batch", **config_over) -> spec.Cell:
    config = json.loads((CHIP / "configs" / "granite-8b-18l.json").read_text())
    config = {**config, **copy.deepcopy(TINY), **config_over}
    config["name"] = "granite-tiny"
    mix = json.loads((CHIP / "traffic" / f"{traffic}.json").read_text())
    mix.update(copy.deepcopy(TRAFFIC[traffic]))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    name = f"granite-8b-18l.{traffic}"
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per = [m for m in bench["per_layer"] if name in m.get("workloads", [])]
    return spec.Cell(name=name, config_name=config["name"],
                     traffic_name=traffic, chips=1, config=config,
                     traffic=mix, end_to_end=e2e, per_layer=per)
