"""Serving cells of a granitemoehybrid configuration: the program's
``ServeEngine`` on its paged pool (attention K/V in blocks, one Mamba2
state slot per lane), batched right-padded prefill through the
``flash_attention`` and ``mamba2_ssd`` kernels, decode through the
``paged_attention`` kernel, driven by the same load generator, window and
sample as ``harness/serve.py`` (imported, not copied).

What differs from a dense cell: the configuration's keys
(``granitemoehybrid``), its weights (``weights_hybrid.py``), and, in a
traced run, the decode and prefill programs' optimised HLO text, which
the per-scope readers (``scopes.py``) look each device operation up in.
"""

from __future__ import annotations

import gc
import sys
from pathlib import Path

import numpy as np

import spec
import weights_hybrid

serve = spec.module("harness", "serve")
program_class = serve.program_class
# where a traced run's profile goes; program_spans.py reads the program's
# spans from the same place
TRACE_DIR = Path(__file__).resolve().parents[3] / ".bench_trace"


def model_config(config: dict):
    """The program's ``ModelConfig`` for a granitemoehybrid file."""
    from repro.configs.base import ModelConfig

    if config.get("model_type") != "granitemoehybrid":
        raise ValueError("this harness serves granitemoehybrid configurations")
    if config["hidden_act"] != "silu" or config["mamba_n_groups"] != 1:
        raise ValueError("SwiGLU experts and one Mamba2 group are served here")
    n = weights_hybrid.dims(config)
    return ModelConfig(
        arch_id=config["name"], family="hybrid", n_layers=n["layers"],
        d_model=n["d"], n_heads=n["h"], n_kv_heads=n["g"], head_dim=n["dh"],
        d_ff=n["f"], vocab_size=n["vocab"],
        n_experts=n["experts"], top_k=n["topk"], n_shared_experts=1,
        shared_expert_ff=n["fs"], held_experts=n["held"],
        router="topk_softmax",
        ssm_state=n["ns"], ssm_expand=config["mamba_expand"],
        ssm_headdim=n["mp"], ssm_conv=n["conv"], ssm_chunk=n["chunk"],
        layer_types=n["kinds"],
        position_embedding=config["position_embedding_type"],
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rope_theta=float(config["rope_theta"]), act="silu", glu=True,
        qkv_bias=bool(config["attention_bias"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]), source=config["source"])


def build(config: dict, seed: int):
    import jax

    from repro.configs.base import RunConfig
    from repro.models.api import build_model
    from repro.serving.engine import EngineConfig, ServeEngine

    dt = config["torch_dtype"]
    s = config["serve"]
    model = build_model(model_config(config),
                        RunConfig(param_dtype=dt, compute_dtype=dt, remat=False,
                                  use_kernels=bool(s.get("use_kernels", True))))
    params = weights_hybrid.make(config, seed)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("benchmark weights do not match the program's "
                           "parameter tree")
    jax.block_until_ready(params)
    return ServeEngine(
        model, params, s["max_batch"], s["max_len"],
        prefill_buckets=s["prefill_buckets"],
        max_prefill_batch=s["max_prefill_batch"],
        config=EngineConfig(kv_blocks=s["kv_blocks"],
                            kv_block_size=s["kv_block_size"]))


def program_texts(engine) -> dict:
    """Optimised HLO text of the decode step and of the largest batched
    prefill, as compiled for this device (from the compilation cache the
    warm-up filled)."""
    import jax.numpy as jnp

    b = engine.backend
    toks = jnp.zeros((engine.max_batch, 1), jnp.int32)
    dec = b._decode.lower(engine.params, b.cache, toks,
                          jnp.asarray(b.block_tables)).compile()
    c, w = engine.max_prefill_batch, engine.buckets[-1]
    pre = engine._prefill_n.lower(engine.params, jnp.zeros((c, w), jnp.int32),
                                  jnp.ones((c,), jnp.int32)).compile()
    return {"decode": dec.as_text(), "prefill": pre.as_text()}


# an engine step this long is logged with its phases and the device's
# memory after it (the batch cell's steps take about 45 ms)
STALL_S = 1.0


def watch_stalls(engine, device, log):
    """Wrap ``engine.step`` so that a step of ``STALL_S`` or more is logged:
    the seconds of each engine phase in it (``serve.*`` spans, from the
    engine's own collector) and the device's memory statistics after it.
    A step costs two clock reads more."""
    step = engine.step

    def watched():
        before = {k: v[1] for k, v in engine.metrics.phases.items()}
        t0 = serve.CLOCK()
        out = step()
        took = serve.CLOCK() - t0
        if took >= STALL_S:
            spent = {k: round(v[1] - before.get(k, 0.0), 4)
                     for k, v in engine.metrics.phases.items()
                     if v[1] - before.get(k, 0.0) >= 1e-3}
            log(f"step of {took:.3f} s: phases {spent}; device memory "
                f"{device.memory_stats()}")
        return out

    engine.step = watched


def run(cell, seed: int, seconds: float, traced: bool, devices, control=None):
    """Set-up, window, then the comparison with the reference; the same
    steps and result as ``harness/serve.py``'s ``run``."""
    import jax

    import trace as bench_trace

    config = cell.config
    engine = build(config, seed)
    serve.warm(engine, config["vocab_size"])
    texts = program_texts(engine) if traced else None
    gc.collect()
    gc.freeze()
    setup_done = serve.CLOCK()
    trace_dir = None
    if traced:
        import shutil
        trace_dir = str(TRACE_DIR)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    watch_stalls(engine, devices[0],
                 lambda m: print(f"[bench] {m}", file=sys.stderr, flush=True))
    win = serve.drive(engine, cell, seed, seconds, traced)
    if traced:
        jax.profiler.stop_trace()
        win.programs = texts
    snap = engine.metrics_snapshot()
    failed = snap.rejected + snap.expired
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    chk = config["check"]
    sample = serve.served_sample(win, seed, chk["min_tokens"], chk["max_seqs"])
    seqs = [(np.concatenate([t.req.prompt,
                             np.asarray(t.req.out_tokens, np.int32)]),
             len(t.req.prompt)) for t in sample]
    engine.backend.cache = None
    engine.params = None
    del engine
    gc.unfreeze()
    gc.collect()
    ref = spec.module("reference", config["reference"])
    t_ref = serve.CLOCK()
    if seqs:
        served, ctl = ref.logit_gaps(config, seed, seqs,
                                     pad_to=config["serve"]["max_len"],
                                     control=control)
        gap = float(max(g.max() for g in served))
    else:
        served, ctl, gap = [], None, None
    ref_s = serve.CLOCK() - t_ref
    judged = gap if control is None else (
        float(max(g.max() for g in ctl)) if ctl else None)
    out = {
        "window_start": setup_done,
        "window": win, "snapshot": snap, "failed": failed,
        "attempted": len(win.tracks), "memory_peak_bytes": peak,
        "compared": {"logit_gap": (judged, float(chk["logit_gap_limit"]))},
        "reference_s": ref_s,
        "served_tokens_compared": int(sum(len(g) for g in served)),
        "seqs_compared": len(seqs), "program_gap": gap,
    }
    if traced:
        out["trace"] = bench_trace.load(trace_dir)
        out["trace_dir"] = trace_dir
    return out
