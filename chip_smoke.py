#!/usr/bin/env python3
"""Smoke run of the system's main paths on TPU chips.  Not a benchmark.

    python3 chip_smoke.py               # one chip: serve granite-8b
    python3 chip_smoke.py --four-chips  # four chips: pipeline-parallel train step

One chip.  granite-8b at its published widths (d_model 4096, 32 q heads,
8 KV heads, head_dim 128, d_ff 14336, vocab 49152) with the depth cut from
36 to 24 layers, bf16 random weights from a seed, built by
``launch/serve.py``'s :func:`build_engine`: ``ServeEngine`` admission, the
paged KV backend, the compiled Pallas kernels and the ``Sampler``.  The run
checks that the decode step's compiled program holds a Pallas kernel, that
eight greedy requests (prompts of 72 to 1024 tokens) each complete with
``MAX_NEW`` tokens, and that prefill plus cache decode through the kernels
gives the same logits as the jnp path on the same params.

Four chips.  The ``pp_shardmap`` hybrid-schedule train step of
``launch/steps.py`` on a (1, 4) mesh, one granite-width layer per stage,
against the single-program reference (``model.loss`` value-and-grad plus
``adamw.update``) on one chip: loss, Adam first moments and updated params.

Every phase runs in this one process, because a chip belongs to one
process.  No phase's failure is caught: any failure exits nonzero.  Times
printed are set-up figures of a smoke run, compilation included.  The last
line of stdout is ``{"ok": true, "device": {"platform": "tpu", ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# serving phase
ARCH = "granite-8b"
LAYERS = 24             # of 36: bf16 weights (10.9 GB) + KV pool fit 16 GB
KV_BLOCKS = 1024        # 16-token blocks: 1.6 GB of K/V at 24 layers
MAX_BATCH, MAX_LEN, MAX_NEW = 8, 2048, 32
PROMPT_LENS = (72, 96, 112, 128, 640, 768, 896, 1024)  # prefill buckets 128, 1024
CHECK_LEN, CHECK_STEPS = 200, 4
# kernel vs jnp logits, bf16: the two paths round differently inside
# attention (the kernels keep probabilities and accumulators in float32,
# the jnp path rounds probabilities to bf16), which moves a bf16 logit by
# an ulp or two after 24 layers (at d_model 512 on the CPU: one ulp).  The
# tolerance is 4 bf16 ulps of the largest logit.  A path that drops a
# layer, reads the wrong cache block or mixes up KV groups is off by O(1).
LOGIT_ULPS = 4

# four-chip phase
PP_LAYERS = 4           # one per stage; the reference step fits one chip
PP_BATCH, PP_SEQ, PP_MICRO = 8, 128, 4
PP_LR = 1e-3
# pipeline vs single program, bf16 compute, one Adam step; the schedules
# sum the same terms in another order, each rounded to bf16 (2^-9
# relative).  Loss is a float32 mean over bf16-rounded logits: 2e-3
# relative.  Adam's first moments are 0.1 x the clipped gradient: 3e-2
# relative L2.  Adam's first update is close to lr * sign(g), so an element
# whose gradient is near zero may step the other way: 0.2 relative L2 of
# the update.  (At d_model 256 on the CPU the three read 2e-7, 3.4e-3 and
# 3.7e-2.)
PP_LOSS_RTOL, PP_M_RTOL, PP_UPDATE_RTOL = 2e-3, 3e-2, 0.2


def pin_tpu() -> None:
    """Run on the TPU or not at all: JAX may not fall back to the CPU.
    An environment that lists the TPU among other platforms ("tpu,cpu")
    is narrowed to the TPU alone; one that does not list it is refused."""
    platforms = os.environ.get("JAX_PLATFORMS") or "tpu"
    if "tpu" not in platforms.split(","):
        raise SystemExit(f"chip_smoke: JAX_PLATFORMS={platforms!r}; this run "
                         f"needs the TPU (JAX_PLATFORMS=tpu)")
    os.environ["JAX_PLATFORMS"] = "tpu"
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, str(SRC))


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def peak_gb(device) -> float:
    return (device.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9


# ---------------------------------------------------------------------------
# one chip: serving
# ---------------------------------------------------------------------------

def paged_logits(model, params, prompt, feed, max_len: int, kv_blocks: int):
    """Logits (1 + len(feed), V) of ``prompt``'s prefill, then of decode
    steps that feed ``feed`` one token at a time, through a one-lane paged
    backend: the engine's own prefill paste and decode step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.backends import make_backend
    from repro.serving.engine import EngineConfig

    n = len(prompt)
    backend = make_backend(model, 1, max_len, EngineConfig(kv_blocks=kv_blocks))
    prefill = jax.jit(model.prefill, static_argnums=2)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            max_len)
    res = backend.alloc(n, n + len(feed), None)
    backend.prefill_paste(0, cache, 0, n, n, res)
    out = [logits[0]]
    for tok in feed:
        if not backend.prepare_lane(0):
            raise RuntimeError("check pool exhausted")
        out.append(backend.step(params, np.asarray([[tok]], np.int32),
                                np.asarray([True]))[0])
    vocab = model.cfg.vocab_size
    return np.stack([np.asarray(o[:vocab], np.float32) for o in out])


def kernel_vs_jnp(model, params, *, prompt_len: int, steps: int,
                  max_len: int, seed: int) -> None:
    """Check the kernel path's logits against the jnp path's
    (``use_kernels=False``) on the same params, teacher-forced."""
    import numpy as np

    from repro.models.api import build_model

    ref = build_model(model.cfg, dataclasses.replace(model.rcfg,
                                                     use_kernels=False))
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, model.cfg.vocab_size, prompt_len + steps,
                        dtype=np.int32)
    prompt, feed = toks[:prompt_len], toks[prompt_len:]
    blocks = -(-(prompt_len + steps) // 16) + 1
    got = paged_logits(model, params, prompt, feed, max_len, blocks)
    want = paged_logits(ref, params, prompt, feed, max_len, blocks)
    err = float(np.abs(got - want).max())
    top = float(np.abs(want).max())
    tol = LOGIT_ULPS * 2.0 ** (np.floor(np.log2(top)) - 7)   # bf16 ulp
    log(f"kernel vs jnp logits: max|diff| {err:.4g} over {got.shape[0]} "
        f"positions (max|logit| {top:.4g}, tolerance {tol:.4g})")
    if not err <= tol:
        raise AssertionError(f"kernel vs jnp logits differ by {err} > {tol}")


def decode_hlo(engine) -> str:
    """Compiled text of the engine's paged decode step at its live shapes."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(engine.model.decode_state.pool_step, donate_argnums=1)
    toks = jnp.zeros((engine.max_batch, 1), jnp.int32)
    return step.lower(engine.params, engine.backend.cache, toks,
                      jnp.asarray(engine.backend.block_tables)
                      ).compile().as_text()


def serve(engine, prompt_lens, max_new: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    for n in prompt_lens:
        engine.submit(rng.integers(0, engine.model.cfg.vocab_size, n,
                                   dtype=np.int32), max_new=max_new)
    return engine.run_until_drained()


def one_chip(seed: int) -> None:
    import jax

    from repro.launch.serve import build_engine

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    engine = build_engine(ARCH, reduced=False, layers=LAYERS,
                          max_batch=MAX_BATCH, max_len=MAX_LEN,
                          kv_blocks=KV_BLOCKS, seed=seed)
    cfg = engine.model.cfg
    jax.block_until_ready(engine.params)
    log(f"{ARCH}: d_model {cfg.d_model}, {cfg.n_heads} q / {cfg.n_kv_heads} "
        f"kv heads x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"depth cut 36 -> {cfg.n_layers}; bf16; backend "
        f"{engine.backend.name}, {KV_BLOCKS} x 16-token blocks; params built "
        f"in {time.perf_counter() - t0:.1f}s")

    if "tpu_custom_call" not in decode_hlo(engine):
        raise AssertionError("decode step compiled without a Pallas kernel")
    log("decode step HLO holds tpu_custom_call")

    t0 = time.perf_counter()
    done = serve(engine, PROMPT_LENS, MAX_NEW, seed)
    dt = time.perf_counter() - t0
    lens = sorted(len(r.out_tokens) for r in done)
    if len(done) != len(PROMPT_LENS) or lens != [MAX_NEW] * len(PROMPT_LENS):
        raise AssertionError(f"{len(done)} of {len(PROMPT_LENS)} requests "
                             f"done, output lengths {lens}")
    log(f"served {len(done)} requests, {sum(lens)} tokens, "
        f"{engine.steps} engine steps in {dt:.1f}s (first run: compiles "
        f"included); peak device memory {peak_gb(dev):.2f} GB")

    kernel_vs_jnp(engine.model, engine.params, prompt_len=CHECK_LEN,
                  steps=CHECK_STEPS, max_len=MAX_LEN, seed=seed)
    log(f"peak device memory {peak_gb(dev):.2f} GB")


# ---------------------------------------------------------------------------
# four chips: the pipeline-parallel train step
# ---------------------------------------------------------------------------

def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over two trees, on the host."""
    import jax
    import numpy as np

    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        num += float(np.sum(np.square(x - y), dtype=np.float64))
        den += float(np.sum(np.square(y), dtype=np.float64))
    return (num / den) ** 0.5


def pipeline_vs_reference(cfg, rcfg, shape, opt_cfg, mesh, seed: int):
    """One hybrid-schedule pp_shardmap step on ``mesh`` and one
    single-program step on the default device, from the same params and
    batch.  Returns (loss_pp, loss_ref, m_rel_l2, update_rel_l2)."""
    import jax
    import numpy as np

    from repro.launch.steps import make_train_step
    from repro.models.api import init_params
    from repro.optim import adamw

    built = make_train_step(cfg, shape, rcfg, mesh, opt_cfg,
                            strategy="pp_shardmap")
    model = built["model"]
    p_shard, o_shard, b_shard = built["in_shardings"]
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (shape.global_batch, shape.seq_len), dtype=np.int32)
    n_model = mesh.shape["model"]

    params = init_params(model, seed)
    p0 = jax.device_get(params)
    params_pp = jax.jit(built["to_pipeline"], out_shardings=p_shard)(params)
    del params
    for leaf in jax.tree.leaves(params_pp["blocks"]):
        shards = leaf.addressable_shards
        if (len({s.device for s in shards}) != n_model
                or any(s.data.shape[0] != 1 for s in shards)):
            raise AssertionError(
                f"stage params not one per device: "
                f"{[(s.device, s.data.shape) for s in shards]}")
    log(f"stage params: one stage on each of {n_model} devices "
        f"{sorted(d.id for d in mesh.devices.flat)}")
    opt_pp = jax.jit(adamw.init, out_shardings=o_shard)(params_pp)
    step = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                   out_shardings=built["out_shardings"], donate_argnums=(0, 1))
    t0 = time.perf_counter()
    new_pp, opt_pp, metrics = step(params_pp, opt_pp,
                                   jax.device_put({"tokens": tokens}, b_shard))
    loss_pp = float(metrics["loss"])
    log(f"pipeline step ({built['meta']['S']} stages, "
        f"{built['meta']['M']} microbatches, {rcfg.schedule}) in "
        f"{time.perf_counter() - t0:.1f}s, compile included")
    new_pp = built["from_pipeline"](jax.device_get(new_pp))
    m_pp = built["from_pipeline"](jax.device_get(opt_pp["m"]))
    del opt_pp, metrics

    def ref_step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch)[0])(params)
        new_p, new_o, _ = adamw.update(opt_cfg, grads, opt, params)
        return new_p, new_o, loss

    params = init_params(model, seed)
    new_ref, opt_ref, loss_ref = jax.jit(ref_step, donate_argnums=(0, 1))(
        params, adamw.init(params), {"tokens": tokens})
    loss_ref = float(loss_ref)
    new_ref = jax.device_get(new_ref)
    m_ref = jax.device_get(opt_ref["m"])
    del opt_ref

    # bf16 params: the difference of two is exact in float32
    delta = lambda new: jax.tree.map(
        lambda n, o: np.asarray(n, np.float32) - np.asarray(o, np.float32),
        new, p0)
    return (loss_pp, loss_ref, rel_l2(m_pp, m_ref),
            rel_l2(delta(new_pp), delta(new_ref)))


def four_chips(seed: int) -> None:
    import jax

    from repro.configs import RunConfig, ShapeConfig, get_config
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw

    n = len(jax.devices())
    if n != 4:
        raise SystemExit(f"chip_smoke --four-chips: {n} devices, need 4")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=PP_LAYERS)
    rcfg = RunConfig(param_dtype="bfloat16", compute_dtype="bfloat16",
                     remat=False, schedule="hybrid", microbatches=PP_MICRO)
    shape = ShapeConfig("smoke", PP_SEQ, PP_BATCH, "train")
    opt_cfg = adamw.AdamWConfig(lr=PP_LR, warmup_steps=0, schedule="const",
                                weight_decay=0.0)
    log(f"{ARCH} train step: published widths, depth cut 36 -> {PP_LAYERS}, "
        f"bf16, batch {PP_BATCH} x {PP_SEQ} tokens")
    loss_pp, loss_ref, m_err, upd_err = pipeline_vs_reference(
        cfg, rcfg, shape, opt_cfg, make_host_mesh(), seed)
    loss_err = abs(loss_pp - loss_ref) / abs(loss_ref)
    log(f"loss pipeline {loss_pp:.6f} vs reference {loss_ref:.6f} "
        f"(rel {loss_err:.3g}, tol {PP_LOSS_RTOL}); Adam m rel L2 "
        f"{m_err:.3g} (tol {PP_M_RTOL}); update rel L2 {upd_err:.3g} "
        f"(tol {PP_UPDATE_RTOL})")
    if not (loss_err <= PP_LOSS_RTOL and m_err <= PP_M_RTOL
            and upd_err <= PP_UPDATE_RTOL):
        raise AssertionError("pipeline step disagrees with the reference")
    log(f"peak device memory {max(peak_gb(d) for d in jax.devices()):.2f} GB")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pipeline-parallel train step on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    pin_tpu()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found {dev.platform!r}, not a TPU")
    log(f"smoke run, not a benchmark: {len(devices)} x {dev.device_kind}; "
        f"compile cache {enable_compile_cache()}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
