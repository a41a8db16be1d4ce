"""Serving cells: the program's ``ServeEngine`` with its paged KV pool,
Pallas kernels and ``Sampler``, driven by a seeded traffic mix.

The engine is built as ``repro.launch.serve.build_engine`` builds it
(``build_model`` at the configuration's widths in its served dtype with
``use_kernels``, ``ServeEngine`` on a paged pool), with the benchmark's
weights and the prefill buckets and prefill batch the configuration
names.  Set-up warms every program the window can use: one batched
prefill (and paste, and sampling) per pair of chunk size and bucket, and
the full-batch decode step and sampler.  The window then calls
``ServeEngine.step`` until ``--seconds`` have passed.

Timing is the host clock at the return of each step, which follows the
download of the step's sampled tokens, so every token's time is a
device-complete time.  A traced run adds host spans
(``jax.profiler.TraceAnnotation``) around the engine step, the backend's
decode step, the sampler, the batched prefill and the load generator's
phases, and records what the per-layer metrics count: each decode
step's lane contexts and each prefill's real lengths.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

import gen
import weights

CLOCK = time.perf_counter


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) configurations are served here")
    n = weights.dims(config)
    return ModelConfig(
        arch_id=config["name"], family="dense", n_layers=n["layers"],
        d_model=n["d"], n_heads=n["h"], n_kv_heads=n["g"], head_dim=n["dh"],
        d_ff=n["f"], vocab_size=n["vocab"],
        rope_theta=float(config["rope_theta"]), act="silu", glu=True,
        qkv_bias=bool(config.get("attention_bias", False)),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]), source=config["source"])


@dataclasses.dataclass
class Track:
    """One request as the load generator saw it (seconds after the window
    opened)."""
    req: object                     # the engine's Request
    spec: gen.Req
    due: Optional[float]
    submitted: float
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class Window:
    seconds: float = 0.0            # length of the window as measured
    tracks: List[Track] = dataclasses.field(default_factory=list)
    tokens: int = 0                 # tokens generated inside the window
    steps: int = 0
    late_max_s: float = 0.0         # largest submit time past a due time
    step_max_s: float = 0.0         # the longest engine step
    step_max_firsts: int = 0        # first tokens that step produced
    decode_ctx: List[List[int]] = dataclasses.field(default_factory=list)
    prefills: List[List[int]] = dataclasses.field(default_factory=list)
    compiles: int = 0
    compile_names: List[str] = dataclasses.field(default_factory=list)
    gc_runs: List[int] = dataclasses.field(default_factory=lambda: [0, 0, 0])
    gc_s: float = 0.0               # seconds inside Python's collector


def program_class(config: dict):
    """``classify(module, ops)`` for the trace: the sampler's programs by
    name, and a program holding a Pallas kernel as the decode step when
    the kernel takes the engine's block table (``s32[max_batch,
    max_len / block]``, the paged attention kernel), else as a batched
    prefill (the flash attention kernel)."""
    import trace as bench_trace

    s = config["serve"]
    table = f"s32[{s['max_batch']},{-(-s['max_len'] // s['kv_block_size'])}]"

    def classify(module, ops):
        if "sample_tokens" in module.name:
            return "sample"
        kernels = [o for o in ops if bench_trace.is_kernel(o)]
        if not kernels:
            return None
        return "decode" if any(table in k.name for k in kernels) else "prefill"
    return classify


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
    params = weights.make(config, seed)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("benchmark weights do not match the program's "
                           "parameter tree")
    jax.block_until_ready(params)
    engine = ServeEngine(
        model, params, s["max_batch"], s["max_len"],
        prefill_buckets=s["prefill_buckets"],
        max_prefill_batch=s["max_prefill_batch"],
        config=EngineConfig(kv_blocks=s["kv_blocks"],
                            kv_block_size=s["kv_block_size"],
                            prefix_cache=bool(s.get("prefix_cache", False))))
    return engine


def warm(engine, vocab: int) -> None:
    """Run every program the window can use once: each (chunk, bucket)
    batched prefill with its paste and sampling, then full-batch decode."""
    from repro.serving.sampling import SamplingParams

    rng = np.random.default_rng(0)
    for b in engine.buckets:
        for c in range(1, engine.max_prefill_batch + 1):
            for _ in range(c):
                engine.submit(rng.integers(0, vocab, b, dtype=np.int32),
                              max_new=1)
            engine.step()
            if engine.active() or engine.scheduler.depth:
                raise RuntimeError(f"warm-up chunk {c} x {b} did not admit "
                                   f"at once")
    for i in range(engine.max_batch):
        engine.submit(rng.integers(0, vocab, engine.buckets[0], dtype=np.int32),
                      max_new=3,
                      sampling=SamplingParams(temperature=0.8, top_p=0.95,
                                              seed=i) if i % 2 else None)
    engine.run_until_drained()
    engine.reset_stats()


def _submit(engine, r: gen.Req):
    from repro.serving.sampling import GREEDY, SamplingParams

    sp = GREEDY if r.greedy else SamplingParams(
        temperature=r.temperature, top_p=r.top_p, seed=r.seed)
    rid = engine.submit(r.prompt, max_new=r.max_new, sampling=sp)
    if rid is None:
        raise RuntimeError("engine queue refused a request")
    for q in engine.queue:
        if q.rid == rid:
            return q
    raise RuntimeError(f"request {rid} not in the queue after submit")


def instrument(engine, win: Window) -> None:
    """Host spans and counters of a traced run, on this engine instance."""
    from jax.profiler import TraceAnnotation

    step = engine.step

    def step_w():
        with TraceAnnotation("bench.engine_step"):
            return step()
    engine.step = step_w

    bstep = engine.backend.step

    def bstep_w(params, toks, active):
        win.decode_ctx.append([len(r.prompt) + len(r.out_tokens)
                               for r in engine.slots if r is not None])
        with TraceAnnotation("bench.backend_step"):
            return bstep(params, toks, active)
    engine.backend.step = bstep_w

    sample = engine.sampler.sample

    def sample_w(*a, **k):
        with TraceAnnotation("bench.sample"):
            return sample(*a, **k)
    engine.sampler.sample = sample_w

    prefill = engine._prefill_n

    def prefill_w(p, toks, lens):
        win.prefills.append(np.asarray(lens).tolist() + [int(toks.shape[1])])
        with TraceAnnotation("bench.prefill"):
            return prefill(p, toks, lens)
    engine._prefill_n = prefill_w


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _record(live: Dict[int, Track], t: float, win: Window) -> int:
    """Stamp the step's new tokens; returns how many were first tokens."""
    firsts = 0
    for rid in list(live):
        tr = live[rid]
        n = len(tr.req.out_tokens)
        if n > len(tr.times):
            firsts += not tr.times
            win.tokens += n - len(tr.times)
            tr.times.extend([t] * (n - len(tr.times)))
        if tr.req.done_t is not None:
            del live[rid]
    return firsts


@contextlib.contextmanager
def _collector_clock(win: Window):
    """Count Python's collections inside the block and time them."""
    began = [0.0]

    def clock(phase, info):
        if phase == "start":
            began[0] = CLOCK()
        else:
            win.gc_s += CLOCK() - began[0]
            win.gc_runs[info["generation"]] += 1
    gc.callbacks.append(clock)
    try:
        yield
    finally:
        gc.callbacks.remove(clock)


def drive(engine, cell, seed: int, seconds: float, traced: bool) -> Window:
    """The measured window."""
    from repro.runtime.guard import TraceGuard

    mix = cell.traffic
    vocab = cell.config["vocab_size"]
    win = Window()
    live: Dict[int, Track] = {}
    if mix["load"] == "backlog":
        stream = gen.backlog(mix, seed, vocab)
        queue_min = int(mix["queue_min"])
        due = None
    elif mix["load"] == "open_loop":
        due = gen.open_loop(mix, seed, vocab, seconds)
    else:
        raise ValueError(f"unknown load {mix['load']!r}")
    if traced:
        instrument(engine, win)
    nxt = 0
    with TraceGuard(max_retraces=None, name="window") as guard, \
            _span("bench.window", traced):
        t0 = CLOCK()
        while True:
            t = CLOCK() - t0
            with _span("bench.submit", traced):
                if due is None:
                    while engine.scheduler.depth < queue_min:
                        r = next(stream)
                        q = _submit(engine, r)
                        tr = Track(q, r, None, CLOCK() - t0)
                        win.tracks.append(tr)
                        live[q.rid] = tr
                else:
                    while nxt < len(due) and due[nxt].due <= t:
                        r = due[nxt]
                        q = _submit(engine, r)
                        tr = Track(q, r, r.due, CLOCK() - t0)
                        win.late_max_s = max(win.late_max_s,
                                             tr.submitted - r.due)
                        win.tracks.append(tr)
                        live[q.rid] = tr
                        nxt += 1
            if t >= seconds:
                break
            if engine.active() or engine.scheduler.depth:
                began = CLOCK()
                engine.step()
                win.steps += 1
                ended = CLOCK()
                firsts = _record(live, ended - t0, win)
                if ended - began > win.step_max_s:
                    win.step_max_s, win.step_max_firsts = ended - began, firsts
            else:
                wake = min(due[nxt].due if nxt < len(due) else seconds,
                           seconds)
                with _span("bench.wait_arrival", traced):
                    time.sleep(max(0.0, wake - (CLOCK() - t0)))
        win.seconds = CLOCK() - t0
    win.compiles = guard.total
    win.compile_names = guard.events[:5]
    return win


def served_sample(win: Window, seed: int, min_tokens: int, max_seqs: int):
    """Finished greedy requests to compare: the longest, then others drawn
    from the seed until ``min_tokens`` served tokens are in."""
    done = [t for t in win.tracks
            if t.spec.greedy and t.req.done_t is not None and t.req.out_tokens]
    if not done:
        return []
    done.sort(key=lambda t: (-len(t.req.out_tokens), t.req.rid))
    pick, rest = [done[0]], done[1:]
    rng = np.random.default_rng(seed + 1)
    order = rng.permutation(len(rest)).tolist()
    n = len(done[0].req.out_tokens)
    for i in order:
        if n >= min_tokens or len(pick) >= max_seqs:
            break
        pick.append(rest[i])
        n += len(rest[i].req.out_tokens)
    return pick


def run(cell, seed: int, seconds: float, traced: bool, devices, control=None):
    """Set-up, window, then the comparison with the reference.  Returns a
    dict the runner turns into the result line."""
    import jax

    import spec as bench_spec
    import trace as bench_trace

    config = cell.config
    engine = build(config, seed)
    warm(engine, config["vocab_size"])
    # the set-up heap (weights' tree, traced programs) is left out of the
    # window's collections, so their cost does not depend on set-up
    gc.collect()
    gc.freeze()
    setup_done = CLOCK()
    trace_dir = None
    if traced:
        from pathlib import Path
        trace_dir = str(Path(__file__).resolve().parents[3] / ".bench_trace")
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    win = drive(engine, cell, seed, seconds, traced)
    if traced:
        jax.profiler.stop_trace()
    snap = engine.metrics_snapshot()
    failed = snap.rejected + snap.expired
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    # free the program's state before the reference runs
    chk = config["check"]
    sample = served_sample(win, seed, chk["min_tokens"], chk["max_seqs"])
    seqs = [(np.concatenate([t.req.prompt,
                             np.asarray(t.req.out_tokens, np.int32)]),
             len(t.req.prompt)) for t in sample]
    engine.backend.cache = None
    engine.params = None
    del engine
    gc.unfreeze()
    gc.collect()
    ref = bench_spec.module("reference", config["reference"])
    t_ref = CLOCK()
    if seqs:
        served, ctl = ref.logit_gaps(config, seed, seqs,
                                     pad_to=config["serve"]["max_len"],
                                     control=control)
        gap = float(max(g.max() for g in served))
    else:
        served, ctl, gap = [], None, None          # nothing finished to compare
    ref_s = CLOCK() - t_ref
    # the control, where asked for, stands in the program's place
    judged = gap if control is None else (
        float(max(g.max() for g in ctl)) if ctl else None)
    compared = {"logit_gap": (judged, float(chk["logit_gap_limit"]))}
    out = {
        "window_start": setup_done,
        "window": win, "snapshot": snap, "failed": failed,
        "attempted": len(win.tracks), "memory_peak_bytes": peak,
        "compared": compared, "reference_s": ref_s,
        "served_tokens_compared": int(sum(len(g) for g in served)),
        "seqs_compared": len(seqs), "program_gap": gap,
    }
    if traced:
        out["trace"] = bench_trace.load(trace_dir)
        out["trace_dir"] = trace_dir
    return out
