"""Paged KV-cache serving: block manager, model hooks, engine preemption."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, reduced_config
from repro.models.api import build_model
from repro.serving.block_manager import BlockManager
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.sampling import SamplingParams

RCFG = RunConfig(param_dtype="float32", compute_dtype="float32", remat=False)


@pytest.fixture(scope="module")
def small_lm():
    cfg = dataclasses.replace(reduced_config(get_config("granite-8b")),
                              n_layers=2)
    model = build_model(cfg, RCFG)
    return model, model.init(jax.random.key(0))


# ---------------------------------------------------------------------------
# block manager
# ---------------------------------------------------------------------------

def test_block_manager_alloc_release_watermark():
    m = BlockManager(8, block_size=4, watermark_frac=0.25)
    assert m.blocks_needed(1) == 1 and m.blocks_needed(4) == 1
    assert m.blocks_needed(5) == 2 and m.blocks_needed(0) == 1
    a = m.allocate(3)
    assert len(a) == 3 and all(1 <= b <= 8 for b in a)      # 0 is the sink
    assert m.in_use == 3 and m.free == 5 and m.peak_in_use == 3
    # watermark: 2 blocks reserved for growth -> only 3 admittable
    assert m.can_admit(3) and not m.can_admit(4)
    assert m.allocate(6) is None and m.in_use == 3          # no side effects
    b = m.allocate(5)                                       # growth ignores it
    assert len(b) == 5 and m.free == 0 and m.peak_in_use == 8
    m.release(a)
    assert m.free == 3 and m.in_use == 5
    with pytest.raises(ValueError):
        m.release([0])                                      # sink is unmanaged
    with pytest.raises(ValueError):
        m.release([a[0]])                                   # double free
    with pytest.raises(ValueError):
        m.release([b[0], b[0]])                             # dup in one call
    assert m.free == 3                                      # list untouched
    with pytest.raises(ValueError):
        BlockManager(0, 4)


def test_pool_gate_excludes_nonattention_state():
    """The pool layout is offered only where decode state is a
    position-addressed K/V cache: dense + moe.  Recurrent / enc-dec
    families advertise their own state kind instead."""
    for arch in ("granite-8b", "grok-1-314b", "llama4-scout-17b-a16e"):
        m = build_model(reduced_config(get_config(arch)), RCFG)
        if m.cfg.attention == "full":
            assert m.decode_state.poolable, arch
            assert m.decode_state.kind == "attention", arch
    for arch in ("rwkv6-1.6b", "zamba2-7b"):
        m = build_model(reduced_config(get_config(arch)), RCFG)
        assert not m.decode_state.poolable, arch
        assert m.decode_state.kind == "recurrent", arch
    m = build_model(reduced_config(get_config("whisper-small")), RCFG)
    assert not m.decode_state.poolable
    assert m.decode_state.kind == "encdec"


# ---------------------------------------------------------------------------
# model-level parity
# ---------------------------------------------------------------------------

def test_dense_vs_paged_decode_logit_parity(small_lm):
    """Same prefill pasted into a block pool must decode to the same logits
    as the dense lane cache, for several steps (gather reference path)."""
    model, params = small_lm
    cfg = model.cfg
    rng = np.random.default_rng(7)
    P, bs, max_len = 11, 4, 32
    prompt = rng.integers(0, cfg.vocab_size, size=P)
    logits, dense = model.prefill(params,
                                  {"tokens": jnp.asarray(prompt[None])},
                                  max_len)
    paged = model.decode_state.pool_init(1, 10, bs)
    blocks = [4, 2, 9]                          # deliberately out of order
    flat = np.array([blocks[i // bs] * bs + i % bs for i in range(P)])
    for kk in ("k", "v"):
        pool = paged["layers"][kk]
        nl = pool.shape[0]
        fl = pool.reshape((nl, -1) + pool.shape[3:])
        paged["layers"][kk] = fl.at[:, flat].set(
            dense["layers"][kk][:, 0, :P]).reshape(pool.shape)
    paged["pos"] = jnp.asarray([P], jnp.int32)
    bt = np.zeros((1, 8), np.int32)
    # prompt blocks + growth blocks for the decoded tokens (the engine's
    # grow-on-decode guarantees a real block exists before every write —
    # only idle lanes ever write to the sink)
    bt[0, :5] = blocks + [1, 6]
    v = cfg.vocab_size
    tok = int(jnp.argmax(logits[0, :v]))
    for _ in range(6):
        t = jnp.asarray([[tok]], jnp.int32)
        ld, dense = model.decode_step(params, dense, t)
        lp, paged = model.decode_state.pool_step(params, paged, t,
                                                 jnp.asarray(bt))
        np.testing.assert_allclose(np.asarray(ld[0, :v]),
                                   np.asarray(lp[0, :v]), atol=1e-5)
        tok = int(jnp.argmax(ld[0, :v]))


# (h, g, d, bs, nb, table width, pool dtype, STEP_BYTES or None for the
#  module's own, each lane's blocks or count of fresh blocks (0: an
#  all-sink row), each lane's pos)
KERNEL_CASES = {
    # the original case: one gather covers the whole 4-block table
    "one_gather": (4, 2, 16, 8, 9, 4, "float32", None,
                   [[3, 5], [1, 2, 7, 4], [8]], [9, 30, 0]),
    # 1 KB f32 pages, 3 pages a gather over a 7-wide table: contexts end
    # mid-gather, on a gather's edge, on the edge plus one page, fill the
    # whole table; idle sink rows first and in the middle
    "steps_of_3": (4, 2, 16, 8, 40, 7, "float32", 3 * 1024,
                   [0, 2, 3, 4, 0, 7, 1], [55, 12, 23, 24, 3, 55, 0]),
    # granite's page in bf16: 16 pages of (16 x 8, 128) a gather over a
    # 20-wide table
    "bf16_granite_page": (32, 8, 128, 16, 64, 20, "bfloat16", None,
                          [0, 6, 16, 20, 17], [319, 83, 255, 319, 271]),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_paged_kernel_matches_gather_reference(case, monkeypatch):
    """The Pallas paged flash-decode kernel must match the pure-jnp gather
    path (interpret mode on CPU), streaming only each lane's live blocks
    whatever the gather size; a lane whose row starts with the sink block
    holds none and returns zeros."""
    from repro.kernels import ops as kops
    from repro.kernels import paged_attention as pa
    from repro.models.attention import _repeat_kv, sdpa

    h, g, d, bs, nb, mb, dtype, step_bytes, lanes, pos = KERNEL_CASES[case]
    if step_bytes is not None:
        monkeypatch.setattr(pa, "STEP_BYTES", step_bytes)
    rng = np.random.default_rng(0)
    b = len(lanes)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), dt)
    kp = jnp.asarray(rng.normal(size=(nb, bs, g, d)), dt)
    vp = jnp.asarray(rng.normal(size=(nb, bs, g, d)), dt)
    free = list(rng.permutation(np.arange(1, nb)))
    bt = np.zeros((b, mb), np.int32)
    for i, x in enumerate(lanes):
        blocks = x if isinstance(x, list) else [free.pop() for _ in range(x)]
        bt[i, :len(blocks)] = blocks
    pos = jnp.asarray(pos, jnp.int32)    # last written position
    bt = jnp.asarray(bt)
    out = kops.paged_decode_attention(q, kp, vp, bt, pos, scale=d ** -0.5)
    span = mb * bs
    f32 = lambda a: a.astype(jnp.float32)
    ck = f32(kp)[bt].reshape(b, span, g, d)
    cv = f32(vp)[bt].reshape(b, span, g, d)
    valid = jnp.arange(span)[None, :] <= pos[:, None]
    ref = sdpa(f32(q), _repeat_kv(ck, h // g), _repeat_kv(cv, h // g),
               valid[:, None, None, :], d ** -0.5)
    live = np.asarray(bt[:, 0]) != 0
    # f32 keeps the original bound; a bf16 pool and output round to 8 bits
    atol, rtol = (2e-5, 0) if dtype == "float32" else (2e-2, 2e-2)
    np.testing.assert_allclose(np.asarray(f32(out))[live],
                               np.asarray(ref)[live], atol=atol, rtol=rtol)
    assert not np.asarray(f32(out))[~live].any()


@pytest.mark.parametrize("path", ["prefix_cache_preempt", "spec_rollback"])
def test_kernel_engine_matches_gather_engine(small_lm, monkeypatch, path):
    """The paged engine on the Pallas kernel (interpret mode on CPU) decodes
    the same tokens as on the gather path, over the block manager's own
    tables: copy-on-write prefix sharing, preemption (a released row goes
    back to the sink), the speculative verify window and its rollback.
    The kernel treats a row that starts with the sink as idle, so every
    lane a step serves must hold a real first block."""
    from repro.kernels import ops as kops
    from repro.serving.backends import PagedBackend
    from repro.serving.speculative import SpecEngine

    model, params = small_lm
    # a 16x attention output projection lets what each lane attends to
    # decide its tokens; at 1x the random model repeats its last token
    params = {**params, "blocks": {**params["blocks"], "attn": {
        **params["blocks"]["attn"],
        "wo": params["blocks"]["attn"]["wo"] * 16}}}
    kmodel = build_model(model.cfg,
                         dataclasses.replace(RCFG, use_kernels=True))
    dcfg = dataclasses.replace(model.cfg, n_layers=1)
    draft = build_model(dcfg, RCFG)
    dparams = draft.init(jax.random.key(3))
    traced, checked = [], []
    kernel = kops.paged_decode_attention
    monkeypatch.setattr(kops, "paged_decode_attention",
                        lambda *a, **k: traced.append(1) or kernel(*a, **k))

    def sink_first_only_when_idle(run):
        def wrapped(self, params, tokens, active):
            # every lane the step serves holds a real first block
            assert (self.block_tables[active, 0] != 0).all()
            checked.append(1)
            return run(self, params, tokens, active)
        return wrapped

    for name in ("step", "verify_step"):
        monkeypatch.setattr(PagedBackend, name, sink_first_only_when_idle(
            getattr(PagedBackend, name)))
    rng = np.random.default_rng(13)
    v = model.cfg.vocab_size
    prefix = rng.integers(0, v, size=12)
    prompts = [np.concatenate([prefix, rng.integers(0, v, size=int(n))])
               for n in (6, 9, 4, 8)]

    def serve(m):
        config = EngineConfig(kv_blocks=12, kv_block_size=4,
                              prefix_cache=path == "prefix_cache_preempt")
        if path == "prefix_cache_preempt":
            eng = ServeEngine(m, params, max_batch=4, max_len=64,
                              config=config)
        else:
            eng = SpecEngine(m, params, draft, dparams, max_batch=4,
                             max_len=64, spec_k=3, config=config)
        rids = [eng.submit(p, max_new=7) for p in prompts]
        eng.run_until_drained()
        done = {r.rid: list(r.out_tokens) for r in eng.finished}
        return [done[r] for r in rids], eng.metrics_snapshot()

    ref, _ = serve(model)
    assert not traced
    got, snap = serve(kmodel)
    assert traced and checked
    assert got == ref
    assert snap.preemptions > 0 and snap.resumes > 0
    if path == "prefix_cache_preempt":
        assert snap.prefix_hit_rate > 0
    else:
        assert snap.spec_acceptance_rate < 1      # some windows rolled back


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def _run(model, params, prompts, config=None, max_batch=4, max_new=6,
         sampling=None, max_len=48):
    eng = ServeEngine(model, params, max_batch=max_batch, max_len=max_len,
                      config=config)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new=max_new,
                   sampling=sampling[i] if sampling else None)
    done = eng.run_until_drained()
    return {r.rid: r.out_tokens for r in done}, eng.metrics_snapshot()


def test_paged_engine_matches_dense_tokens(small_lm):
    model, params = small_lm
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=int(n))
               for n in (5, 9, 14, 7, 21, 3)]
    dense, _ = _run(model, params, prompts)
    paged, snap = _run(model, params, prompts,
                       EngineConfig(kv_blocks=40, kv_block_size=4))
    assert dense == paged
    assert snap.preemptions == 0
    assert snap.kv_blocks_total == 40 and snap.kv_blocks_peak > 0
    assert 0.0 < snap.kv_block_utilization <= 1.0


def test_preempt_then_resume_token_identical_greedy(small_lm):
    """A pool too small for every admitted lane to grow must preempt, and
    the preempted greedy request must resume with identical output."""
    model, params = small_lm
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=int(n))
               for n in (5, 9, 14, 7, 21, 3)]
    dense, _ = _run(model, params, prompts)
    tight, snap = _run(model, params, prompts,
                       EngineConfig(kv_blocks=9, kv_block_size=4))
    assert dense == tight
    assert snap.preemptions > 0 and snap.resumes > 0
    assert snap.completed == len(prompts)


def test_preempt_then_resume_token_identical_stochastic(small_lm):
    """Preemption freezes the per-lane PRNG counter, so a STOCHASTIC
    request also resumes on the exact sample stream it left."""
    model, params = small_lm
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=int(n))
               for n in (8, 13, 6, 17)]
    sp = [SamplingParams(temperature=8.0, top_k=64, seed=100 + i)
          for i in range(len(prompts))]
    ample, _ = _run(model, params, prompts,
                    EngineConfig(kv_blocks=64, kv_block_size=4), sampling=sp)
    tight, snap = _run(model, params, prompts,
                       EngineConfig(kv_blocks=8, kv_block_size=4),
                       sampling=sp)
    assert snap.preemptions > 0
    assert ample == tight


def test_admission_with_zero_free_blocks_waits(small_lm):
    """With every block held by a running lane, new work must stay queued
    (no crash, no drop) and admit once blocks free up."""
    model, params = small_lm
    rng = np.random.default_rng(4)
    eng = ServeEngine(model, params, max_batch=2, max_len=48,
                      config=EngineConfig(kv_blocks=2, kv_block_size=8))
    first = eng.submit(rng.integers(0, model.cfg.vocab_size, size=14),
                       max_new=2)           # needs both blocks
    second = eng.submit(rng.integers(0, model.cfg.vocab_size, size=8),
                        max_new=2)
    eng._admit()
    assert eng.active() == 1                # only the first fits
    assert eng.scheduler.depth == 1 and eng.backend.blocks.free == 0
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == [first, second]
    assert eng.backend.blocks.free == 2     # everything released


def test_request_larger_than_pool_is_rejected(small_lm):
    """Feasibility is judged on the FINAL footprint (prompt + max_new):
    both a too-big prompt and a short prompt that must GROW past the pool
    are rejected up front, with zero wasted decode steps."""
    model, params = small_lm
    rng = np.random.default_rng(5)
    eng = ServeEngine(model, params, max_batch=2, max_len=48,
                      config=EngineConfig(kv_blocks=2, kv_block_size=4))
    big = eng.submit(rng.integers(0, model.cfg.vocab_size, size=20),
                     max_new=2)             # needs 5 blocks, pool has 2
    grow = eng.submit(rng.integers(0, model.cfg.vocab_size, size=7),
                      max_new=6)            # 7+6-1 = 12 positions: 3 blocks
    ok = eng.submit(rng.integers(0, model.cfg.vocab_size, size=6), max_new=2)
    done = eng.run_until_drained()
    assert [r.rid for r in done] == [ok]
    assert sorted(r.rid for r in eng.scheduler.rejected) == [big, grow]
    assert eng.metrics_snapshot().rejected == 2
    assert eng.metrics_snapshot().preemptions == 0


def test_preempted_request_exempt_from_deadline_expiry():
    """A requeued preemption carries tokens a client is owed; the queue
    deadline (which bounds pre-admission wait) must not expire it."""
    from repro.serving.engine import Request
    from repro.serving.scheduler import AdmissionScheduler

    sched = AdmissionScheduler()
    fresh = Request(0, np.arange(4, dtype=np.int32), submitted_t=0.0,
                    deadline_s=1.0)
    resumed = Request(1, np.arange(4, dtype=np.int32), submitted_t=0.0,
                      deadline_s=1.0, admitted_t=0.5,
                      out_tokens=[7, 8])
    sched.push(fresh, 0.0)
    sched.requeue(resumed)
    popped = sched.pop(4, now=10.0)             # both deadlines long past
    assert [r.rid for r in popped] == [1]       # resumed survives
    assert [r.rid for r in sched.expired] == [0]


def test_running_lane_growth_outranks_admission(small_lm):
    """Growth of a running lane must be served before a new admission can
    take the last free blocks — otherwise the admission pays a prefill
    only to be the LIFO preemption victim in the same step."""
    model, params = small_lm
    rng = np.random.default_rng(10)
    eng = ServeEngine(model, params, max_batch=2, max_len=48,
                      config=EngineConfig(kv_blocks=3, kv_block_size=4))
    a = eng.submit(rng.integers(0, model.cfg.vocab_size, size=7), max_new=6)
    eng.step()                                  # A active on 2 blocks
    b = eng.submit(rng.integers(0, model.cfg.vocab_size, size=3), max_new=2)
    eng.step()              # A grows into the last block FIRST; B must wait
    assert eng.scheduler.depth == 1
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == [a, b]
    assert eng.metrics_snapshot().preemptions == 0


def test_watermark_infeasible_request_rejected_not_livelocked(small_lm):
    """A request whose prompt blocks exceed the watermark-reduced usable
    pool can NEVER pass can_admit; it must be rejected up front instead of
    requeueing forever and head-of-line-blocking later traffic."""
    model, params = small_lm
    rng = np.random.default_rng(9)
    eng = ServeEngine(model, params, max_batch=2, max_len=48,
                      config=EngineConfig(kv_blocks=4, kv_block_size=4,
                                          watermark_frac=0.3))
    big = eng.submit(rng.integers(0, model.cfg.vocab_size, size=14),
                     max_new=2)     # final 15 -> 4 blocks > usable 3
    ok = eng.submit(rng.integers(0, model.cfg.vocab_size, size=6), max_new=2)
    done = eng.run_until_drained(max_steps=200)
    assert [r.rid for r in done] == [ok]
    assert [r.rid for r in eng.scheduler.rejected] == [big]


def test_pad_id_is_inert_and_configurable(small_lm):
    """Bucketed prefill right-pads with EngineConfig.pad_id; causal masking
    makes the choice inert, so any pad id must give identical tokens."""
    model, params = small_lm
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=int(n))
               for n in (5, 9, 14)]
    base, _ = _run(model, params, prompts)
    other, _ = _run(model, params, prompts,
                    EngineConfig(pad_id=model.cfg.vocab_size - 1))
    assert base == other


def test_paged_config_on_recurrent_family_gets_recurrent_backend(small_lm):
    """Requesting paged KV for a non-pageable recurrent family no longer
    silently drops to dense lanes: it gets the pooled constant-footprint
    RecurrentBackend (and still serves correctly)."""
    from repro.serving.backends import RecurrentBackend

    cfg = reduced_config(get_config("rwkv6-1.6b"))
    model = build_model(cfg, RCFG)
    params = model.init(jax.random.key(1))
    eng = ServeEngine(model, params, max_batch=2, max_len=32,
                      config=EngineConfig(kv_blocks=16, kv_block_size=4))
    assert isinstance(eng.backend, RecurrentBackend)
    assert eng.backend.token_footprint(6, 3) == eng.backend.state_units > 0
    rng = np.random.default_rng(8)
    eng.submit(rng.integers(0, cfg.vocab_size, size=6), max_new=3)
    done = eng.run_until_drained()
    assert len(done) == 1 and len(done[0].out_tokens) == 3
