"""granitemoehybrid served through the normal path, against the plain
reference (``models/hybrid_ref.py``), at a CPU size: Mamba2 and NoPE
attention layers over one paged pool, the dropless held-share expert
layer, batched right-padded prefill, lane reuse, and what the pool
refuses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, reduced_config
from repro.models import hybrid_ref, moe
from repro.models.api import build_model, stage_model
from repro.serving.engine import EngineConfig, ServeEngine

RCFG = RunConfig(param_dtype="float32", compute_dtype="float32", remat=False)
# float32 end to end: the engine and the reference differ only by the
# order of float32 sums (chunked vs sequential scan, fused matmuls), about
# 1e-6 of the logits' scale; 1e-4 of it leaves room for that and is far
# below what bf16 compute gives (about 1e-2, see the last test)
LOGIT_TOL = 1e-4


def _cfg(**kw):
    cfg = reduced_config(get_config("granite-4.0-h-small"))
    # 8 routed experts, top-3, by default
    return dataclasses.replace(cfg, **{"n_experts": 8, "top_k": 3, **kw})


@pytest.fixture(scope="module")
def hybrid():
    cfg = _cfg()
    model = build_model(cfg, RCFG)
    return model, model.init(jax.random.key(3))


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(n)) for n in sizes]


def _record_logits(eng):
    """Wrap the engine's sampler: every row of logits it is handed, by the
    request that row samples for, in order."""
    rows = {}
    admitting = []
    admit = eng._admit_group

    def admit_group(items, slots, *a, **k):
        admitting[:] = [req for req, _ in items]
        try:
            return admit(items, slots, *a, **k)
        finally:
            admitting.clear()
    eng._admit_group = admit_group
    sample = eng.sampler.sample

    def sampled(logits, *a, **k):
        lg = np.asarray(logits)
        reqs = list(admitting) if admitting else eng.slots
        for i, req in enumerate(reqs):
            if req is not None:
                rows.setdefault(req.rid, []).append(lg[i])
        return sample(logits, *a, **k)
    eng.sampler.sample = sampled
    return rows


def _engine_vs_reference(model, params, config, prompts, max_new=6):
    cfg = model.cfg
    eng = ServeEngine(model, params, max_batch=3, max_len=48,
                      prefill_buckets=[16, 32, 48], max_prefill_batch=2,
                      config=config)
    rows = _record_logits(eng)
    for p in prompts:
        eng.submit(p, max_new=max_new)
    done = eng.run_until_drained()
    assert len(done) == len(prompts)
    worst = 0.0
    for req in done:
        seq = np.concatenate([req.prompt, np.asarray(req.out_tokens[:-1],
                                                     np.int32)])
        want = np.asarray(hybrid_ref.forward(cfg, params, seq))
        want = want[len(req.prompt) - 1:, :cfg.vocab_size]
        got = np.stack(rows[req.rid])
        assert got.shape == want.shape
        worst = max(worst, np.abs(got - want).max() / np.abs(want).max())
    return eng, worst


def test_engine_prefill_then_paged_decode_matches_reference(hybrid):
    model, params = hybrid
    eng, worst = _engine_vs_reference(
        model, params, EngineConfig(kv_blocks=40, kv_block_size=4),
        _prompts(model.cfg, (5, 13, 9, 20)))
    assert eng.backend.name == "paged"
    assert eng.metrics_snapshot().prefill_batch_mean > 1.0   # batched
    assert worst < LOGIT_TOL, worst


def test_engine_with_kernels_matches_reference(hybrid):
    """The flash, ``mamba2_ssd`` and paged kernels (interpret mode) in the
    engine's prefill and decode."""
    model, params = hybrid
    kmodel = build_model(model.cfg, dataclasses.replace(RCFG, use_kernels=True))
    _, worst = _engine_vs_reference(
        kmodel, params, EngineConfig(kv_blocks=40, kv_block_size=4),
        _prompts(model.cfg, (7, 16), seed=1), max_new=4)
    assert worst < LOGIT_TOL, worst


def test_recurrent_lanes_match_the_pool(hybrid):
    model, params = hybrid
    prompts = _prompts(model.cfg, (6, 11, 3), seed=2)
    outs = {}
    for name, config in {"paged": EngineConfig(kv_blocks=40, kv_block_size=4),
                         "recurrent": EngineConfig(backend="recurrent")}.items():
        eng = ServeEngine(model, params, max_batch=2, max_len=48,
                          prefill_buckets=[16, 48], config=config)
        for p in prompts:
            eng.submit(p, max_new=5)
        outs[name] = {r.rid: r.out_tokens for r in eng.run_until_drained()}
        assert eng.backend.name == name
    assert outs["paged"] == outs["recurrent"]


def _share_params(p, lo, hi, shared: bool):
    out = dict(p, wi=p["wi"][lo:hi], wg=p["wg"][lo:hi], wo=p["wo"][lo:hi])
    if not shared:
        out.pop("shared")
    return out


def test_eight_expert_shares_sum_to_the_uncut_layer():
    """Each of 8 chips holds 2 of 16 experts and routes over all 16; the
    partial outputs, with the shared expert counted once, add up to the
    layer that holds every expert."""
    cfg = _cfg(n_experts=16, top_k=4)
    p = moe.init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 7, cfg.d_model))
    whole, _ = moe.apply_moe_dropless(p, cfg, x)
    parts = []
    for s in range(8):
        share = dataclasses.replace(cfg, held_experts=(2 * s, 2 * s + 2))
        y, _ = moe.apply_moe_dropless(
            _share_params(p, 2 * s, 2 * s + 2, shared=s == 0), share, x)
        parts.append(y)
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5, rtol=1e-5)
    # and one share is the reference's held-share layer
    share = dataclasses.replace(cfg, held_experts=(4, 6))
    y, _ = moe.apply_moe_dropless(_share_params(p, 4, 6, True), share, x)
    want = hybrid_ref.moe(share, _share_params(p, 4, 6, True),
                          x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(y.reshape(-1, cfg.d_model), want, atol=1e-5,
                               rtol=1e-5)


def test_dropless_when_routing_forces_every_token_onto_one_expert():
    """A zero router ties every logit, so top-1 sends all 64 tokens to
    expert 0: each still gets its expert's output in full (the capacity
    layer of training drops most of them)."""
    cfg = _cfg(top_k=1, n_experts=4)
    p = moe.init_moe(jax.random.key(0), cfg, jnp.float32)
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.d_model))
    y, held = moe.apply_moe_dropless(p, cfg, x)
    xf = x.reshape(-1, cfg.d_model)
    want = hybrid_ref._swiglu(xf, p["wi"][0], p["wg"][0], p["wo"][0]) \
        + hybrid_ref._swiglu(xf, p["shared"]["wi"], p["shared"]["wg"],
                             p["shared"]["wo"])
    np.testing.assert_allclose(y.reshape(-1, cfg.d_model), want, atol=1e-5,
                               rtol=1e-5)
    assert int(held) == 64
    dropped, _ = moe.apply_moe(p, cfg, x, group_size=64)
    assert not np.allclose(dropped, y, atol=1e-3)


def test_dropless_layer_in_token_chunks_and_from_a_layer_stack(monkeypatch):
    """A long batch goes through in token chunks, and a stacked layer's
    experts are run as groups of the whole stack: neither changes the
    output or the held-slot count."""
    cfg = _cfg(held_experts=(2, 5))
    ps = jax.vmap(lambda k: moe.init_moe(k, cfg, jnp.float32))(
        jax.random.split(jax.random.key(0), 3))
    p1 = jax.tree.map(lambda a: a[1], ps)
    x = jax.random.normal(jax.random.key(1), (4, 24, cfg.d_model))
    mask = jnp.arange(4)[:, None] < jnp.asarray([[3]])
    mask = jnp.broadcast_to(mask, (4, 24))
    want, n_want = moe.apply_moe_dropless(p1, cfg, x, mask)
    monkeypatch.setattr(moe, "CHUNK_TOKENS", 16)
    got, n_got = moe.apply_moe_dropless(ps, cfg, x, mask, layer=1)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert int(n_got) == int(n_want) > 0


def test_dropless_layer_pads_a_token_count_no_chunk_divides(monkeypatch):
    """A prime token count goes through in few equal chunks, the last one
    padded (97 tokens in chunks of at most 16: 7 chunks of 14), not in 97
    one-token chunks; the pad rows change neither the output nor the
    held-slot count."""
    cfg = _cfg(held_experts=(2, 5))
    p = moe.init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 97, cfg.d_model))
    mask = (jnp.arange(97) % 3 > 0)[None]
    want, n_want = moe.apply_moe_dropless(p, cfg, x, mask)
    monkeypatch.setattr(moe, "CHUNK_TOKENS", 16)
    got, n_got = moe.apply_moe_dropless(p, cfg, x, mask)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert int(n_got) == int(n_want) > 0
    jpr = jax.make_jaxpr(lambda a: moe.apply_moe_dropless(p, cfg, a, mask))(x)
    lengths = [e.params["length"] for e in jpr.eqns
               if e.primitive.name == "scan"]
    assert lengths == [7]


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
def test_right_padded_prefill_equals_exact_length_prefill(hybrid, kernels):
    """Three prompts right-padded into one 24-wide batch give each prompt's
    exact-length logits, Mamba2 state, conv tail and K/V."""
    model, params = hybrid
    m = build_model(model.cfg, dataclasses.replace(RCFG, use_kernels=kernels))
    prompts = _prompts(model.cfg, (24, 5, 17), seed=4)
    toks = np.zeros((3, 24), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    lg, cache = m.decode_state.batched_prefill(params, {"tokens": toks},
                                               lens, 32)
    for i, p in enumerate(prompts):
        one, c1 = m.prefill(params, {"tokens": jnp.asarray(p[None])}, 32)
        np.testing.assert_allclose(lg[i], one[0], atol=1e-5, rtol=1e-4)
        for leaf in ("conv", "ssm"):
            np.testing.assert_allclose(cache["state"][leaf][:, i],
                                       c1["state"][leaf][:, 0],
                                       atol=1e-5, rtol=1e-4)
        n = len(p)
        np.testing.assert_allclose(cache["layers"]["k"][:, i, :n],
                                   c1["layers"]["k"][:, 0, :n], atol=1e-5)
        assert int(cache["pos"][i]) == n


def _alone(model, params, prompt, max_new):
    eng = ServeEngine(model, params, max_batch=1, max_len=48,
                      prefill_buckets=[16, 48],
                      config=EngineConfig(kv_blocks=20, kv_block_size=4))
    eng.submit(prompt, max_new=max_new)
    return eng.run_until_drained()[0].out_tokens


def test_a_lane_reused_after_release_or_preemption_matches_a_fresh_engine(
        hybrid):
    model, params = hybrid
    a, b = _prompts(model.cfg, (9, 14), seed=5)
    want = _alone(model, params, b, 8)
    eng = ServeEngine(model, params, max_batch=1, max_len=48,
                      prefill_buckets=[16, 48],
                      config=EngineConfig(kv_blocks=20, kv_block_size=4))
    eng.submit(a, max_new=5)
    eng.run_until_drained()                      # lane 0 released after a
    eng.submit(b, max_new=8)
    eng.step()
    eng.step()
    eng.step()
    eng.preempt(0)                               # state slot cleared
    got = eng.run_until_drained()
    assert got[-1].out_tokens == want
    snap = eng.metrics_snapshot()
    assert snap.preemptions == 1 and snap.resumes == 1
    st = eng.backend.cache["state"]
    assert not np.asarray(st["ssm"]).any()       # released: slot zeroed


def test_the_pool_refuses_what_recurrent_state_cannot_do(hybrid):
    model, params = hybrid
    with pytest.raises(ValueError, match="prefix_cache is refused.*recurrent"):
        ServeEngine(model, params, max_batch=2, max_len=48,
                    config=EngineConfig(kv_blocks=20, kv_block_size=4,
                                        prefix_cache=True))
    eng = ServeEngine(model, params, max_batch=2, max_len=48,
                      config=EngineConfig(kv_blocks=20, kv_block_size=4))
    eng.submit(_prompts(model.cfg, (6,))[0], max_new=4)
    eng.step()
    with pytest.raises(ValueError, match="speculative verify is refused"):
        eng.backend.append_tokens(0, [1, 2])
    with pytest.raises(ValueError, match="speculative verify is refused"):
        eng.backend.verify_step(params, np.zeros((2, 2), np.int32),
                                np.asarray([True, False]))
    with pytest.raises(ValueError, match="rollback is refused"):
        eng.backend.rollback(0, 1)
    with pytest.raises(ValueError, match="recurrent state"):
        stage_model(model, 0, 2)


def test_counters_and_named_scopes(hybrid):
    """``expert_load_mean`` and ``recurrent_state_bytes`` in the snapshot,
    and the ``mamba`` and ``moe`` scopes with their children in the decode
    step's and the batched prefill's operations."""
    model, params = hybrid
    cfg = model.cfg
    eng = ServeEngine(model, params, max_batch=2, max_len=48,
                      prefill_buckets=[16, 48],
                      config=EngineConfig(kv_blocks=40, kv_block_size=4))
    for p in _prompts(cfg, (5, 8)):
        eng.submit(p, max_new=6)
    eng.run_until_drained()
    snap = eng.metrics_snapshot()
    # every active token routes top_k=3 of 8 experts, all held here
    assert snap.expert_load_mean == pytest.approx(
        snap.busy_lanes_mean * 3 / 8, rel=0.35)
    assert snap.recurrent_state_bytes == pytest.approx(
        eng.backend.lane_state_bytes * snap.busy_lanes_mean)
    b = eng.backend
    texts = [
        b._decode.lower(params, b.cache, jnp.zeros((2, 1), jnp.int32),
                        jnp.asarray(b.block_tables)).compile().as_text(),
        eng._prefill_n.lower(params, jnp.zeros((2, 16), jnp.int32),
                             jnp.ones((2,), jnp.int32)).compile().as_text()]
    for text in texts:
        for path in ("mamba/in_proj", "mamba/conv", "mamba/ssm_state",
                     "mamba/out_proj", "moe/router", "moe/experts",
                     "moe/shared_expert"):
            assert f"/{path}/" in text, path


def test_build_engine_serves_the_hybrid_at_reduced_size():
    from repro.launch.serve import build_engine
    eng = build_engine("granite-4.0-h-small", max_batch=2, max_len=64,
                       kv_blocks=32)
    assert eng.backend.name == "paged"
    eng.submit(np.arange(7, dtype=np.int32), max_new=3)
    assert len(eng.run_until_drained()[0].out_tokens) == 3


def test_bf16_compute_of_the_reference_fails_the_logit_tolerance(hybrid):
    """The tolerance above is tight enough that the reference computed in
    bfloat16 falls outside it."""
    model, params = hybrid
    seq = _prompts(model.cfg, (12,), seed=6)[0]
    f32 = np.asarray(hybrid_ref.forward(model.cfg, params, seq))
    bf16 = np.asarray(hybrid_ref.forward(model.cfg, params, seq,
                                         dtype=jnp.bfloat16), np.float32)
    assert np.abs(bf16 - f32).max() / np.abs(f32).max() > 10 * LOGIT_TOL
