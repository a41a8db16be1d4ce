"""The paper's technique: pipeline parallelism over the "model" mesh axis,
implemented with manual `shard_map` + ring `lax.ppermute` boundary transfers.

Layout (DESIGN §3): the 16-way "model" axis hosts S pipeline stages × R = 16/S
replica columns (replicas are extra data parallelism *inside* the model axis —
the paper's single phone generalises to a column of devices).  Device
d ∈ [0,16): stage s = d % S, replica r = d // S.  Stage weights are stacked
(16, L/S, ...) with replica duplicates; stage boundaries move by ppermute on
the ring within each replica block; "data"/"pod" axes carry ordinary DP.

Schedules:

* ``gpipe``  — forward-only scan (M + S - 1 ticks); backward generated by AD
  (reverse-order scan with reversed ppermute) — GPipe semantics.
* ``hybrid`` — the paper's hybrid GPipe/1F1B (§3.5): ONE scan of M + 2S - 2
  ticks; the LAST stage runs a fused forward+loss+backward per microbatch
  (`jax.vjp` — exactly MPSGraph's fused static F+B graph, kept because it
  saves the last boundary stash and a tick); earlier stages interleave fwd of
  one microbatch with bwd of another (1F1B steady state), recomputing their
  chunk from the stashed stage input (remat).

MoE archs are excluded (their aux-loss needs cross-stage plumbing; they use
``gspmd_pp``) — asserted below.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.partition import PipelinePlan, plan_pipeline
from repro.launch.mesh import data_axes, dp_degree
from repro.models import blocks as B
from repro.models.api import build_model
from repro.models.common import chunked_xent, embed_tokens, rmsnorm
from repro.models.lm import head_weight, init_lm
from repro.optim import adamw


# ---------------------------------------------------------------------------
# param layout: model (L, ...) stacked blocks  <->  pipeline (16, L/S, ...)
# ---------------------------------------------------------------------------

def to_pipeline(params: dict, plan: PipelinePlan, model_axis: int) -> dict:
    """Pad + reshape + replicate the stacked blocks for the pipeline layout."""
    s, lps = plan.n_stages, plan.layers_per_stage

    def fix(a):
        pad = plan.slots - a.shape[0]
        if pad:
            a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
        a = a.reshape((s, lps) + a.shape[1:])
        idx = jnp.arange(model_axis) % s
        return jnp.take(a, idx, axis=0)              # (16, lps, ...)

    out = dict(params)
    out["blocks"] = jax.tree.map(fix, params["blocks"])
    return out


def from_pipeline(params_pp: dict, plan: PipelinePlan, n_layers: int) -> dict:
    """Inverse of :func:`to_pipeline` (for checkpoints / evaluation)."""
    s, lps = plan.n_stages, plan.layers_per_stage

    def unfix(a):
        a = a[:s].reshape((s * lps,) + a.shape[2:])
        return a[:n_layers]

    out = dict(params_pp)
    out["blocks"] = jax.tree.map(unfix, params_pp["blocks"])
    return out


# ---------------------------------------------------------------------------
# per-device stage chunk
# ---------------------------------------------------------------------------

def _chunk_fwd(cfg: ModelConfig, blocks_dev, shared, x, stage, lps: int,
               uk: bool, remat: bool):
    """Run this device's L/S layer slots (masked no-ops for pad slots)."""
    n_layers = cfg.n_layers

    def body(x, inp):
        bp, i = inp
        gidx = stage * lps + i

        def live(x):
            y, _aux = B.block_train(cfg, bp, x, gidx, uk)
            if shared is not None:
                y = B.shared_attn_train(cfg, shared, y, gidx, uk)
            return y

        x = jax.lax.cond(gidx < n_layers, live, lambda x: x, x)
        return x, None

    fn = jax.checkpoint(body, prevent_cse=False) if remat else body
    x, _ = jax.lax.scan(fn, x, (blocks_dev, jnp.arange(lps)))
    return x


# ---------------------------------------------------------------------------
# pipeline runtime
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Geom:
    S: int
    R: int
    lps: int
    M: int
    b_mb: int          # sequences per microbatch (per column)
    t_tok: int         # token positions per sequence
    p_front: int       # frontend prefix length (vlm)
    msize: int         # model axis size

    @property
    def b_col(self) -> int:
        return self.b_mb * self.M


def _perms(g: _Geom):
    fwd, bwd = [], []
    for d in range(g.msize):
        s = d % g.S
        fwd.append((d, d + 1 if s < g.S - 1 else d - (g.S - 1)))
        bwd.append((d, d - 1 if s > 0 else d + g.S - 1))
    return fwd, bwd


def _embed_mb(cfg, params, batch, mb, g: _Geom, r, cdt):
    """Slice replica-column microbatch ``mb`` and embed it. Returns (x, tokens_mb)."""
    tokens = batch["tokens"]                         # (b_local, T_tok)
    start = r * g.b_col + mb * g.b_mb
    tok_mb = jax.lax.dynamic_slice(tokens, (start, 0), (g.b_mb, tokens.shape[1]))
    x = embed_tokens(params["embed"], tok_mb, cdt)
    if g.p_front:
        fr = jax.lax.dynamic_slice(
            batch["frontend"], (start, 0, 0),
            (g.b_mb,) + batch["frontend"].shape[1:]).astype(cdt)
        x = jnp.concatenate([fr, x], axis=1)
    return x, tok_mb


def _head_loss(cfg, params, y, tok_mb, g: _Geom, cdt):
    """Final norm + chunked CE for one microbatch (mean over tokens)."""
    h = rmsnorm(params["final_ln"], y)
    w = head_weight(cfg, params, cdt)
    p = g.p_front
    t_tok = tok_mb.shape[1]
    if p:
        hh = h[:, p - 1 : p + t_tok - 1]
        labels = tok_mb
    else:
        hh = h[:, : t_tok - 1]
        labels = tok_mb[:, 1:]
    chunk = min(2048, hh.shape[1])
    while hh.shape[1] % chunk:
        chunk -= 1
    return chunked_xent(hh, w, labels, cfg.vocab_size, chunk=chunk)


def _pvary(x, axes):
    return jax.tree.map(lambda a: jax.lax.pcast(a, axes, to="varying"), x)


def _butterfly_psum(x, S: int, R: int, msize: int):
    """psum across the R replica columns (devices with equal stage)."""
    k = 1
    while k < R:
        perm = [(d, ((d // S) ^ k) * S + (d % S)) for d in range(msize)]
        other = jax.tree.map(
            lambda a: jax.lax.ppermute(a, "model", perm), x)
        x = jax.tree.map(jnp.add, x, other)
        k *= 2
    return x


def _split_blocks(tree):
    blocks = tree["blocks"]
    rest = {k: v for k, v in tree.items() if k != "blocks"}
    return blocks, rest


# ---------------------------------------------------------------------------
# GPipe loss (forward pipeline; AD produces the reverse sweep)
# ---------------------------------------------------------------------------

def make_pp_loss(cfg: ModelConfig, g: _Geom, rcfg: RunConfig, mesh,
                 batch_has_frontend: bool):
    cdt = jnp.dtype(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    fwd_perm, _ = _perms(g)
    daxes = data_axes(mesh)
    all_axes = tuple(daxes) + ("model",)
    ncols = g.R * dp_degree(mesh)

    def body(params, batch):
        d = jax.lax.axis_index("model")
        s, r = d % g.S, d // g.S
        shared = params.get("shared")
        t_total = g.p_front + g.t_tok
        buf0 = jnp.zeros((g.b_mb, t_total, cfg.d_model), cdt)
        buf0 = _pvary(buf0, all_axes)
        loss0 = _pvary(jnp.float32(0.0), all_axes)

        def tick(carry, t):
            buf, loss = carry
            mb_f = t - s
            fvalid = (mb_f >= 0) & (mb_f < g.M)
            mb_c = jnp.clip(mb_f, 0, g.M - 1)

            def inject(_):
                x, _tok = _embed_mb(cfg, params, batch, mb_c, g, r, cdt)
                return x

            x_in = jax.lax.cond(s == 0, inject, lambda b: b, buf)
            y = _chunk_fwd(cfg, jax.tree.map(lambda a: a[0], params["blocks"]),
                           shared, x_in, s, g.lps, uk, rcfg.remat)

            def last_loss(y):
                start = r * g.b_col + mb_c * g.b_mb
                tok_mb = jax.lax.dynamic_slice(
                    batch["tokens"], (start, 0), (g.b_mb, g.t_tok))
                return _head_loss(cfg, params, y, tok_mb, g, cdt)

            ce = jax.lax.cond((s == g.S - 1) & fvalid, last_loss,
                              lambda y: jnp.float32(0.0), y)
            loss = loss + ce / g.M
            y = jnp.where(fvalid, y, jnp.zeros_like(y))
            buf = jax.lax.ppermute(y, "model", fwd_perm)
            return (buf, loss), None

        (_, loss), _ = jax.lax.scan(tick, (buf0, loss0),
                                    jnp.arange(g.M + g.S - 1))
        loss = jax.lax.psum(loss, "model")
        for ax in daxes:
            loss = jax.lax.psum(loss, ax)
        return loss / ncols

    in_specs = (None, None)  # filled by caller
    return body


# ---------------------------------------------------------------------------
# Hybrid fused F+B (the paper's schedule) — returns (loss, grads) directly
# ---------------------------------------------------------------------------

def make_pp_hybrid_fb(cfg: ModelConfig, g: _Geom, rcfg: RunConfig, mesh):
    cdt = jnp.dtype(rcfg.compute_dtype)
    uk = rcfg.use_kernels
    fwd_perm, bwd_perm = _perms(g)
    daxes = data_axes(mesh)
    all_axes = tuple(daxes) + ("model",)
    ncols = g.R * dp_degree(mesh)
    t_total = g.p_front + g.t_tok

    def body(params, batch):
        d = jax.lax.axis_index("model")
        s, r = d % g.S, d // g.S
        shared = params.get("shared")
        blocks_dev = jax.tree.map(lambda a: a[0], params["blocks"])
        rest = {k: v for k, v in params.items() if k != "blocks"}

        # §Perf iteration B1: grad accumulators in bf16 when computing in
        # bf16 (the fp32 embed+head accumulators double-buffered through the
        # tick scan dominated peak memory); fp32 kept for fp32 runs (tests)
        acc_dt = jnp.float32 if cdt == jnp.float32 else cdt

        def chunk(bl, rst, x):
            sh = rst.get("shared")
            return _chunk_fwd(cfg, bl, sh, x, s, g.lps, uk, rcfg.remat)

        def fused_last(bl, rst, x, tok_mb):
            y = chunk(bl, rst, x)
            return _head_loss(cfg, {**rst, "blocks": None}, y, tok_mb, g, cdt) / g.M

        zeros_like_f32 = lambda t: jax.tree.map(
            lambda a: jnp.zeros(a.shape, acc_dt), t)
        gacc0 = {"blocks": zeros_like_f32(blocks_dev),
                 "rest": zeros_like_f32(rest)}
        stash0 = jnp.zeros((g.M, g.b_mb, t_total, cfg.d_model), cdt)
        buf0 = jnp.zeros((g.b_mb, t_total, cfg.d_model), cdt)
        carry0 = ( _pvary(stash0, all_axes), _pvary(gacc0, all_axes),
                   _pvary(buf0, all_axes), _pvary(buf0, all_axes),
                   _pvary(jnp.float32(0.0), all_axes) )

        n_ticks = g.M + 2 * g.S - 2

        def tick(carry, t):
            stash, gacc, fbuf, gbuf, loss = carry
            # ---------------- forward phase indices ----------------
            mb_f = t - s
            fvalid = (mb_f >= 0) & (mb_f < g.M)
            mb_fc = jnp.clip(mb_f, 0, g.M - 1)
            # ---------------- backward phase indices ----------------
            mb_b = t - (2 * g.S - 2 - s)
            bvalid = (mb_b >= 0) & (mb_b < g.M) & (s < g.S - 1)
            mb_bc = jnp.clip(mb_b, 0, g.M - 1)

            def inject(_):
                x, _ = _embed_mb(cfg, params, batch, mb_fc, g, r, cdt)
                return x

            x_in = jax.lax.cond(s == 0, inject, lambda b: b, fbuf)
            stash = jnp.where(
                fvalid,
                jax.lax.dynamic_update_index_in_dim(stash, x_in, mb_fc, 0),
                stash)

            start_f = r * g.b_col + mb_fc * g.b_mb
            tok_f = jax.lax.dynamic_slice(batch["tokens"], (start_f, 0),
                                          (g.b_mb, g.t_tok))
            start_b = r * g.b_col + mb_bc * g.b_mb
            tok_b = jax.lax.dynamic_slice(batch["tokens"], (start_b, 0),
                                          (g.b_mb, g.t_tok))

            def last_branch(op):
                stash, gacc, x_in, _gbuf = op
                (ce, (gbl, grst, gx)) = jax.value_and_grad(
                    fused_last, argnums=(0, 1, 2))(blocks_dev, rest, x_in, tok_f)
                mask = fvalid.astype(jnp.float32)
                gacc = {
                    "blocks": jax.tree.map(lambda a, b: a + (mask * b).astype(a.dtype),
                                           gacc["blocks"], gbl),
                    "rest": jax.tree.map(lambda a, b: a + (mask * b).astype(a.dtype),
                                         gacc["rest"], grst),
                }
                y_send = jnp.zeros_like(x_in)
                g_send = jnp.where(fvalid, gx.astype(cdt), jnp.zeros_like(x_in))
                return gacc, y_send, g_send, jnp.where(fvalid, ce, 0.0)

            def mid_branch(op):
                stash, gacc, x_in, gbuf = op
                y = chunk(blocks_dev, rest, x_in)
                y_send = jnp.where(fvalid, y, jnp.zeros_like(y))
                # backward: recompute vjp at the stashed input for mb_b
                x_b = stash[mb_bc]

                def fwd_embgrad(bl, rst, x):
                    return chunk(bl, rst, x)

                _, vjp = jax.vjp(fwd_embgrad, blocks_dev, rest, x_b)
                gbl, grst, gx = vjp(gbuf)
                mask = bvalid.astype(jnp.float32)
                gacc = {
                    "blocks": jax.tree.map(
                        lambda a, b: a + (mask * b).astype(a.dtype),
                        gacc["blocks"], gbl),
                    "rest": jax.tree.map(
                        lambda a, b: a + (mask * b).astype(a.dtype),
                        gacc["rest"], grst),
                }
                # stage 0: token-embedding gradient via scatter-add
                def emb_grad(gacc):
                    gemb = jnp.zeros(params["embed"]["tok"].shape, acc_dt)
                    gx_tok = gx[:, g.p_front:]
                    gemb = gemb.at[tok_b].add(gx_tok.astype(acc_dt))
                    gacc = dict(gacc)
                    rest_g = dict(gacc["rest"])
                    rest_g["embed"] = {
                        "tok": (rest_g["embed"]["tok"]
                                + mask * gemb).astype(acc_dt)}
                    gacc["rest"] = rest_g
                    return gacc

                gacc = jax.lax.cond(s == 0, emb_grad, lambda ga: ga, gacc)
                g_send = jnp.where(bvalid, gx.astype(cdt), jnp.zeros_like(gbuf))
                return gacc, y_send, g_send, jnp.float32(0.0)

            gacc, y_send, g_send, ce = jax.lax.cond(
                s == g.S - 1, last_branch, mid_branch,
                (stash, gacc, x_in, gbuf))
            loss = loss + ce
            fbuf = jax.lax.ppermute(y_send, "model", fwd_perm)
            gbuf = jax.lax.ppermute(g_send, "model", bwd_perm)
            return (stash, gacc, fbuf, gbuf, loss), None

        (stash, gacc, _, _, loss), _ = jax.lax.scan(
            tick, carry0, jnp.arange(n_ticks))

        # ----- reductions: loss + grads -----
        loss = jax.lax.psum(loss, "model")
        for ax in daxes:
            loss = jax.lax.psum(loss, ax)
        loss = loss / ncols

        gblocks = _butterfly_psum(gacc["blocks"], g.S, g.R, g.msize)
        grest = jax.lax.psum(gacc["rest"], "model")
        for ax in daxes:
            gblocks = jax.lax.psum(gblocks, ax)
            grest = jax.lax.psum(grest, ax)
        scale = 1.0 / ncols
        gblocks = jax.tree.map(lambda a: (a * scale)[None], gblocks)
        grest = jax.tree.map(lambda a: a * scale, grest)
        grads = dict(grest)
        grads["blocks"] = gblocks
        return loss, grads

    return body


# ---------------------------------------------------------------------------
# public builder
# ---------------------------------------------------------------------------

def geometry(cfg: ModelConfig, shape: ShapeConfig, rcfg: RunConfig,
             mesh) -> Tuple[PipelinePlan, _Geom]:
    assert cfg.n_experts == 0, "MoE archs use gspmd_pp (DESIGN §3)"
    msize = mesh.shape["model"]
    plan = plan_pipeline(cfg.n_layers, msize, rcfg.microbatches,
                         rcfg.schedule)
    dp = dp_degree(mesh)
    b_col = shape.global_batch // (dp * plan.replicas)
    assert b_col >= 1, (shape.global_batch, dp, plan.replicas)
    m = min(plan.n_micro, b_col)
    while b_col % m:
        m -= 1
    p_front = cfg.frontend_seq if cfg.frontend else 0
    t_tok = shape.seq_len - p_front
    g = _Geom(S=plan.n_stages, R=plan.replicas, lps=plan.layers_per_stage,
              M=m, b_mb=b_col // m, t_tok=t_tok, p_front=p_front, msize=msize)
    plan = dataclasses.replace(plan, n_micro=m)
    return plan, g


def make_pp_train_step(cfg: ModelConfig, shape: ShapeConfig, rcfg: RunConfig,
                       mesh, opt_cfg: Optional[adamw.AdamWConfig] = None):
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    plan, g = geometry(cfg, shape, rcfg, mesh)
    model = build_model(cfg, rcfg)
    pdt = jnp.dtype(rcfg.param_dtype)
    daxes = data_axes(mesh)

    # ---- shapes & shardings ----
    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    pp_shape = jax.eval_shape(
        functools.partial(to_pipeline, plan=plan, model_axis=g.msize),
        params_shape)
    opt_shape = jax.eval_shape(adamw.init, pp_shape)
    batch_specs = model.input_specs(shape)

    def pspec(tree, blocks_spec):
        def fn(path, leaf):
            p = "/".join(str(getattr(k, "key", getattr(k, "idx", ""))) for k in path)
            if p.startswith("blocks"):
                return NamedSharding(mesh, blocks_spec)
            return NamedSharding(mesh, P())
        return jax.tree_util.tree_map_with_path(fn, tree)

    p_shard = pspec(pp_shape, P("model"))

    def moment_shard(tree):
        """blocks moments follow the stage sharding; the REPLICATED params
        (embed/head/shared/final_ln) get their Adam moments ZeRO-sharded
        over "model" on dim 0 when divisible (vocab is padded to 256)."""
        def fn(path, leaf):
            pth = "/".join(str(getattr(k, "key", getattr(k, "idx", "")))
                           for k in path)
            if pth.startswith("blocks"):
                return NamedSharding(mesh, P("model"))
            if leaf.ndim >= 1 and leaf.shape[0] % mesh.shape["model"] == 0:
                return NamedSharding(mesh, P("model"))
            return NamedSharding(mesh, P())
        return jax.tree_util.tree_map_with_path(fn, tree)

    opt_shard = {"m": moment_shard(pp_shape),
                 "v": moment_shard(pp_shape),
                 "step": NamedSharding(mesh, P())}
    b_shard = jax.tree.map(
        lambda a: NamedSharding(mesh, P(daxes)) if np.ndim(a) else
        NamedSharding(mesh, P()), batch_specs)

    # shard_map in/out specs (PartitionSpecs)
    params_spec = jax.tree.map(
        lambda _: P(), pp_shape)
    params_spec = dict(params_spec)
    params_spec["blocks"] = jax.tree.map(lambda _: P("model"),
                                         pp_shape["blocks"])
    batch_spec_sm = jax.tree.map(lambda a: P(daxes) if np.ndim(a) else P(),
                                 batch_specs)

    grads_spec = dict(params_spec)

    if rcfg.schedule == "hybrid":
        fb = make_pp_hybrid_fb(cfg, g, rcfg, mesh)
        fb_sm = shard_map(fb, mesh=mesh,
                          in_specs=(params_spec, batch_spec_sm),
                          out_specs=(P(), grads_spec),
                          check_vma=False)

        def train_step(params, opt_state, batch):
            loss, grads = fb_sm(params, batch)
            new_p, new_o, stats = adamw.update(opt_cfg, grads, opt_state, params)
            return new_p, new_o, dict(loss=loss, **stats)
    else:
        loss_body = make_pp_loss(cfg, g, rcfg, mesh,
                                 batch_has_frontend="frontend" in batch_specs)

        # Differentiate INSIDE the shard_map body (like the hybrid schedule)
        # rather than transposing the shard_map itself: AD of the tick scan
        # produces the reverse pipeline sweep via transposed ppermutes, and
        # the replica/data-axis grad reductions are explicit psums — the one
        # pattern every jax version traces identically.
        def fbg(params, batch):
            loss, gacc = jax.value_and_grad(loss_body)(params, batch)
            # per-device grads: blocks hold this stage/column's contribution
            # (scaled 1/ncols by the loss mean), rest is nonzero only where
            # the owning stage touched it -> sum columns, then data replicas
            gblocks = _butterfly_psum(gacc["blocks"], g.S, g.R, g.msize)
            grest = {k: v for k, v in gacc.items() if k != "blocks"}
            grest = jax.lax.psum(grest, "model")
            for ax in daxes:
                gblocks = jax.lax.psum(gblocks, ax)
                grest = jax.lax.psum(grest, ax)
            grads = dict(grest)
            grads["blocks"] = gblocks
            return loss, grads

        fbg_sm = shard_map(fbg, mesh=mesh,
                           in_specs=(params_spec, batch_spec_sm),
                           out_specs=(P(), grads_spec), check_vma=False)

        def train_step(params, opt_state, batch):
            loss, grads = fbg_sm(params, batch)
            new_p, new_o, stats = adamw.update(opt_cfg, grads, opt_state, params)
            return new_p, new_o, dict(loss=loss, **stats)

    metrics_shape = jax.eval_shape(train_step, pp_shape, opt_shape,
                                   batch_specs)[2]
    out_shardings = (p_shard, opt_shard,
                     jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                  metrics_shape))
    return dict(
        fn=train_step,
        args=(pp_shape, opt_shape, batch_specs),
        in_shardings=(p_shard, opt_shard, b_shard),
        out_shardings=out_shardings,
        donate_argnums=(0, 1),
        meta={"strategy": "pp_shardmap", "schedule": rcfg.schedule,
              "S": g.S, "R": g.R, "M": g.M, "layers_per_stage": g.lps,
              "n_pad_layers": plan.n_pad,
              "ticks": plan.ticks(),
              "layers_multiplier": g.lps,
              "tick_multiplier": g.M + (g.S - 1) if rcfg.schedule == "gpipe"
                                 else g.M + 2 * g.S - 2,
              # ppermutes/psums live in the TICK loop only (not the inner
              # layer loop): collectives scale by ticks alone
              "wire_multiplier": (g.M + (g.S - 1)) * (2 if rcfg.schedule == "gpipe" else 1)
                                 if rcfg.schedule == "gpipe"
                                 else g.M + 2 * g.S - 2},
        model=model,
        plan=plan,
        to_pipeline=functools.partial(to_pipeline, plan=plan, model_axis=g.msize),
        from_pipeline=functools.partial(from_pipeline, plan=plan,
                                        n_layers=cfg.n_layers),
    )
