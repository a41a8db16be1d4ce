"""Model protocol: one object per (arch, run) pair with uniform entry points.

    model = build_model(cfg, rcfg)
    params = model.init(key)
    loss, metrics = model.loss(params, batch)
    logits, cache = model.prefill(params, batch, max_len)
    logits, cache = model.decode_step(params, cache, tokens)
    specs = model.input_specs(shape)

Decode-state capabilities live in ONE structured descriptor,
``model.decode_state`` (a :class:`DecodeState`), consumed exclusively by
the serving cache backends (:mod:`repro.serving.backends`).  The engine
never inspects it — it talks to a ``CacheBackend`` built from it — and
eligibility (which family may use which state layout) is decided HERE,
once, instead of being re-derived per call site.

MIGRATION (old optional hooks -> backend methods)
-------------------------------------------------
Earlier revisions grew one ``Optional[Callable]`` per capability on
``Model``; each is now a ``DecodeState`` field feeding a backend method:

* ``model.prefill_ragged(...)``     -> ``decode_state.batched_prefill``;
  callers go through the engine's bucketed prefill, which pastes into the
  active backend via ``CacheBackend.prefill_paste``.
* ``model.init_paged_cache(...)``   -> ``decode_state.pool_init``; only
  ``PagedBackend`` calls it (``CacheBackend.alloc`` is the public verb).
* ``model.decode_step_paged(...)``  -> ``decode_state.pool_step``; only
  ``PagedBackend`` calls it (``CacheBackend.step`` is the public verb).

Code that previously probed ``model.<hook> is not None`` should either
ask ``model.decode_state`` (capability checks) or, better, build a
backend with :func:`repro.serving.backends.make_backend` and use the
protocol.  ``DecodeState.kind`` routes recurrent-state families
(ssm / rwkv / hybrid) to the pooled constant-footprint
``RecurrentBackend`` instead of exiling them to dense lanes.

Families: decoder-only (dense/moe/ssm/hybrid/vlm) -> repro.models.lm;
enc-dec (audio/whisper) -> repro.models.encdec.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.models import encdec as ED
from repro.models import hybrid as HY
from repro.models import lm as LM


@dataclasses.dataclass(frozen=True)
class DecodeState:
    """How this model's decode state may be laid out and advanced.

    ``kind`` is the state taxonomy the backend factory dispatches on:

    * ``"attention"`` — per-layer state is (or includes only) a
      position-addressed K/V cache; dense lanes always work, and the
      pooled (paged) layout works when ``pool_step`` is wired.
    * ``"recurrent"`` — ssm / rwkv / hybrid: constant-size per-lane state
      (conv tail, ssm state, rwkv matrix state, plus the hybrid shared
      attention span).  Not position-pageable, but cheap to snapshot and
      restore, which the ``RecurrentBackend`` exploits for
      constant-footprint preemption.
    * ``"encdec"`` — cross-attention caches keyed to an encoder pass;
      dense lanes only.

    The callables are INTERNAL plumbing for the serving backends; nothing
    else should invoke them (see the module docstring's migration note).
    ``batched_prefill(params, batch, lengths, max_len)`` is only set when
    right-padding is provably inert; ``pool_init(n_lanes, n_blocks,
    block_size)`` / ``pool_step(params, cache, tokens, block_tables)``
    only where a block pool is exact.

    ``window_step(params, cache, tokens (B, W))`` (and its pooled twin
    ``pool_window_step``) runs W sequential decode steps in one dispatch,
    returning per-position logits (B, W, Vp) — the speculative-decoding
    verify entry point.  It is always a scan of the single-step body, so
    its outputs are bitwise identical to W separate ``decode_step``
    calls (see :func:`repro.models.lm.lm_decode_window`).
    """

    kind: str
    batched_prefill: Optional[
        Callable[[dict, Dict[str, jax.Array], jax.Array, int],
                 Tuple[jax.Array, dict]]] = None
    pool_init: Optional[Callable[[int, int, int], dict]] = None
    pool_step: Optional[
        Callable[[dict, dict, jax.Array, jax.Array],
                 Tuple[jax.Array, dict]]] = None
    window_step: Optional[
        Callable[[dict, dict, jax.Array], Tuple[jax.Array, dict]]] = None
    pool_window_step: Optional[
        Callable[[dict, dict, jax.Array, jax.Array],
                 Tuple[jax.Array, dict]]] = None

    @property
    def poolable(self) -> bool:
        return self.pool_step is not None


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    rcfg: RunConfig
    init: Callable[[jax.Array], dict]
    loss: Callable[[dict, Dict[str, jax.Array]], Tuple[jax.Array, dict]]
    prefill: Callable[[dict, Dict[str, jax.Array], int], Tuple[jax.Array, dict]]
    decode_step: Callable[[dict, dict, jax.Array], Tuple[jax.Array, dict]]
    init_cache: Callable[[int, int], dict]
    input_specs: Callable[[ShapeConfig], Dict[str, Any]]
    decode_state: DecodeState = DecodeState(kind="attention")


def init_params(model: Model, seed: int = 0) -> dict:
    """``model.init`` under jit.  Eagerly, each stacked weight is drawn in
    float32 and then cast (``models.common.truncated_normal``): at
    granite-8b width that is a 5.6 GB transient for the MLP stack of 24
    layers.  Under jit XLA fuses the draw into the cast, so no float32
    copy of a full-width weight exists."""
    # repro-lint: allow[R001] one init program per model, run once per process
    return jax.jit(model.init)(jax.random.key(seed))


def _window_from_step(step: Callable) -> Callable:
    """Lift a single-token ``step(params, cache, (B,1))`` into a W-token
    window via ``lax.scan`` — bitwise identical to W separate steps (the
    scan body IS the step program; see :func:`repro.models.lm.lm_decode_window`)."""

    def window(params, cache, tokens):
        def body(c, tok):
            lg, c = step(params, c, tok)
            return c, lg

        cache, lgs = jax.lax.scan(
            body, cache, jnp.moveaxis(tokens, 1, 0)[:, :, None])
        return jnp.moveaxis(lgs, 0, 1), cache

    return window


def build_model(cfg: ModelConfig, rcfg: RunConfig) -> Model:
    pdt = jnp.dtype(rcfg.param_dtype)
    cdt = jnp.dtype(rcfg.compute_dtype)
    if cfg.family == "audio" and cfg.n_enc_layers:
        ed_step = lambda p, c, t: ED.encdec_decode_step(cfg, p, c, t, rcfg)
        return Model(
            cfg=cfg, rcfg=rcfg,
            init=lambda key: ED.init_encdec(cfg, key, pdt),
            loss=lambda p, b: ED.encdec_loss(cfg, p, b, rcfg),
            prefill=lambda p, b, ml: ED.encdec_prefill(cfg, p, b, rcfg, ml),
            decode_step=ed_step,
            init_cache=lambda bsz, ml: ED.init_encdec_cache(cfg, bsz, ml, cdt),
            input_specs=lambda s: ED.encdec_input_specs(cfg, s, rcfg),
            decode_state=DecodeState(kind="encdec",
                                     window_step=_window_from_step(ed_step)),
        )
    if cfg.layer_types:
        return _build_hybrid(cfg, rcfg)
    # right-padded batched prefill is exact only when pad tokens cannot leak
    # into real lanes: full causal attention, no recurrent state, no frontend.
    # MoE is excluded too — pad tokens compete for (and resize) expert
    # capacity, perturbing real tokens' routing vs an exact-length prefill.
    ragged_ok = (cfg.family == "dense" and not cfg.rwkv
                 and cfg.attention == "full" and not cfg.frontend
                 and not cfg.n_enc_layers)
    # a block pool is exact wherever the per-layer decode state is a pure
    # attention K/V cache addressed by position: dense and moe (routing is
    # per-token at decode, so paging cannot perturb it).  Recurrent state
    # (ssm/rwkv/hybrid) and enc-dec cross caches are not position-pageable;
    # chunked_local's ring-buffer addressing is dense-span specific.
    pool_ok = (cfg.family in ("dense", "moe") and not cfg.rwkv
               and cfg.attention == "full" and not cfg.n_enc_layers)
    recurrent = cfg.rwkv or cfg.family in ("ssm", "hybrid")
    return Model(
        cfg=cfg, rcfg=rcfg,
        init=lambda key: LM.init_lm(cfg, key, pdt),
        loss=lambda p, b: LM.lm_loss(cfg, p, b, rcfg),
        prefill=lambda p, b, ml: LM.lm_prefill(cfg, p, b, rcfg, ml),
        decode_step=lambda p, c, t: LM.lm_decode_step(cfg, p, c, t, rcfg),
        init_cache=lambda bsz, ml: LM.init_cache(cfg, bsz, ml, cdt),
        input_specs=lambda s: LM.input_specs(cfg, s, rcfg),
        decode_state=DecodeState(
            kind="recurrent" if recurrent else "attention",
            batched_prefill=(
                (lambda p, b, ln, ml: LM.lm_prefill_padded(cfg, p, b, ln, rcfg, ml))
                if ragged_ok else None),
            pool_init=(
                (lambda nl, nb, bs: LM.init_pool_cache(cfg, nl, nb, bs, cdt))
                if pool_ok else None),
            pool_step=(
                (lambda p, c, t, bt: LM.lm_decode_step_pool(cfg, p, c, t, bt, rcfg))
                if pool_ok else None),
            window_step=lambda p, c, t: LM.lm_decode_window(cfg, p, c, t, rcfg),
            pool_window_step=(
                (lambda p, c, t, bt: LM.lm_decode_window_pool(
                    cfg, p, c, t, bt, rcfg))
                if pool_ok else None),
        ),
    )


def _build_hybrid(cfg: ModelConfig, rcfg: RunConfig) -> Model:
    """granitemoehybrid (per-layer Mamba2 / attention mixers, each with its
    own expert FFN; :mod:`repro.models.hybrid`).  Its state is recurrent,
    so the dense-lane layout goes to the ``RecurrentBackend``; the pool
    keeps the attention K/V in blocks and one Mamba2 state slot per lane.
    Right-padded batched prefill is exact for it (pads carry dt = 0 and
    the expert layer is dropless).  No pooled verify window: the
    speculative rollback cannot retreat recurrent state in a pool."""
    pdt = jnp.dtype(rcfg.param_dtype)
    cdt = jnp.dtype(rcfg.compute_dtype)
    step = lambda p, c, t: HY.hybrid_decode_step(cfg, p, c, t, rcfg)
    return Model(
        cfg=cfg, rcfg=rcfg,
        init=lambda key: HY.init_hybrid(cfg, key, pdt),
        loss=lambda p, b: HY.hybrid_loss(cfg, p, b, rcfg),
        prefill=lambda p, b, ml: HY.hybrid_prefill(cfg, p, b, rcfg, ml),
        decode_step=step,
        init_cache=lambda bsz, ml: HY.init_cache(cfg, bsz, ml, cdt),
        input_specs=lambda s: HY.input_specs(cfg, s, rcfg),
        decode_state=DecodeState(
            kind="recurrent",
            batched_prefill=lambda p, b, ln, ml: HY.hybrid_prefill(
                cfg, p, b, rcfg, ml, lengths=ln),
            pool_init=lambda nl, nb, bs: HY.init_pool_cache(cfg, nl, nb, bs,
                                                            cdt),
            pool_step=lambda p, c, t, bt: HY.hybrid_decode_step_pool(
                cfg, p, c, t, bt, rcfg),
            window_step=_window_from_step(step),
        ),
    )


# ---------------------------------------------------------------------------
# layer-range stage models (pipeline-split serving, paper §4.1 topology)
# ---------------------------------------------------------------------------

def stage_eligible(cfg: ModelConfig) -> bool:
    """Can this family's layers be cut into self-contained stages?

    A stage is exact iff nothing couples layers across the cut: dense and
    moe qualify (per-layer attention KV + per-token routing); excluded are
    rwkv/ssm/hybrid (the zamba2 SHARED attention block fires across the
    whole depth; recurrent state would work layer-wise but the serving
    backends treat it whole), enc-dec (cross-attention keyed to one
    encoder pass) and frontend configs (the embedding concat is a
    first-stage-only input the stage protocol doesn't carry)."""
    return (cfg.family in ("dense", "moe") and not cfg.rwkv
            and not cfg.n_enc_layers and not cfg.frontend)


def _stage_stub(what: str):
    def stub(*_a, **_k):
        raise RuntimeError(
            f"stage models hold one layer slice of a split model; {what} "
            f"belongs to the full model (build_model)")
    return stub


@functools.lru_cache(maxsize=128)
def stage_model(model: Model, lo: int, hi: int) -> Model:
    """A Model executing only layers [lo, hi) of ``model``.

    Its ``prefill`` takes ``{"tokens"}`` on the first stage and
    ``{"hidden"}`` (the previous stage's boundary activations) otherwise;
    its ``decode_step`` input is tokens (B, 1) or hidden (B, 1, D) the
    same way.  Non-last stages OUTPUT the boundary hidden instead of
    logits.  Params are the slice produced by :func:`split_stage_params`.

    ``init_cache`` covers exactly the slice's layers, so a serving
    :class:`~repro.serving.backends.CacheBackend` instantiates per stage
    over the layer range — stage 0 owns the low-layer KV, stage 1 the
    rest.  Cached (lru) so every engine serving the same cut shares one
    Model object and therefore one set of jitted programs.
    """
    cfg, rcfg = model.cfg, model.rcfg
    if not stage_eligible(cfg):
        why = ("its recurrent state (Mamba2 / RWKV) lives in the serving "
               "pool as one slot per lane, which the stage protocol does not "
               "carry across a cut" if cfg.rwkv or cfg.family in ("ssm",
                                                                  "hybrid")
               else "its layers are not self-contained")
        raise ValueError(
            f"family {cfg.family!r} (rwkv={cfg.rwkv}) cannot be layer-split "
            f"into serving stages: {why}")
    if not (0 <= lo < hi <= cfg.n_layers):
        raise ValueError(f"bad stage range [{lo}, {hi}) for "
                         f"{cfg.n_layers} layers")
    first, last = lo == 0, hi == cfg.n_layers
    scfg = dataclasses.replace(cfg, n_layers=hi - lo)
    cdt = jnp.dtype(rcfg.compute_dtype)
    return Model(
        cfg=scfg, rcfg=rcfg,
        init=_stage_stub("init"),
        loss=_stage_stub("loss"),
        prefill=lambda p, b, ml: LM.lm_stage_prefill(
            scfg, p, b, rcfg, ml, first=first, last=last),
        decode_step=lambda p, c, t: LM.lm_stage_decode_step(
            scfg, p, c, t, rcfg, first=first, last=last),
        init_cache=lambda bsz, ml: LM.init_cache(scfg, bsz, ml, cdt),
        input_specs=_stage_stub("input_specs"),
        decode_state=DecodeState(kind="attention"),
    )


def split_stage_params(model: Model, params: dict,
                       cuts: Sequence[int]) -> List[dict]:
    """Slice a full param tree into per-stage trees for ``cuts``.

    Stage i holds ``blocks[bounds[i]:bounds[i+1]]``; the first stage adds
    the embedding table, the last adds the final norm and the head — for
    tied embeddings the last stage carries its own copy of the embedding
    (a real deployment ships the table to both ends of the wire, which is
    exactly the honest memory accounting).  The slices are materialised
    (not views), so callers may drop the full ``params`` afterwards —
    that is the memory-wall point of the split."""
    n = model.cfg.n_layers
    bounds = (0,) + tuple(cuts) + (n,)
    if list(bounds) != sorted(set(bounds)):
        raise ValueError(f"cuts {cuts!r} not strictly increasing in (0, {n})")
    out: List[dict] = []
    for i in range(len(bounds) - 1):
        lo, hi = bounds[i], bounds[i + 1]
        p = {"blocks": jax.tree.map(lambda a: a[lo:hi], params["blocks"])}
        if i == 0:
            p["embed"] = params["embed"]
        if hi == n:
            p["final_ln"] = params["final_ln"]
            if model.cfg.tie_embeddings:
                p.setdefault("embed", params["embed"])
            else:
                p["head"] = params["head"]
        out.append(p)
    return out


def param_bytes(tree: Any) -> int:
    """Total bytes of a param (sub)tree — stage memory accounting."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(tree)))
