"""Cache backends: every decode-state layout behind ONE protocol.

The serving engine holds exactly one :class:`CacheBackend` and speaks only
its verbs — it has no idea whether lanes are dense ``max_len`` strips, a
shared block pool, or pooled recurrent state.  Model-side capabilities
come from ``model.decode_state`` (:class:`repro.models.api.DecodeState`);
eligibility is decided there once and realised here once, so adding a new
layout (quantized KV, host offload, sharded multi-device cache) means one
new subclass, not another optional hook + engine branch.

Protocol (one backend instance per engine; ``slot`` is a lane index):

* ``token_footprint(n_ctx, max_new, tokens)`` — admission charge, in the
  backend's capacity units (cache positions for attention layouts, state
  units for recurrent ones).  Prefix-cache aware for the paged layout.
* ``alloc(n_ctx, final_len, tokens)`` — reserve capacity for one request:
  a :class:`Reservation` on success, ``None`` when it cannot fit *now*
  (spill back to the queue), or :data:`INFEASIBLE` when it can never fit
  (reject up front instead of livelocking).
* ``prefill_paste(slot, group_cache, src_lane, n_ctx, width, res)`` —
  scatter one lane of a (possibly right-padded, batched) prefill cache
  into the backend's storage for ``slot``.
* ``activate(slot, res)`` — install a FULL-HIT reservation without any
  prefill: every needed K/V position is already cached, so the lane
  starts directly in decode (TTFT skips the prefill entirely).
* ``prepare_lane(slot)`` — make the lane's next write position safe
  before a decode step: grow into a fresh block, COW-split a shared one,
  or uncache a sole-holder cached one.  ``False`` = out of memory, the
  engine must preempt a victim and retry.
* ``step(params, tokens, active)`` — advance every lane one token.
* ``append_tokens(slot, toks)`` / ``verify_step(params, tokens, active)``
  / ``rollback(slot, n)`` — the speculative-decoding verify plumbing:
  reserve write capacity for ``len(toks)`` consecutive positions (paged
  grows / COW-splits per position; ``False`` = pool exhausted), advance
  every lane W tokens in one scanned dispatch returning per-position
  logits (B, W, Vp), then truncate the last ``n`` of a lane's writes
  after partial acceptance (dense/paged retreat the position; recurrent
  state is not position-addressed, so the backend replays the kept
  prefix of the verify window from a host-side stash).
* ``reset_lane(slot)`` — return a lane to the empty-stream state (the
  draft side of a speculative pair admits 1-token prompts with nothing
  to prefill).
* ``snapshot(slot)`` / ``restore(slot, snap)`` — preemption support:
  backends with cheap constant-size state return it host-side so a
  preempted request resumes WITHOUT recompute; ``None`` means the
  recompute (re-prefill) policy applies.
* ``release(slot, tokens)`` — free the lane; paged registers the token
  content actually written so future prompts can prefix-match it.

Implementations:

* :class:`DenseBackend` — one ``max_len``-wide lane per slot (the
  original layout; admission is bound by lane count).
* :class:`PagedBackend` — block-pooled KV with refcounted
  copy-on-write prefix caching over :class:`BlockManager`.
* :class:`RecurrentBackend` — ssm / rwkv / hybrid: a pool of
  constant-footprint state lanes (admission charged in state units, not
  fictitious ``max_len`` tokens) with snapshot/restore preemption.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.serving.block_manager import BlockManager

# alloc() verdict: the request can NEVER fit (final footprint exceeds the
# pool or the lane span) — reject up front, don't requeue forever.
INFEASIBLE = object()


@dataclasses.dataclass
class Reservation:
    """Capacity reserved by ``alloc`` for one admission.

    ``blocks`` / ``n_cached`` are paged-layout details (empty elsewhere);
    ``full_hit`` marks a reservation whose every context position short of
    the last is already cached — the engine skips prefill and calls
    ``activate``.  ``n_lookup`` is the token count of the prefix-cache
    query (0 = no lookup happened) for hit-rate accounting.
    """

    blocks: List[int] = dataclasses.field(default_factory=list)
    n_cached: int = 0
    n_lookup: int = 0
    full_hit: bool = False


def _lane_axes(model: Model, n_lanes: int, max_len: int):
    """Locate each cache leaf's lane axis ONCE by diffing the shapes of two
    abstract caches that differ only in batch (-1 = no lane axis)."""
    s_a = jax.eval_shape(lambda: model.init_cache(n_lanes, max_len))
    s_b = jax.eval_shape(lambda: model.init_cache(n_lanes + 1, max_len))

    def lane_axis(a, b):
        for ax, (da, db) in enumerate(zip(a.shape, b.shape)):
            if da != db:
                return ax
        return -1

    return jax.tree.map(lane_axis, s_a, s_b), s_a


# jax.jit caches are PER WRAPPER OBJECT, and a fleet builds one engine per
# worker from the same model — per-instance wrappers would re-trace and
# re-compile identical programs once per worker (and once per reference
# engine in benches).  These caches share one wrapper per key; Model /
# DecodeState are frozen and hashable, and hold no params, so keeping them
# alive in the cache is cheap.
@functools.lru_cache(maxsize=64)
def _lane_tools(model: Model, n_lanes: int, max_len: int):
    """Lane-axis map, abstract cache shapes, and the jitted lane
    paste / extract shared by dense-layout backends of one
    (model, n_lanes, max_len)."""
    lane_ax, shapes = _lane_axes(model, n_lanes, max_len)

    def paste(cache, src_cache, src_lane, dst_slot):
        """Copy lane ``src_lane`` of a prefill cache into decode lane
        ``dst_slot``.  Lane indices are traced, so every admission
        reuses one compile per source-batch shape."""
        def fix(ax, dst, src):
            if ax < 0:
                return dst
            piece = jax.lax.dynamic_index_in_dim(src, src_lane, axis=ax,
                                                 keepdims=True)
            idx = tuple(dst_slot if i == ax else 0
                        for i in range(dst.ndim))
            return jax.lax.dynamic_update_slice(
                dst, piece.astype(dst.dtype), idx)
        return jax.tree.map(fix, lane_ax, cache, src_cache)

    def extract(cache, slot):
        def fix(ax, leaf):
            if ax < 0:
                return leaf
            return jax.lax.dynamic_index_in_dim(leaf, slot, axis=ax,
                                                keepdims=True)
        return jax.tree.map(fix, lane_ax, cache)

    return (lane_ax, shapes, jax.jit(paste, donate_argnums=0),
            jax.jit(extract))


@functools.lru_cache(maxsize=64)
def _decode_jit(model: Model):
    return jax.jit(model.decode_step, donate_argnums=1)


@functools.lru_cache(maxsize=64)
def _pool_step_jit(decode_state):
    # a named function: a profile shows the program as jit_decode_pool_step
    def decode_pool_step(params, cache, tokens, block_tables):
        return decode_state.pool_step(params, cache, tokens, block_tables)
    return jax.jit(decode_pool_step, donate_argnums=1)


@functools.lru_cache(maxsize=64)
def _window_jit(model: Model, donate: bool):
    """Jitted W-token verify window.  ``donate=False`` for the recurrent
    backend, whose rollback replays from a stashed pre-window cache that
    donation would invalidate."""
    ws = model.decode_state.window_step
    if ws is None:
        raise ValueError(
            f"model family {model.cfg.family!r} wires no window_step; "
            f"speculative verify is unavailable on it")
    return jax.jit(ws, donate_argnums=1) if donate else jax.jit(ws)


@functools.lru_cache(maxsize=64)
def _pool_window_jit(decode_state):
    return jax.jit(decode_state.pool_window_step, donate_argnums=1)


def _dense_add_pos(cache, slot, delta):
    return {**cache, "pos": cache["pos"].at[slot].add(delta)}


def _dense_set_pos(cache, slot, val):
    return {**cache, "pos": cache["pos"].at[slot].set(val)}


_DENSE_ADD_POS = jax.jit(_dense_add_pos, donate_argnums=0)
_DENSE_SET_POS = jax.jit(_dense_set_pos, donate_argnums=0)


def _pool_paste(cache, src, src_lane, flat_idx, dst_slot, length):
    """Scatter lane ``src_lane`` of a prefill cache into a lane's
    allocated pool blocks.  ``flat_idx`` (width,) maps prefill positions
    to flattened pool slots; positions past the real context — and
    positions already covered by SHARED cache blocks, which must never be
    rewritten — point at the sink.  A pool with per-lane recurrent state
    (``cache["state"]``, lane axis second) gets the lane's state copied
    into slot ``dst_slot`` whole."""
    src_layers = src["layers"]

    def fix(pool, src):
        nl = pool.shape[0]
        flat = pool.reshape((nl, -1) + pool.shape[3:])
        piece = jax.lax.dynamic_index_in_dim(
            src, src_lane, axis=1, keepdims=False)
        piece = jax.lax.slice_in_dim(
            piece, 0, flat_idx.shape[0], axis=1)
        flat = flat.at[:, flat_idx].set(piece.astype(flat.dtype))
        return flat.reshape(pool.shape)
    layers = {"k": fix(cache["layers"]["k"], src_layers["k"]),
              "v": fix(cache["layers"]["v"], src_layers["v"])}
    out = {**cache, "layers": layers,
           "pos": cache["pos"].at[dst_slot].set(length)}
    if "state" in cache:
        def lane(dst, s):
            piece = jax.lax.dynamic_index_in_dim(s, src_lane, 1, True)
            return jax.lax.dynamic_update_index_in_dim(
                dst, piece.astype(dst.dtype), dst_slot, 1)
        out["state"] = jax.tree.map(lane, cache["state"], src["state"])
    return out


def _pool_set_pos(cache, slot, val):
    return {**cache, "pos": cache["pos"].at[slot].set(val)}


def _pool_cow_copy(cache, src, dst):
    """Duplicate one pool block (all layers, K and V) dst <- src."""
    def fix(pool):
        return pool.at[:, dst].set(pool[:, src])
    return {**cache, "layers": {"k": fix(cache["layers"]["k"]),
                                "v": fix(cache["layers"]["v"])}}


def _pool_clear_lane(cache, slot):
    """Zero one lane's recurrent state slot (release / preemption)."""
    return {**cache, "state": jax.tree.map(
        lambda a: a.at[:, slot].set(0), cache["state"])}


# pool-layout helpers are model-independent pure functions: one wrapper
# per process (recompiles per pool shape happen inside jit as usual)
_POOL_PASTE = jax.jit(_pool_paste, donate_argnums=0)
_POOL_SET_POS = jax.jit(_pool_set_pos, donate_argnums=0)
_POOL_COW_COPY = jax.jit(_pool_cow_copy, donate_argnums=0)
_POOL_CLEAR_LANE = jax.jit(_pool_clear_lane, donate_argnums=0)


class CacheBackend:
    """Base class: the dense-lane defaults every layout can fall back on."""

    #: attributes fleet/engine code duck-types against on ANY backend; a
    #: subclass may shadow them but must never delete them (repro-lint
    #: R005 checks this statically for every ``*Backend`` class).
    REQUIRED_ATTRS = ("name", "n_blocks", "state_version", "snapshot_free")

    name = "dense"

    def __init__(self, model: Model, n_lanes: int, max_len: int):
        self.model = model
        self.n_lanes = n_lanes
        self.max_len = max_len

    # -- gauges (zeros unless the layout tracks them) -------------------
    lane_state_bytes = 0
    n_blocks = 0
    blocks_in_use = 0
    peak_blocks = 0
    shared_blocks_peak = 0
    cow_splits = 0
    cache_evictions = 0
    # bumped whenever capacity/match state changes; footprints computed at
    # one version stay valid while it holds (engine memoizes against it)
    state_version = 0
    # True where snapshot()/restore() resume WITHOUT recompute (recurrent
    # state) — cost-aware migration prefers such lanes as victims
    snapshot_free = False

    def fits(self, n_ctx: int, final_len: int) -> bool:
        """Could a request with this FINAL footprint ever be admitted
        here?  The side-effect-free face of ``alloc``'s INFEASIBLE
        verdict — fleet migration consults it before picking a
        destination, so a mid-flight request is never moved onto a
        worker that must reject it."""
        return True

    def cached_prefix_tokens(self, tokens) -> int:
        """Context positions a re-prefill of ``tokens`` would find already
        cached HERE — the failover plane's recompute estimate when
        resurrecting a dead worker's lane on this backend.  Zero unless
        the layout runs a content-addressed prefix cache."""
        return 0

    def forget_cache(self) -> int:
        """Drop reusable cached content (a zombie worker rejoins COLD
        after a reboot: stale registrations must not be served as hits).
        Returns entries dropped; zero where nothing is cached."""
        return 0

    # capacity the admission scheduler may pack against; None = the lane
    # count is the only bound (footprints are not budget-constrained)
    @property
    def budget_tokens(self) -> Optional[int]:
        return None

    @property
    def capacity_tokens(self) -> Optional[int]:
        return None

    def reset_counters(self) -> None:
        pass

    def expert_load_mean(self) -> float:
        return 0.0


class DenseBackend(CacheBackend):
    """One ``max_len``-wide cache lane per slot (the original layout)."""

    name = "dense"

    def __init__(self, model: Model, n_lanes: int, max_len: int):
        super().__init__(model, n_lanes, max_len)
        from repro.models.attention import cache_span

        self._span = cache_span(model.cfg, max_len) \
            if model.decode_state.kind != "encdec" else max_len
        self.cache = model.init_cache(n_lanes, max_len)
        self._lane_ax, _, self._paste, self._extract = _lane_tools(
            model, n_lanes, max_len)
        self._decode = _decode_jit(model)
        self._window = None          # built on first verify_step

    # ------------------------------------------------------------------
    def token_footprint(self, n_ctx: int, max_new: int,
                        tokens: Optional[Sequence[int]] = None) -> int:
        # a lane is max_len wide no matter how short the request is —
        # that fiction is exactly what the paged layout removes
        return self._span

    def alloc(self, n_ctx: int, final_len: int,
              tokens: Optional[Sequence[int]] = None):
        # dense lanes admit anything (writes past max_len clamp, as the
        # pre-paged engine always did); capacity is the lane count, which
        # the engine bounds before calling alloc
        return Reservation()

    def prefill_paste(self, slot: int, group_cache, src_lane: int,
                      n_ctx: int, width: int, res: Reservation) -> None:
        self.cache = self._paste(self.cache, group_cache,
                                 jnp.int32(src_lane), jnp.int32(slot))

    def activate(self, slot: int, res: Reservation, n_ctx: int) -> None:
        raise NotImplementedError("dense lanes never produce full hits")

    def prepare_lane(self, slot: int) -> bool:
        return True

    def step(self, params, tokens: np.ndarray, active: np.ndarray):
        logits, self.cache = self._decode(params, self.cache,
                                          jnp.asarray(tokens))
        return logits

    # -- speculative verify plumbing -----------------------------------
    def append_tokens(self, slot: int,
                      toks: Sequence[int]) -> bool:
        return True          # lane strips are pre-sized max_len wide

    def verify_step(self, params, tokens: np.ndarray, active: np.ndarray):
        """W sequential decode steps in one dispatch.  tokens (B, W);
        returns per-position logits (B, W, Vp).  Every lane's pos
        advances by W — idle-lane garbage, reset at the next paste, the
        same contract as ``step``."""
        if self._window is None:
            self._window = _window_jit(self.model, True)
        logits, self.cache = self._window(params, self.cache,
                                          jnp.asarray(tokens))
        return logits

    def rollback(self, slot: int, n: int) -> None:
        """Un-write the lane's last ``n`` positions.  Attention K/V is
        position-addressed: retreating pos is enough, the stale entries
        are masked out of every read and overwritten by the next write."""
        if n <= 0:
            return
        if self.model.decode_state.kind == "recurrent":
            raise RuntimeError(
                "dense lanes cannot roll back recurrent state; use "
                "backend='recurrent'")
        self.cache = _DENSE_ADD_POS(self.cache, jnp.int32(slot),
                                    jnp.int32(-n))

    def reset_lane(self, slot: int) -> None:
        self.cache = _DENSE_SET_POS(self.cache, jnp.int32(slot),
                                    jnp.int32(0))

    def snapshot(self, slot: int) -> Optional[Any]:
        return None          # recompute policy: resume re-prefills

    def restore(self, slot: int, snap: Any) -> bool:
        return False

    def release(self, slot: int,
                tokens: Optional[Sequence[int]] = None) -> None:
        pass                 # lane garbage is overwritten by the next paste


class RecurrentBackend(DenseBackend):
    """Pooled constant-footprint lanes for recurrent-state families.

    ssm / rwkv / hybrid decode state does not grow with context length —
    per lane it is a fixed bundle (conv tail + ssm state / rwkv matrix
    state / hybrid shared-attention span).  These families were previously
    exiled to dense lanes with a fictitious ``max_len``-token admission
    charge; ``token_footprint`` now reports the true per-lane state size.
    Every lane costs the same, so admission stays exactly lane-bound (the
    scheduler's budget packing only engages for backends with a finite
    ``budget_tokens``, i.e. paged) — the constant unit is there for
    observability and for future layouts that spill state.  The real win
    is preemption: ``snapshot`` copies the (small, fixed) state host-side
    and a preempted request resumes with ZERO recompute.
    """

    name = "recurrent"
    snapshot_free = True

    def __init__(self, model: Model, n_lanes: int, max_len: int):
        super().__init__(model, n_lanes, max_len)
        # true per-lane state size (elements across all cache leaves);
        # _extract comes shared from _lane_tools via DenseBackend
        _, shapes, _, _ = _lane_tools(model, n_lanes, max_len)
        sizes = jax.tree.leaves(jax.tree.map(
            lambda ax, s: int(np.prod(s.shape)) // (s.shape[ax] if ax >= 0 else 1)
            if ax >= 0 else 0, self._lane_ax, shapes))
        self.state_units = int(sum(sizes))
        # speculative-rollback stash: host copy of the pre-window cache +
        # the window tokens + params, and replayed prefixes memoized per
        # kept length (several lanes rolling back the same amount after
        # one verify round share one replay dispatch)
        self._stash = None
        self._stash_tokens: Optional[np.ndarray] = None
        self._stash_params = None
        self._replay_memo: dict = {}
        self._zero_lane = None

    def token_footprint(self, n_ctx: int, max_new: int,
                        tokens: Optional[Sequence[int]] = None) -> int:
        return self.state_units     # independent of prompt/generation length

    def step(self, params, tokens: np.ndarray, active: np.ndarray):
        # extend the rollback record: single steps taken AFTER a verify
        # window (the draft side of a speculative pair drafts this way)
        # are part of the replayable history.  Memoized prefixes stay
        # valid — appending columns never changes tokens[:, :keep].
        if self._stash_tokens is not None:
            self._stash_tokens = np.concatenate(
                # repro-lint: allow[R004] tokens is the host-side input batch; extends the host rollback record, no device transfer
                [self._stash_tokens, np.asarray(tokens)], axis=1)
        return super().step(params, tokens, active)

    # -- speculative verify plumbing -----------------------------------
    def verify_step(self, params, tokens: np.ndarray, active: np.ndarray):
        """Like the dense window, but rollback must be able to rebuild the
        state as of any window prefix — recurrent state is not
        position-addressed, so nothing can be 'un-written'.  Stash a HOST
        copy of the pre-window cache (the window jit must therefore not
        donate its cache argument) and replay from it on rollback."""
        self._stash = jax.tree.map(np.asarray, self.cache)
        # repro-lint: allow[R004] tokens is the host-side window batch; the stash above is the one deliberate sync per verify window
        self._stash_tokens = np.asarray(tokens)
        self._stash_params = params
        self._replay_memo = {}
        if self._window is None:
            self._window = _window_jit(self.model, False)
        logits, self.cache = self._window(params, self.cache,
                                          jnp.asarray(tokens))
        return logits

    def rollback(self, slot: int, n: int) -> None:
        """Rebuild the lane's state as of window position W - n by
        replaying the kept prefix on the stashed pre-window cache, then
        pasting that one lane into the live cache.  The replay runs the
        FULL multi-lane batch (a 1-lane replay could drift bitwise via
        batch-shape-dependent reduction order); a length-(W-n) scan of
        the same body is bitwise identical to the first W-n iterations
        of the length-W scan."""
        if n <= 0:
            return
        if self._stash is None:
            raise RuntimeError("rollback without a preceding verify_step")
        keep = self._stash_tokens.shape[1] - n
        if keep not in self._replay_memo:
            pre = jax.tree.map(jnp.asarray, self._stash)
            if keep <= 0:
                self._replay_memo[keep] = pre
            else:
                _, replayed = self._window(
                    self._stash_params, pre,
                    jnp.asarray(self._stash_tokens[:, :keep]))
                self._replay_memo[keep] = replayed
        lane = self._extract(self._replay_memo[keep], jnp.int32(slot))
        self.cache = self._paste(self.cache,
                                 jax.tree.map(np.asarray, lane),
                                 jnp.int32(0), jnp.int32(slot))

    def reset_lane(self, slot: int) -> None:
        if self._zero_lane is None:
            self._zero_lane = jax.tree.map(
                np.asarray, self.model.init_cache(1, self.max_len))
        self.cache = self._paste(self.cache, self._zero_lane,
                                 jnp.int32(0), jnp.int32(slot))

    def snapshot(self, slot: int) -> Any:
        snap = self._extract(self.cache, jnp.int32(slot))
        return jax.tree.map(np.asarray, snap)   # host-side, survives donation

    def restore(self, slot: int, snap: Any) -> bool:
        self.cache = self._paste(self.cache, snap, jnp.int32(0),
                                 jnp.int32(slot))
        return True


class PagedBackend(CacheBackend):
    """Block-pooled KV with refcounted copy-on-write prefix caching."""

    name = "paged"

    def __init__(self, model: Model, n_lanes: int, max_len: int,
                 kv_blocks: int, block_size: int,
                 watermark_frac: float = 0.0, prefix_cache: bool = False):
        super().__init__(model, n_lanes, max_len)
        ds = model.decode_state
        # a hybrid's pool holds one recurrent state slot per lane beside
        # the attention blocks: no prefix of it can be shared, and nothing
        # written to it can be taken back
        self.recurrent = ds.kind == "recurrent"
        if prefix_cache and self.recurrent:
            raise ValueError(
                "prefix_cache is refused for a pool with recurrent state: a "
                "lane's Mamba2 state after a prefix cannot be shared through "
                "cached K/V blocks, so a prefix hit would skip the state's "
                "prefill and serve wrong tokens")
        self.blocks = BlockManager(kv_blocks, block_size, watermark_frac)
        self.prefix_cache = prefix_cache
        self.max_blocks_per_lane = -(-max_len // block_size)
        self.cache = ds.pool_init(n_lanes, kv_blocks, block_size)
        self._decode_steps = 0
        self._held_base = 0
        self.block_tables = np.zeros(
            (n_lanes, self.max_blocks_per_lane), np.int32)
        self._lane_blocks: List[List[int]] = [[] for _ in range(n_lanes)]
        self._lane_pos = np.zeros((n_lanes,), np.int64)
        self._decode = _pool_step_jit(ds)
        self._pool_window = None     # built on first verify_step
        self._paste = _POOL_PASTE
        self._set_pos = _POOL_SET_POS
        self._cow_copy = _POOL_COW_COPY

    # -- gauges ---------------------------------------------------------
    @property
    def n_blocks(self) -> int:                           # type: ignore[override]
        return self.blocks.n_blocks

    @property
    def blocks_in_use(self) -> int:                      # type: ignore[override]
        return self.blocks.in_use

    @property
    def peak_blocks(self) -> int:                        # type: ignore[override]
        return self.blocks.peak_in_use

    @property
    def shared_blocks_peak(self) -> int:                 # type: ignore[override]
        return self.blocks.shared_peak

    @property
    def cow_splits(self) -> int:                         # type: ignore[override]
        return self.blocks.cow_splits

    @property
    def cache_evictions(self) -> int:                    # type: ignore[override]
        return self.blocks.evictions

    @property
    def state_version(self) -> int:                      # type: ignore[override]
        return self.blocks.version

    @property
    def budget_tokens(self) -> Optional[int]:
        bm = self.blocks
        return max(0, bm.free - bm.watermark_blocks) * bm.block_size

    @property
    def capacity_tokens(self) -> Optional[int]:
        bm = self.blocks
        return (bm.n_blocks - bm.watermark_blocks) * bm.block_size

    def reset_counters(self) -> None:
        bm = self.blocks
        bm.peak_in_use = bm.in_use
        bm.shared_peak = bm.shared_now
        bm.cow_splits = 0
        bm.evictions = 0
        self._decode_steps = 0
        self._held_base = self._held_slots()

    def _held_slots(self) -> int:
        counters = self.cache.get("counters")
        return int(counters["held_slots"]) if counters is not None else 0

    def expert_load_mean(self) -> float:
        """Token-slots of active lanes routed to the held experts, per held
        expert, per expert layer and decode step since the last reset (a
        device counter, read here once)."""
        cfg = self.model.cfg
        if "counters" not in self.cache or not self._decode_steps:
            return 0.0
        per = self._decode_steps * cfg.n_layers * cfg.n_held_experts
        return (self._held_slots() - self._held_base) / per

    @property
    def lane_state_bytes(self) -> int:
        """Bytes of one lane's recurrent state slot (0 without one)."""
        st = self.cache.get("state")
        if st is None:
            return 0
        return int(sum(a.size // a.shape[1] * a.dtype.itemsize
                       for a in jax.tree.leaves(st)))

    def cached_prefix_tokens(self, tokens) -> int:
        if not self.prefix_cache or tokens is None:
            return 0
        return min(self.blocks.match_prefix(tokens).n_tokens, len(tokens))

    def forget_cache(self) -> int:
        return self.blocks.flush_cache()

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def token_footprint(self, n_ctx: int, max_new: int,
                        tokens: Optional[Sequence[int]] = None) -> int:
        """Free-pool tokens this admission would consume NOW: blocks for
        the context, minus blocks already held live by other lanes (a
        refcount-zero cache hit still consumes a free block when revived,
        so only live-shared hits are discounted)."""
        bm = self.blocks
        need = bm.blocks_needed(n_ctx)
        if self.prefix_cache and tokens is not None:
            m = bm.match_prefix(tokens)
            need -= sum(1 for b in m.blocks if bm.ref_count(b) > 0)
        return need * bm.block_size

    def fits(self, n_ctx: int, final_len: int) -> bool:
        # feasibility is judged on the FINAL footprint: the context plus
        # every token the request may still generate.  A request admitted
        # on prompt size alone but over-budget at completion would die in
        # a preempt/reject loop; one past max_len could resume with more
        # context than the prefill cache span holds.  Blocks freed by
        # prefix sharing don't relax this bound: COW can re-privatise
        # every shared block before the request completes.
        bm = self.blocks
        usable = bm.n_blocks - bm.watermark_blocks
        return (final_len <= self.max_len
                and bm.blocks_needed(final_len) <= usable)

    def alloc(self, n_ctx: int, final_len: int,
              tokens: Optional[Sequence[int]] = None):
        bm = self.blocks
        if not self.fits(n_ctx, final_len):
            return INFEASIBLE
        hits: List[int] = []
        n_cached = n_lookup = 0
        if self.prefix_cache and tokens is not None:
            m = bm.match_prefix(tokens)
            hits, n_cached, n_lookup = list(m.blocks), m.n_tokens, n_ctx
        need = bm.blocks_needed(n_ctx)
        fresh_n = need - len(hits)
        revived = sum(1 for b in hits if bm.ref_count(b) == 0)
        # admission charges only blocks the free pool actually loses:
        # fresh allocations plus revived cache hits; live-shared blocks
        # ride along for free
        if not bm.can_admit(fresh_n + revived):
            return None
        for b in hits:
            bm.ref(b)        # BEFORE allocate(): hits must not be evicted
        fresh = bm.allocate(fresh_n) if fresh_n else []
        blocks = hits + fresh
        if self.prefix_cache and tokens is not None:
            # register the prompt's full blocks NOW (content arrives with
            # this round's paste, before any decode dispatch reads it) so
            # same-round admissions already share them
            bm.register(blocks, tokens)
        full_hit = bool(self.prefix_cache and tokens is not None
                        and n_cached >= n_ctx - 1)
        return Reservation(blocks=blocks, n_cached=n_cached,
                           n_lookup=n_lookup, full_hit=full_hit)

    def _flat_idx(self, blocks: List[int], n_cached: int, n_ctx: int,
                  width: int) -> np.ndarray:
        """Flattened pool slots for prefill positions 0..width-1: positions
        the lane must write go to its blocks; the pad tail AND the shared
        cached prefix (already holding identical K/V) go to the sink."""
        bs = self.blocks.block_size
        i = np.arange(width)
        phys = (i % bs).astype(np.int64)               # sink by default
        mine = (i >= n_cached) & (i < n_ctx)
        ids = np.asarray(blocks, np.int64)
        phys[mine] = ids[i[mine] // bs] * bs + i[mine] % bs
        return phys

    def prefill_paste(self, slot: int, group_cache, src_lane: int,
                      n_ctx: int, width: int, res: Reservation) -> None:
        flat = self._flat_idx(res.blocks, res.n_cached, n_ctx, width)
        self.cache = self._paste(self.cache, group_cache,
                                 jnp.int32(src_lane), jnp.asarray(flat),
                                 jnp.int32(slot), jnp.int32(n_ctx))
        self._install(slot, res.blocks, n_ctx)

    def activate(self, slot: int, res: Reservation, n_ctx: int) -> None:
        """Full hit: every context position short of the last is cached.
        The lane starts at pos = n_ctx - 1 and its first decode step feeds
        the last context token — no prefill dispatch at all."""
        self.cache = self._set_pos(self.cache, jnp.int32(slot),
                                   jnp.int32(n_ctx - 1))
        self._install(slot, res.blocks, n_ctx - 1)

    def _install(self, slot: int, blocks: List[int], pos: int) -> None:
        self._lane_blocks[slot] = list(blocks)
        self.block_tables[slot, :] = 0
        self.block_tables[slot, :len(blocks)] = blocks
        self._lane_pos[slot] = pos

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def prepare_lane(self, slot: int) -> bool:
        """Make the lane's next write position safe: grow into a fresh
        block on a boundary, COW-split a shared block on first write, or
        uncache a sole-holder cached block whose content will diverge.
        False = pool exhausted (the engine preempts and retries)."""
        bm = self.blocks
        bs = bm.block_size
        bidx = int(self._lane_pos[slot]) // bs
        if bidx >= self.max_blocks_per_lane:
            return True                  # saturated: dense-path clamp
        blocks = self._lane_blocks[slot]
        if bidx >= len(blocks):
            blk = bm.allocate_one()
            if blk is None:
                return False
            blocks.append(blk)
            self.block_tables[slot, bidx] = blk
            return True
        blk = blocks[bidx]
        if bm.ref_count(blk) > 1:
            fresh = bm.cow_split(blk)
            if fresh is None:
                return False
            self.cache = self._cow_copy(self.cache, jnp.int32(blk),
                                        jnp.int32(fresh))
            blocks[bidx] = fresh
            self.block_tables[slot, bidx] = fresh
        elif bm.is_cached(blk):
            bm.uncache(blk)              # sole holder: write in place
        return True

    def step(self, params, tokens: np.ndarray, active: np.ndarray):
        logits, self.cache = self._decode(params, self.cache,
                                          jnp.asarray(tokens),
                                          jnp.asarray(self.block_tables))
        self._lane_pos[active] += 1
        self._decode_steps += 1
        return logits

    # -- speculative verify plumbing -----------------------------------
    def _refuse_recurrent(self, what: str) -> None:
        if self.recurrent:
            raise ValueError(
                f"{what} is refused for a pool with recurrent state: the "
                f"verify window advances each lane's Mamba2 state, which a "
                f"rollback cannot retreat as it retreats K/V positions")

    def append_tokens(self, slot: int, toks: Sequence[int]) -> bool:
        """Reserve write capacity for ``len(toks)`` consecutive positions:
        run the single-position ``prepare_lane`` (grow / COW-split /
        uncache) once per position, crossing block boundaries as needed.
        All-or-nothing: on exhaustion the position is restored and the
        engine preempts a victim and retries."""
        self._refuse_recurrent("speculative verify")
        pos0 = int(self._lane_pos[slot])
        for i in range(len(toks)):
            self._lane_pos[slot] = pos0 + i
            if not self.prepare_lane(slot):
                self._lane_pos[slot] = pos0
                return False
        self._lane_pos[slot] = pos0
        return True

    def verify_step(self, params, tokens: np.ndarray, active: np.ndarray):
        self._refuse_recurrent("speculative verify")
        if self._pool_window is None:
            self._pool_window = _pool_window_jit(self.model.decode_state)
        w = tokens.shape[1]
        logits, self.cache = self._pool_window(
            params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.block_tables))
        self._lane_pos[active] += w
        return logits

    def rollback(self, slot: int, n: int) -> None:
        """Truncate the lane's last ``n`` writes and free trailing blocks
        it no longer covers.  Safe by construction: a verify round always
        commits at least one token, so the post-rollback position sits
        strictly past the pre-round content — every freed block is a
        this-round private allocation (``append_tokens`` grows fresh or
        COW-private blocks), never a shared/cached prefix block."""
        if n <= 0:
            return
        self._refuse_recurrent("rollback")
        bm = self.blocks
        new_pos = max(0, int(self._lane_pos[slot]) - n)
        self._lane_pos[slot] = new_pos
        self.cache = self._set_pos(self.cache, jnp.int32(slot),
                                   jnp.int32(new_pos))
        blocks = self._lane_blocks[slot]
        keep = bm.blocks_needed(new_pos)
        if len(blocks) > keep:
            tail = blocks[keep:]
            del blocks[keep:]
            bm.release(tail)
            self.block_tables[slot, keep:] = 0

    def reset_lane(self, slot: int) -> None:
        self.release(slot)
        self.cache = self._set_pos(self.cache, jnp.int32(slot),
                                   jnp.int32(0))

    def snapshot(self, slot: int) -> Optional[Any]:
        return None          # recompute policy (resume prefix-matches the
        #                      blocks registered at release, so the
        #                      re-prefill is usually a full hit anyway)

    def restore(self, slot: int, snap: Any) -> bool:
        return False

    def release(self, slot: int,
                tokens: Optional[Sequence[int]] = None) -> None:
        blocks = self._lane_blocks[slot]
        if blocks:
            if self.prefix_cache and tokens is not None:
                n_valid = min(int(self._lane_pos[slot]), len(tokens))
                self.blocks.register(blocks, tokens[:n_valid])
            self.blocks.release(blocks)
            if self.recurrent:
                self.cache = _POOL_CLEAR_LANE(self.cache, jnp.int32(slot))
        self._lane_blocks[slot] = []
        self.block_tables[slot, :] = 0
        self._lane_pos[slot] = 0


def make_backend(model: Model, n_lanes: int, max_len: int,
                 config) -> CacheBackend:
    """Pick the backend for (model, engine config).

    ``config`` is the engine's :class:`~repro.serving.engine.EngineConfig`.
    ``config.backend`` forces a layout (``"dense" | "paged" | "recurrent"``);
    the default ``None`` auto-selects: a block pool wherever the family
    supports it and ``kv_blocks`` is set, pooled recurrent lanes for
    recurrent-state families, dense lanes otherwise.
    """
    ds = model.decode_state
    choice = config.backend
    if choice is None:
        if config.kv_blocks is not None and ds.poolable:
            choice = "paged"
        elif ds.kind == "recurrent":
            choice = "recurrent"
        else:
            choice = "dense"
    if choice == "paged":
        if not ds.poolable:
            raise ValueError(
                f"family {model.cfg.family!r} has no pool-layout decode "
                f"state; only attention-K/V families are pageable")
        if config.kv_blocks is None:
            raise ValueError("backend='paged' requires EngineConfig.kv_blocks")
        return PagedBackend(model, n_lanes, max_len,
                            kv_blocks=config.kv_blocks,
                            block_size=config.kv_block_size,
                            watermark_frac=config.watermark_frac,
                            prefix_cache=config.prefix_cache)
    if choice == "recurrent":
        if ds.kind != "recurrent":
            raise ValueError(
                f"backend='recurrent' on a {ds.kind!r}-state family")
        return RecurrentBackend(model, n_lanes, max_len)
    if choice == "dense":
        return DenseBackend(model, n_lanes, max_len)
    raise ValueError(f"unknown backend {choice!r}")
