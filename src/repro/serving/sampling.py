"""Token sampling for the serving engine.

Per-request :class:`SamplingParams` are flattened into per-lane arrays
(temperature / top-k / top-p / PRNG key) so one jitted :func:`sample_tokens`
call serves every active lane of the continuous batch at once — greedy lanes
and stochastic lanes coexist in the same dispatch.

Semantics (matching the usual serving conventions):

* ``temperature <= 0``  -> greedy argmax; the PRNG is not consumed.
* ``top_k > 0``         -> restrict to the k highest logits.
* ``top_p < 1``         -> restrict to the smallest prefix of the
  probability-sorted vocab whose cumulative mass reaches ``top_p``
  (the nucleus; the boundary token is always kept).
* filters compose: top-k first, then top-p over the RENORMALIZED
  survivor distribution (HF-style).

Each lane owns an independent counter-mode PRNG stream derived from the
request's ``seed``, so decode order / lane placement / batch composition
never change a request's sampled tokens.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.spans import Spans

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy.  Defaults reproduce the old greedy engine."""
    temperature: float = 0.0
    top_k: int = 0                 # 0 = disabled
    top_p: float = 1.0             # 1 = disabled
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


@dataclasses.dataclass
class LaneSampling:
    """SoA view of the sampling state of every lane (host side).

    The engine owns one of these sized ``max_batch``; admission writes a
    request's params into its lane, and every decode step ships the arrays
    to :func:`sample_tokens` and writes back the advanced PRNG counters.
    """
    temperature: np.ndarray        # (B,) float32
    top_k: np.ndarray              # (B,) int32
    top_p: np.ndarray              # (B,) float32
    key: np.ndarray                # (B, 2) uint32 (jax threefry key data)

    @classmethod
    def empty(cls, n_lanes: int) -> "LaneSampling":
        # key width depends on the active PRNG impl (threefry: 2 uint32,
        # rbg: 4) — ask jax rather than hardcoding
        kd = jax.random.key_data(jax.random.key(0))
        return cls(
            temperature=np.zeros((n_lanes,), np.float32),
            top_k=np.zeros((n_lanes,), np.int32),
            top_p=np.ones((n_lanes,), np.float32),
            key=np.zeros((n_lanes,) + kd.shape, kd.dtype),
        )

    def set_lane(self, lane: int, params: SamplingParams) -> None:
        self.temperature[lane] = params.temperature
        self.top_k[lane] = params.top_k
        self.top_p[lane] = params.top_p
        self.key[lane] = jax.random.key_data(jax.random.key(params.seed))

    def clear_lane(self, lane: int) -> None:
        self.set_lane(lane, GREEDY)


def _filter_one(logits: jax.Array, temperature: jax.Array, top_k: jax.Array,
                top_p: jax.Array) -> jax.Array:
    """Temperature-scale then top-k/top-p mask one lane's logits (V,)."""
    v = logits.shape[-1]
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    order = jnp.sort(scaled)[::-1]                       # descending
    # top-k threshold: value of the k-th largest logit (k==0 -> whole vocab)
    k = jnp.where(top_k > 0, top_k, v)
    in_topk = jnp.arange(v) < k
    kth = order[jnp.clip(k - 1, 0, v - 1)]
    # top-p over the RENORMALIZED top-k survivors: keep entries whose
    # *preceding* cumulative survivor mass is < top_p (boundary included)
    probs = jax.nn.softmax(jnp.where(in_topk, order, NEG_INF))
    prior_mass = jnp.cumsum(probs) - probs
    in_nucleus = in_topk & (prior_mass < top_p)
    pth = jnp.min(jnp.where(in_nucleus, order, jnp.inf))
    cut = jnp.maximum(kth, pth)
    return jnp.where(scaled < cut, NEG_INF, scaled)


def _sample_tokens(logits: jax.Array, temperature: jax.Array,
                   top_k: jax.Array, top_p: jax.Array, key_data: jax.Array):
    """Sample one token per lane.

    logits (B, V) float; temperature (B,), top_k (B,), top_p (B,),
    key_data (B, 2) uint32.  Returns (tokens (B,) int32, new key_data).
    """
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(l, t, k, p, kd):
        kk = jax.random.wrap_key_data(kd)
        kk, sub = jax.random.split(kk)
        tok = jax.random.categorical(sub, _filter_one(l, t, k, p))
        return tok.astype(jnp.int32), jax.random.key_data(kk)

    samp_tok, new_kd = jax.vmap(one)(logits, temperature, top_k, top_p,
                                     key_data)
    is_greedy = temperature <= 0.0
    tokens = jnp.where(is_greedy, greedy_tok, samp_tok)
    # greedy lanes leave their stream untouched (reproducible mid-flight
    # policy switches, and admission of a fresh request into a reused lane)
    new_kd = jnp.where(is_greedy[:, None], key_data, new_kd)
    return tokens, new_kd


sample_tokens = jax.jit(_sample_tokens)


def _sample_tokens_masked(logits, temperature, top_k, top_p, key_data, mask):
    """:func:`_sample_tokens` with a lane mask: masked-out lanes keep their
    PRNG stream untouched (their returned token is garbage).  The
    speculative accept loop needs this — a lane that already ended its
    round must not consume key splits for window positions it never
    reaches, or its stream would diverge from the baseline engine's."""
    tokens, new_kd = _sample_tokens(logits, temperature, top_k, top_p,
                                    key_data)
    new_kd = jnp.where(mask[:, None], new_kd, key_data)
    return tokens, new_kd


sample_tokens_masked = jax.jit(_sample_tokens_masked)


def resolve_sampling(sampling: Optional[SamplingParams],
                     extra: dict) -> Optional[SamplingParams]:
    """Resolve an engine ``submit``'s decode policy.

    ``SamplingParams`` is the single supported argument; the loose
    ``temperature=`` / ``top_k=`` / ``top_p=`` / ``seed=`` kwargs of the
    pre-Sampler API are kept as a DEPRECATED shim — popped out of
    ``extra`` (mutating it, so leftovers keep their existing meaning) and
    folded into an equivalent ``SamplingParams``.  Mixing both is an
    error rather than a silent precedence rule.
    """
    legacy = {k: extra.pop(k) for k in ("temperature", "top_k", "top_p",
                                        "seed") if k in extra}
    if not legacy:
        return sampling
    if sampling is not None:
        raise TypeError(
            f"pass decode policy either as sampling=SamplingParams(...) or "
            f"as legacy kwargs, not both (got sampling= and {sorted(legacy)})")
    warnings.warn(
        "loose temperature/top_k/top_p/seed kwargs are deprecated; pass "
        "sampling=SamplingParams(...)", DeprecationWarning, stacklevel=3)
    return SamplingParams(**legacy)


class Sampler:
    """Owns the per-lane filter + PRNG state and both sampling entry
    points — the plain engine's one-token :meth:`sample` and the
    speculative engine's window :meth:`accept` share this object, so the
    speculative path cannot drift from the baseline discipline.

    The state is the same :class:`LaneSampling` SoA the engine always
    kept (exposed as ``.lanes`` — engine/fleet code that snapshots a
    lane's key for preemption keeps working on the arrays in place).

    :meth:`sample` opens the spans ``serve.sample.upload`` (lane state
    and logits to the device), ``.dispatch`` (the ``sample_tokens``
    call), ``.wait`` (until its outputs are ready) and ``.download``
    (tokens and advanced keys to the host).
    """

    def __init__(self, n_lanes: int, spans: Optional[Spans] = None):
        self.n_lanes = n_lanes
        self.lanes = LaneSampling.empty(n_lanes)
        # the engine passes its own, so the sampler's phases land in the
        # engine's collector; alone, a sampler only annotates a trace
        self.spans = spans or Spans()

    # -- lane state ----------------------------------------------------
    def set_lane(self, lane: int, params: SamplingParams) -> None:
        self.lanes.set_lane(lane, params)

    def clear_lane(self, lane: int) -> None:
        self.lanes.clear_lane(lane)

    def copy_state_from(self, other: "Sampler") -> None:
        """Adopt ``other``'s full lane state (filters + PRNG counters) —
        the draft sampler mirrors the target sampler at the start of
        every speculative round, so a perfectly-aligned draft model
        proposes exactly what the target would sample."""
        np.copyto(self.lanes.temperature, other.lanes.temperature)
        np.copyto(self.lanes.top_k, other.lanes.top_k)
        np.copyto(self.lanes.top_p, other.lanes.top_p)
        np.copyto(self.lanes.key, other.lanes.key)

    # -- sampling ------------------------------------------------------
    def sample(self, logits, lanes: Optional[Sequence[int]] = None,
               mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Sample one token per row of ``logits`` and advance the rows'
        PRNG streams in place.  ``lanes`` maps rows to lane indices
        (default: row i is lane i); ``mask`` freezes masked-out lanes'
        streams (their tokens are garbage)."""
        ls = self.lanes
        sp = self.spans
        idx = (np.arange(logits.shape[0]) if lanes is None
               else np.asarray(lanes))
        with sp.span("serve.sample.upload"):
            logits = jnp.asarray(logits)
            args = (jnp.asarray(ls.temperature[idx]),
                    jnp.asarray(ls.top_k[idx]), jnp.asarray(ls.top_p[idx]),
                    jnp.asarray(ls.key[idx]))
            if mask is not None:
                args += (jnp.asarray(mask),)
        with sp.span("serve.sample.dispatch"):
            fn = sample_tokens if mask is None else sample_tokens_masked
            toks, new_kd = fn(logits, *args)
        with sp.span("serve.sample.wait"):
            jax.block_until_ready((toks, new_kd))
        with sp.span("serve.sample.download"):
            ls.key[idx] = np.asarray(new_kd)
            return np.asarray(toks)

    def accept(self, window_logits, drafted: np.ndarray,
               active: np.ndarray, limit: Sequence[int],
               eos_id: Optional[int] = None
               ) -> Tuple[List[List[int]], np.ndarray, np.ndarray]:
        """Coupled acceptance over one verify window.

        ``window_logits`` (B, W, V) are the target's logits after each of
        the W = k + 1 window tokens; ``drafted`` (B, k) the draft's
        proposals; ``active`` (B,) which lanes ran the round; ``limit``
        (B,) tokens each lane may still emit; ``eos_id`` ends a lane.

        Position j's logits are sampled from the TARGET's filtered
        distribution via the lane's frozen stream — exactly the token the
        baseline engine would emit next — and the lane continues past j
        iff that token equals ``drafted[:, j]``.  The draft therefore
        only ever controls how FAR a round reaches, never what is
        emitted: the output stream is bit-for-bit the baseline stream
        for greedy AND stochastic targets, and each lane consumes
        exactly one key split per emitted token (masked sampling), so
        preempt/resume identity is preserved mid-round.

        Returns (per-lane emitted tokens, n_emitted (B,), n_accepted
        (B,) drafted tokens matched).  With k = 1 and an always-ending
        first position this reduces to the baseline sampler exactly.
        """
        b, w, _ = np.asarray(window_logits).shape
        k = w - 1
        alive = np.asarray(active, bool).copy()
        emitted: List[List[int]] = [[] for _ in range(b)]
        n_acc = np.zeros(b, np.int64)
        limit = np.asarray(limit)
        for j in range(w):
            if not alive.any():
                break
            toks = self.sample(window_logits[:, j], mask=alive)
            for i in range(b):
                if not alive[i]:
                    continue
                t = int(toks[i])
                emitted[i].append(t)
                done = (len(emitted[i]) >= limit[i]
                        or (eos_id is not None and t == eos_id))
                # a drafted token the target also sampled is ACCEPTED even
                # when the lane ends here (limit/eos) — done controls
                # continuation, not the proposal's correctness
                if j < k and t == int(drafted[i, j]):
                    n_acc[i] += 1
                if j == k or done or t != int(drafted[i, j]):
                    alive[i] = False
        n_emitted = np.array([len(e) for e in emitted], np.int64)
        return emitted, n_emitted, n_acc
