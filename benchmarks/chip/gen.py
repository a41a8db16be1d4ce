"""Traffic: one general generator that reads a mix's parameters.

A mix file (``traffic/<name>.json``) gives:

* ``load``: ``"backlog"`` (offline batch: a queue kept at least
  ``queue_min`` deep) or ``"open_loop"`` (requests due on a schedule,
  sent whether or not earlier ones have finished);
* ``arrivals`` (open loop): ``"poisson"`` at ``rate_rps``, or ``"mmpp"``
  (two-state Markov-modulated Poisson: ``calm_rps``, ``burst_rps``,
  ``calm_dwell_s``, ``burst_dwell_s``);
* ``prompt`` / ``output``: lognormal token counts, ``median``, ``sigma``,
  clipped to ``[min, max]``; ``max_total`` caps prompt + output;
* ``greedy_share`` of requests decoded greedily, the rest sampled at
  ``temperature`` / ``top_p``;
* optionally ``prefix``: ``{"tokens": n, "count": k}``, each prompt then
  starts with one of k shared prefixes of n tokens.

Every seed gets the same set of sizes (lognormal quantiles), the same set
of Poisson gaps (exponential quantiles) and the same share of greedy
requests, in an order drawn from the seed; token ids are drawn from the
seed too.  So seeds change which request comes when, not how much work a
mix holds.  The Poisson and MMPP processes follow
``repro.serving.traffic`` (``poisson_trace``, ``mmpp_trace``); its
uniform sizes are replaced by heavy-tailed ones.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    prompt: np.ndarray          # (T,) int32
    max_new: int
    greedy: bool
    temperature: float
    top_p: float
    seed: int                   # the request's own sampling stream
    due: Optional[float] = None  # seconds after the window opens (open loop)


def lognormal_set(n: int, p: dict) -> np.ndarray:
    """n lognormal quantiles at (i + 1/2) / n, clipped, as whole tokens."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    v = np.asarray([p["median"] * math.exp(p["sigma"] * nd.inv_cdf(x))
                    for x in q])
    return np.clip(np.rint(v), p["min"], p["max"]).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """n exponential quantiles at (i + 1/2) / n of mean 1 / rate."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _sizes(mix: dict, n: int, rng: np.random.Generator):
    """The mix's set of n (prompt, output, greedy) triples, paired the same
    way for every seed, in the seed's order."""
    pairing = np.random.default_rng(0)
    prompts = lognormal_set(n, mix["prompt"])
    outs = pairing.permutation(lognormal_set(n, mix["output"]))
    outs = np.minimum(outs, mix["max_total"] - prompts)
    n_greedy = int(round(n * mix.get("greedy_share", 1.0)))
    greedy = pairing.permutation(np.arange(n) < n_greedy)
    order = rng.permutation(n)
    return prompts[order], outs[order], greedy[order]


def _build(mix: dict, rng: np.random.Generator, vocab: int, prompts, outs,
           greedy, prefixes) -> List[Req]:
    reqs = []
    for n_p, n_o, g in zip(prompts.tolist(), outs.tolist(), greedy.tolist()):
        if prefixes:
            pre = prefixes[int(rng.integers(len(prefixes)))]
            own = rng.integers(0, vocab, max(1, n_p - len(pre)), dtype=np.int32)
            toks = np.concatenate([pre, own])
        else:
            toks = rng.integers(0, vocab, n_p, dtype=np.int32)
        reqs.append(Req(prompt=toks, max_new=int(n_o), greedy=bool(g),
                        temperature=0.0 if g else float(mix["temperature"]),
                        top_p=1.0 if g else float(mix["top_p"]),
                        seed=int(rng.integers(0, 2**31 - 1))))
    return reqs


def _prefixes(mix: dict, rng: np.random.Generator, vocab: int):
    p = mix.get("prefix")
    if not p:
        return []
    return [rng.integers(0, vocab, p["tokens"], dtype=np.int32)
            for _ in range(p["count"])]


def backlog(mix: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Endless backlog: successive blocks of ``set_size`` requests, each
    block the same set of sizes in a fresh order."""
    rng = np.random.default_rng(seed)
    prefixes = _prefixes(mix, rng, vocab)
    n = int(mix["set_size"])
    while True:
        yield from _build(mix, rng, vocab, *_sizes(mix, n, rng), prefixes)


def poisson_due(rate: float, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
    n = max(1, int(math.ceil(rate * seconds)))
    due = np.cumsum(rng.permutation(exponential_gaps(n, rate)))
    return due[due < seconds]


def mmpp_due(mix: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    parts, t, bursting = [], 0.0, False
    while t < seconds:
        dwell = rng.exponential(mix["burst_dwell_s"] if bursting
                                else mix["calm_dwell_s"])
        end = min(t + dwell, seconds)
        rate = mix["burst_rps"] if bursting else mix["calm_rps"]
        if rate > 0:
            n_max = max(4, int(rate * (end - t) * 2 + 16))
            seg = t + np.cumsum(rng.exponential(1.0 / rate, size=n_max))
            parts.append(seg[seg < end])
        t, bursting = end, not bursting
    return np.concatenate(parts) if parts else np.empty(0)


def open_loop(mix: dict, seed: int, vocab: int, seconds: float) -> List[Req]:
    """Requests due within ``seconds``, sorted by due time."""
    rng = np.random.default_rng(seed)
    prefixes = _prefixes(mix, rng, vocab)
    kind = mix["arrivals"]
    if kind == "poisson":
        due = poisson_due(float(mix["rate_rps"]), seconds, rng)
    elif kind == "mmpp":
        due = mmpp_due(mix, seconds, rng)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    reqs = _build(mix, rng, vocab, *_sizes(mix, len(due), rng), prefixes)
    for r, t in zip(reqs, due.tolist()):
        r.due = t
    return reqs
