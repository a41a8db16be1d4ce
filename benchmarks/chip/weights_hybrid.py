"""Seeded weights of a granitemoehybrid configuration, made on the device
in one jitted call, in the layout of the program's parameter tree
(``repro.models.hybrid``: ``embed.tok``, ``blocks.mamba`` / ``blocks.attn``
/ ``blocks.moe`` stacked by layer of their kind, ``final_ln``) and in the
types the program keeps them in.  The reference makes them again with the
same call.

Scales come from the configuration's ``init`` block.  A matrix that
reads the residual stream (after a norm) is normal with standard
deviation ``gain / sqrt(fan_in)``; one that writes a branch back into it
(Mamba2 ``out_proj``, attention ``wo``, each expert's and the shared
expert's down projection) has ``out_gain / sqrt(fan_in)``; the router
has ``router_gain / sqrt(d)``, so its logits spread by about
``router_gain``.  The embedding has ``embed_std``, the depthwise conv
weights and bias ``conv_std``, norm scales are 1 and the final norm's is
``final_norm_scale``.  The Mamba2 constants follow the published
initialisation: ``A_log = log(1..H)``, ``D = 1``, ``dt_bias`` the inverse
softplus of steps log-uniform in ``[dt_min, dt_max]``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from weights import jax_seed, pad_vocab


def dims(config: dict) -> dict:
    """Every size the program, the reference and the counts need."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"][:layers])
    d_in = config["mamba_expand"] * d
    ng, ns = config["mamba_n_groups"], config["mamba_d_state"]
    return {"d": d, "h": h, "g": config["num_key_value_heads"],
            "dh": config.get("head_dim") or d // h,
            "layers": layers, "kinds": kinds,
            "n_mamba": kinds.count("mamba"),
            "n_attn": kinds.count("attention"),
            "vocab": config["vocab_size"],
            "vp": pad_vocab(config["vocab_size"]),
            "d_in": d_in, "mh": config["mamba_n_heads"],
            "mp": config["mamba_d_head"], "ns": ns, "ng": ng,
            "conv": config["mamba_d_conv"], "conv_dim": d_in + 2 * ng * ns,
            "chunk": config["mamba_chunk_size"],
            "f": config["intermediate_size"],
            "fs": config["shared_intermediate_size"],
            "experts": config["published"]["num_local_experts"],
            "held": tuple(config["held_experts"]),
            "n_held": config["num_local_experts"],
            "topk": config["num_experts_per_tok"]}


def _normal(k, shape, std, dtype):
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _mamba(key, n, init, dtype):
    ks = jax.random.split(key, 5)
    d, d_in, H = n["d"], n["d_in"], n["mh"]
    in_dim = 2 * d_in + 2 * n["ng"] * n["ns"] + H
    lo, hi = np.log(init["dt_min"]), np.log(init["dt_max"])
    dt = jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32) * (hi - lo) + lo)
    return {"ln": {"scale": jnp.ones((d,), dtype)},
            "mamba": {
                "in_proj": _normal(ks[0], (d, in_dim), init["gain"] * d ** -0.5,
                                   dtype),
                "conv_w": _normal(ks[1], (n["conv"], n["conv_dim"]),
                                  init["conv_std"], dtype),
                "conv_b": _normal(ks[2], (n["conv_dim"],), init["conv_std"],
                                  dtype),
                "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                "D": jnp.ones((H,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_scale": jnp.ones((d_in,), dtype),
                "out_proj": _normal(ks[4], (d_in, d),
                                    init["out_gain"] * d_in ** -0.5, dtype)}}


def _attn(key, n, init, dtype):
    ks = jax.random.split(key, 4)
    d, h, g, dh = n["d"], n["h"], n["g"], n["dh"]
    s = init["gain"] * d ** -0.5
    return {"ln": {"scale": jnp.ones((d,), dtype)},
            "attn": {"wq": _normal(ks[0], (d, h * dh), s, dtype),
                     "wk": _normal(ks[1], (d, g * dh), s, dtype),
                     "wv": _normal(ks[2], (d, g * dh), s, dtype),
                     "wo": _normal(ks[3], (h * dh, d),
                                   init["out_gain"] * (h * dh) ** -0.5, dtype)}}


def _moe(key, n, init, dtype):
    ks = jax.random.split(key, 7)
    d, f, fs, e = n["d"], n["f"], n["fs"], n["n_held"]
    s = init["gain"] * d ** -0.5
    return {"ln": {"scale": jnp.ones((d,), dtype)},
            "moe": {"router": _normal(ks[0], (d, n["experts"]),
                                      init["router_gain"] * d ** -0.5,
                                      jnp.float32),
                    "wi": _normal(ks[1], (e, d, f), s, dtype),
                    "wg": _normal(ks[2], (e, d, f), s, dtype),
                    "wo": _normal(ks[3], (e, f, d),
                                  init["out_gain"] * f ** -0.5, dtype),
                    "shared": {"wi": _normal(ks[4], (d, fs), s, dtype),
                               "wg": _normal(ks[5], (d, fs), s, dtype),
                               "wo": _normal(ks[6], (fs, d),
                                             init["out_gain"] * fs ** -0.5,
                                             dtype)}}}


@functools.lru_cache(maxsize=8)
def _maker(config_json: str):
    config = json.loads(config_json)
    n = dims(config)
    init = config["init"]
    dtype = jnp.dtype(config["torch_dtype"])

    def stack(key, count, fn):
        return jax.vmap(lambda k: fn(k, n, init, dtype))(
            jax.random.split(key, count))

    def make(key):
        k_embed, k_m, k_a, k_e = jax.random.split(key, 4)
        blocks = {"mamba": stack(k_m, n["n_mamba"], _mamba),
                  "attn": stack(k_a, n["n_attn"], _attn),
                  "moe": stack(k_e, n["layers"], _moe)}
        return {"embed": {"tok": _normal(k_embed, (n["vp"], n["d"]),
                                         init["embed_std"], dtype)},
                "blocks": blocks,
                "final_ln": {"scale": jnp.full((n["d"],),
                                               init["final_norm_scale"],
                                               dtype)}}

    return jax.jit(make)


def make(config: dict, seed: int):
    """The parameter tree for ``seed``, on the default device."""
    fn = _maker(json.dumps(config, sort_keys=True))
    return fn(jax.random.key(jax_seed(seed)))
