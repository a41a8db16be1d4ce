"""Seeded weights of a configuration, made on the device in one jitted call.

The benchmark makes the weights itself, in the layout of the program's
parameter tree (``repro.models.lm``: ``embed.tok``, stacked ``blocks``,
``final_ln``), in the type they are served in, and hands the same call's
output to the program; the reference makes them again with the same call
and upcasts them one layer at a time.  So the reference takes nothing the
program has made.

Scales come from the configuration's ``init`` block: every matrix is
normal with standard deviation ``gain / sqrt(fan_in)``, the embedding has
``embed_std``, norm scales are 1 and the final norm's scale is
``final_norm_scale``.  With tied embeddings the program's head is
``embed.T / sqrt(d_model)``, so ``embed_std`` and ``final_norm_scale``
set the spread of the logits and how far a token's own logit stands out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def pad_vocab(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple


def jax_seed(seed: int) -> int:
    """A 31-bit key seed for any whole-number ``--seed``."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def dims(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"d": d, "h": h, "g": config["num_key_value_heads"],
            "dh": config.get("head_dim") or d // h,
            "f": config["intermediate_size"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "vp": pad_vocab(config["vocab_size"])}


def _layer(key, n: dict, gain: float, dtype) -> dict:
    ks = jax.random.split(key, 7)
    d, h, g, dh, f = n["d"], n["h"], n["g"], n["dh"], n["f"]

    def w(k, shape):
        std = gain * shape[0] ** -0.5
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return {"ln1": {"scale": jnp.ones((d,), dtype)},
            "ln2": {"scale": jnp.ones((d,), dtype)},
            "attn": {"wq": w(ks[0], (d, h * dh)), "wk": w(ks[1], (d, g * dh)),
                     "wv": w(ks[2], (d, g * dh)), "wo": w(ks[3], (h * dh, d))},
            "mlp": {"wi": w(ks[4], (d, f)), "wg": w(ks[5], (d, f)),
                    "wo": w(ks[6], (f, d))}}


@functools.lru_cache(maxsize=8)
def _maker(config_json: str):
    import json
    config = json.loads(config_json)
    n = dims(config)
    init = config["init"]
    dtype = jnp.dtype(config["torch_dtype"])

    def make(key):
        k_embed, k_blocks = jax.random.split(key)
        blocks = jax.vmap(lambda k: _layer(k, n, init["gain"], dtype))(
            jax.random.split(k_blocks, n["layers"]))
        embed = (init["embed_std"] * jax.random.normal(
            k_embed, (n["vp"], n["d"]), jnp.float32)).astype(dtype)
        final = jnp.full((n["d"],), init["final_norm_scale"], dtype)
        return {"embed": {"tok": embed}, "blocks": blocks,
                "final_ln": {"scale": final}}

    return jax.jit(make)


def make(config: dict, seed: int):
    """The parameter tree for ``seed``, on the default device."""
    import json
    fn = _maker(json.dumps(config, sort_keys=True))
    return fn(jax.random.key(jax_seed(seed)))
