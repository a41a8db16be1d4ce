"""The granitemoehybrid float32 reference (``reference/granitemoehybrid``)
agrees with the program's jnp path (``use_kernels=False``, float32) at a
small width: prefill, then decode through the cache, teacher-forced; and
its gaps are zero on its own choices."""

import jax.numpy as jnp
import numpy as np
import pytest

import spec
import tiny_hybrid
import weights_hybrid


def program_logits(config, seed, toks, n_prompt):
    from repro.configs.base import RunConfig
    from repro.models.api import build_model

    harness = spec.module("harness", "serve_hybrid")
    model = build_model(harness.model_config(config),
                        RunConfig(param_dtype="float32", compute_dtype="float32",
                                  remat=False, use_kernels=False))
    params = weights_hybrid.make(config, seed)
    lg, cache = model.prefill(params, {"tokens": jnp.asarray(toks[None, :n_prompt])},
                              len(toks))
    out = [lg[0]]
    for t in toks[n_prompt:-1]:
        lg, cache = model.decode_step(params, cache, jnp.asarray([[t]], jnp.int32))
        out.append(lg[0])
    return np.stack([np.asarray(o[:config["vocab_size"]]) for o in out])


@pytest.mark.parametrize("seed", [0, 2**33 + 7])
def test_reference_matches_the_program_jnp_path(seed):
    config = tiny_hybrid.config(torch_dtype="float32")
    toks = np.random.default_rng(seed).integers(0, config["vocab_size"], 40,
                                                dtype=np.int32)
    n_prompt = 24
    got = program_logits(config, seed, toks, n_prompt)
    ref = spec.module("reference", "granitemoehybrid")
    want = ref.logits(config, seed, toks)[n_prompt - 1:len(toks) - 1]
    scale = np.abs(want).max()
    assert scale > 1.0                       # logits are not all near zero
    # float32 on both sides: only the order of sums differs (chunked vs
    # sequential scan, grouped vs dense experts)
    np.testing.assert_allclose(got, want, atol=2e-4 * scale, rtol=0)


def test_held_share_is_part_of_the_reference():
    """Holding fewer experts changes the reference's logits: the absent
    experts add nothing."""
    config = tiny_hybrid.config(torch_dtype="float32")
    fewer = tiny_hybrid.config(torch_dtype="float32", num_local_experts=2,
                               held_experts=[0, 2])
    toks = np.arange(20, dtype=np.int32)
    ref = spec.module("reference", "granitemoehybrid")
    a, b = ref.logits(config, 1, toks), ref.logits(fewer, 1, toks)
    assert np.abs(a - b).max() > 1e-3


def test_gaps_are_zero_on_the_reference_own_choices():
    config = tiny_hybrid.config(torch_dtype="float32")
    ref = spec.module("reference", "granitemoehybrid")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, config["vocab_size"], 20, dtype=np.int32)
    toks = list(prompt)
    for _ in range(6):                       # greedy continuation by the reference
        toks.append(int(np.argmax(ref.logits(config, 5, np.asarray(toks))[-1])))
    toks = np.asarray(toks, np.int32)
    served, ctl = ref.logit_gaps(config, 5, [(toks, 20)], pad_to=32,
                                 control="int8")
    assert served[0].shape == (6,)
    assert np.abs(served[0]).max() <= 1e-5
    assert ctl[0].shape == (6,) and (ctl[0] >= 0).all()
