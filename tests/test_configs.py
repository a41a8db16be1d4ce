"""Arch registry: published param counts, reduced configs, shape rules."""
import pytest

from repro.configs import (ARCH_IDS, REGISTRY, SHAPES, get_config,
                           reduced_config, shape_applicable)

# published sizes (B params); tolerance covers counting conventions.
# internvl2-1b's published 0.94B INCLUDES the ~0.3B InternViT frontend,
# which is a stub here (assignment: backbone only) -> LM-only expectation.
PUBLISHED = {
    "whisper-small": 0.244, "zamba2-7b": 7.0, "mistral-nemo-12b": 12.2,
    "yi-34b": 34.4, "granite-8b": 8.1, "command-r-35b": 35.0,
    "llama4-scout-17b-a16e": 109.0, "grok-1-314b": 314.0,
    "rwkv6-1.6b": 1.6, "internvl2-1b": 0.50, "granite-4.0-h-small": 32.2,
}
ACTIVE = {"llama4-scout-17b-a16e": 17.0, "grok-1-314b": 86.0}


def test_ten_archs():
    # the ten assigned architectures and granite-4.0-h-small, served on
    # the chip benchmark
    assert len(ARCH_IDS) == 11


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_counts(arch):
    cfg = get_config(arch)
    n = cfg.total_params() / 1e9
    assert abs(n - PUBLISHED[arch]) / PUBLISHED[arch] < 0.25, (arch, n)
    if arch in ACTIVE:
        na = cfg.active_params() / 1e9
        assert abs(na - ACTIVE[arch]) / ACTIVE[arch] < 0.15, (arch, na)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_preserves_structure(arch):
    full, red = get_config(arch), reduced_config(get_config(arch))
    assert red.family == full.family
    assert (red.n_experts > 0) == (full.n_experts > 0)
    assert red.rwkv == full.rwkv
    assert (red.attn_every > 0) == (full.attn_every > 0)
    assert (red.n_enc_layers > 0) == (full.n_enc_layers > 0)
    assert red.total_params() < 20e6


def test_long_500k_applicability():
    runs = {a for a in ARCH_IDS
            if shape_applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert runs == {"zamba2-7b", "rwkv6-1.6b", "llama4-scout-17b-a16e",
                    "granite-4.0-h-small"}


def test_shapes():
    assert SHAPES["train_4k"].tokens_per_step == 256 * 4096
    assert SHAPES["decode_32k"].tokens_per_step == 128
    assert SHAPES["long_500k"].seq_len == 524288
