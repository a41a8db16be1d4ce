"""FLOP and byte counts at granite-8b widths against hand-worked values,
and the peak table."""

import json

import pytest

import peaks
import spec
import weights
from conftest import CHIP

N = weights.dims(json.loads((CHIP / "configs" / "granite-8b-18l.json").read_text()))


def test_dims_are_the_published_widths():
    assert N == {"d": 4096, "h": 32, "g": 8, "dh": 128, "f": 14336,
                 "layers": 18, "vocab": 49152, "vp": 49152}


def test_decoder_flops():
    dec = spec.module("counts", "decoder")
    # Wq 4096*4096 + Wk,Wv 2*4096*1024 + Wo 4096*4096 + MLP 3*4096*14336
    assert dec.layer_matmul_params(N) == 218_103_808
    # 2*18*218103808 + head 2*4096*49152 + attention 4*18*1000*32*128
    assert dec.decode_token_flops(N, 1000) == 8_549_302_272
    # 512 prompt tokens through 18 layers, the head once, causal attention
    # 2*18*512*513*32*128
    assert dec.prefill_flops(N, 512) == 4_059_222_245_376


def test_paged_attention_work_uses_true_contexts():
    f, b = spec.module("counts", "paged_attn").work(N, [1000, 24], 2)
    assert f == 4 * 18 * 1024 * 32 * 128 == 301_989_888
    # K and V of 1024 positions x 8 heads x 128 in bf16, plus q and o of
    # two lanes x 32 heads x 128, per layer
    assert b == 18 * (2 * 1024 * 8 * 128 * 2 + 2 * 2 * 32 * 128 * 2) == 76_087_296


def test_flash_attention_work_is_causal_at_real_length():
    f, b = spec.module("counts", "flash_attn").work(N, [512], 2)
    assert f == 18 * 2 * 512 * 513 * 32 * 128 == 38_730_203_136
    assert b == 18 * 2 * 512 * (32 + 8) * 128 * 2 == 188_743_680


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
