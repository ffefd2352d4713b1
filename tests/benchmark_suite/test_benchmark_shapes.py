"""Byte and FLOP counts of one decode step against hand-worked figures."""

import json
from pathlib import Path

import pytest

from benchmark.lib import peaks, shapes

CONFIGS = Path(__file__).resolve().parents[2] / "benchmark" / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# hand-worked: per layer q + k + v + o + gate + up + down
PHI3_LAYER = 3072 * 3072 * 4 + 3 * 3072 * 8192
MISTRAL_LAYER = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336


@pytest.mark.parametrize("name,layer,vocab,d", [
    ("phi3-mini", PHI3_LAYER, 32064, 3072),
    ("mistral-7b", MISTRAL_LAYER, 32768, 4096),
])
def test_matmul_params(name, layer, vocab, d):
    p = shapes.matmul_params(cfg(name))
    assert p["per_layer"] == layer
    assert p["blocks"] == 32 * layer
    assert p["head"] == p["embed"] == vocab * d


@pytest.mark.parametrize("name,kv", [("phi3-mini", 393_216), ("mistral-7b", 131_072)])
def test_kv_bytes_per_token(name, kv):
    assert shapes.kv_bytes_per_token(cfg(name)) == kv


@pytest.mark.parametrize("name,gb", [("phi3-mini", 3.825), ("mistral-7b", 7.254)])
def test_stored_weight_bytes(name, gb):
    assert shapes.weight_bytes(cfg(name)) / 1e9 == pytest.approx(gb, abs=0.002)


def test_decode_step_bytes_phi3_by_hand():
    # 12 rows holding 3,600 tokens between them
    weights = 32 * PHI3_LAYER + 32064 * 3072  # int8: one byte each
    kv_read = 3600 * 393_216
    kv_write = 12 * 393_216
    embed = 12 * 3072
    logits = 12 * 32064 * 4
    want = weights + kv_read + kv_write + embed + logits
    assert shapes.decode_step_bytes(cfg("phi3-mini"), 12, 3600) == want
    assert want / 819e9 * 1e3 == pytest.approx(6.28, abs=0.01)  # ms at the HBM peak


def test_decode_step_bytes_mistral_by_hand():
    weights = 32 * MISTRAL_LAYER + 32768 * 4096
    want = weights + 1800 * 131_072 + 6 * 131_072 + 6 * 4096 + 6 * 32768 * 4
    assert shapes.decode_step_bytes(cfg("mistral-7b"), 6, 1800) == want
    assert want / 819e9 * 1e3 == pytest.approx(8.98, abs=0.01)


@pytest.mark.parametrize("name,layer,vocab,d,hq,dh", [
    ("phi3-mini", PHI3_LAYER, 32064, 3072, 32, 96),
    ("mistral-7b", MISTRAL_LAYER, 32768, 4096, 32, 128),
])
def test_flops_by_hand(name, layer, vocab, d, hq, dh):
    c = cfg(name)
    assert shapes.decode_token_flops(c, 300) == 2 * (32 * layer + vocab * d) + 4 * 32 * 300 * hq * dh
    want = 2 * 32 * layer * 256 + 4 * 32 * hq * dh * 256 * 257 / 2 + 2 * vocab * d
    assert shapes.prefill_flops(c, 256) == want


def test_a_256_token_chunk_of_the_7b_model_is_about_3_6_tflop():
    assert shapes.prefill_flops(cfg("mistral-7b"), 256) / 1e12 == pytest.approx(3.59, abs=0.01)


def test_peaks_table():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(peaks.UnknownChip):
        peaks.peaks_for("TPU v9")
