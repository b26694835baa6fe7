"""Operation and byte counts against hand arithmetic at the published
widths of both configurations."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import json  # noqa: E402

import arith  # noqa: E402

DANUBE = json.loads((HERE / "configs" / "danube-1.8b.json").read_text())
# Yi-34B at its published widths (huggingface.co/01-ai/Yi-34B), 6 of its
# 60 layers: a pipeline stage of one 16 GB chip
YI = {"family": "llama", "hidden_size": 7168, "intermediate_size": 20480,
      "num_hidden_layers": 6, "num_attention_heads": 56,
      "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 64000,
      "sliding_window": None, "tie_word_embeddings": False}


def test_danube_counts():
    # q and o 2560x2560 each, k and v 2560x640 each, MLP 3x2560x6912
    layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert layer == 69_468_160 == arith.layer_matmul_params(DANUBE)
    assert arith.matmul_params(DANUBE) == 24 * layer + 32000 * 2560
    assert arith.kv_bytes_per_token(DANUBE) == 2 * 2 * 24 * 8 * 80 == 61_440
    # a page of 32 tokens, as the engine allocates it
    assert 32 * arith.kv_bytes_per_token(DANUBE) == 1_966_080
    w = 2 * (24 * (layer + 2 * 2560) + 2560 + 32000 * 2560)
    assert arith.weight_bytes(DANUBE) == w
    assert arith.decode_least_bytes(DANUBE, [100, 2000]) == \
        w + 2 * 2 * 2560 + 61_440 * 2100


def test_yi_stage_counts():
    layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 20480
    assert layer == 557_842_432 == arith.layer_matmul_params(YI)
    assert arith.matmul_params(YI) == 6 * layer + 64000 * 7168
    assert arith.kv_bytes_per_token(YI) == 2 * 2 * 6 * 8 * 128 == 24_576
    assert 32 * arith.kv_bytes_per_token(YI) == 786_432
    # 4.26 G parameters, 8.53 GB in bf16, as the stage holds them
    total = 6 * (layer + 2 * 7168) + 7168 + 2 * 64000 * 7168
    assert arith.weight_bytes(YI, rows_looked_up=64000) == 2 * total
    assert abs(2 * total / 1e9 - 8.53) < 0.01


@pytest.mark.parametrize("spec", [DANUBE, YI], ids=["danube", "yi"])
def test_token_and_prefill_flops(spec):
    L, nq, dh = (spec[k] for k in ("num_hidden_layers",
                                   "num_attention_heads", "head_dim"))
    n = arith.matmul_params(spec)
    assert arith.token_flops(spec, 0) == 2 * n + 4 * L * nq * dh
    assert arith.token_flops(spec, 999) == 2 * n + 4 * L * nq * dh * 1000
    want = sum(arith.token_flops(spec, p) for p in range(64, 200))
    assert arith.prefill_flops(spec, 64, 200) == pytest.approx(want,
                                                               rel=1e-12)
    assert arith.prefill_flops(spec, 200, 200) == 0
    assert arith.decode_flops(spec, [1, 1000]) == \
        arith.token_flops(spec, 0) + arith.token_flops(spec, 999)


def test_window_caps_context():
    assert arith.attn_context(DANUBE, 5000) == 4096
    assert arith.attn_context(YI, 5000) == 5001


def test_peaks_table():
    pk = arith.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.peaks("TPU v4")
