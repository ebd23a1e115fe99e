"""Analytic counts against hand arithmetic, and the table of peaks."""
import json

import pytest

from smoke import BENCH

from harness import device, flops

QWEN_SMOKE = {"num_hidden_layers": 2, "hidden_size": 224,
              "num_attention_heads": 14, "num_key_value_heads": 2,
              "intermediate_size": 512, "vocab_size": 512,
              "gated_mlp": True, "qkv_bias": True, "norm": "rmsnorm",
              "tie_word_embeddings": True, "torch_dtype": "bfloat16"}
GECTOR_SMOKE = {"num_hidden_layers": 4, "hidden_size": 256,
                "num_attention_heads": 4, "intermediate_size": 1024,
                "vocab_size": 8192, "gated_mlp": False, "qkv_bias": False,
                "norm": "layernorm", "tie_word_embeddings": True,
                "torch_dtype": "bfloat16"}


def test_decoder_counts_by_hand():
    # attention 224*16*(14+4) + 14*16*224, SwiGLU 3*224*512
    assert flops.layer_matmul_params(QWEN_SMOKE) == 64512 + 50176 + 344064
    p = 458752
    # per layer 2P + 4*heads*head_dim*keys, plus the logits 2*d*V
    assert flops.decode_flops(QWEN_SMOKE, 10) == (
        2 * (2 * p + 4 * 14 * 16 * 10) + 2 * 224 * 512)
    # causal scores over n(n+1)/2 pairs, both products: 2*h*hd*n*(n+1)
    assert flops.prefill_flops(QWEN_SMOKE, 4) == (
        2 * (2 * 4 * p + 2 * 14 * 16 * 4 * 5) + 2 * 224 * 512)
    assert flops.kv_bytes_per_token(QWEN_SMOKE) == 2 * 2 * 2 * 16 * 2
    per_layer = p + 18 * 16 + 224            # biases, one RMSNorm scale x2
    per_layer += 224
    assert flops.weight_bytes(QWEN_SMOKE) == 2 * (
        2 * per_layer + 512 * 224 + 224)


def test_encoder_counts_by_hand():
    p = 256 * 64 * 12 + 4 * 64 * 256 + 2 * 256 * 1024
    assert flops.layer_matmul_params(GECTOR_SMOKE) == p
    assert flops.encoder_flops(GECTOR_SMOKE, 10, 516) == (
        4 * (2 * 10 * p + 4 * 10 * 10 * 4 * 64) + 2 * 10 * 256 * 516)


def test_published_qwen_bytes():
    conf = json.loads((BENCH / "configs" / "qwen2_0_5b.json").read_text())
    assert flops.kv_bytes_per_token(conf) == 12288
    assert 0.98e9 < flops.weight_bytes(conf) < 0.995e9


def test_peaks_table():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("cpu")
