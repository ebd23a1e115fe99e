"""Operations and bytes that a dense transformer's algorithm needs, counted
from its published widths: the same work whatever implements it.

Counted: every matrix product with a weight (2 operations per
multiply-add), attention's scores and weighted sums over the keys a query
may see, and the output head where its result is used. Not counted:
norms, activations, softmax, padding, recomputation. Bytes are the
weights read once per step and the keys and values a step reads, in the
configuration's dtype.

``conf`` is a configuration file's dict with the published key names.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    hq = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": conf.get("num_key_value_heads", hq),
            "hd": conf.get("head_dim", d // hq),
            "F": conf["intermediate_size"], "V": conf["vocab_size"],
            "gated": conf["gated_mlp"]}


def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer that multiply an activation."""
    k = dims(conf)
    attn = k["d"] * k["hd"] * (k["hq"] + 2 * k["hkv"]) + k["hq"] * k["hd"] * k["d"]
    mlp = (3 if k["gated"] else 2) * k["d"] * k["F"]
    return attn + mlp


def encoder_flops(conf: dict, n: int, head_outputs: int) -> int:
    """One bidirectional forward over ``n`` real tokens, with output heads
    of ``head_outputs`` columns at every position."""
    k = dims(conf)
    per_layer = (2 * n * layer_matmul_params(conf)
                 + 4 * n * n * k["hq"] * k["hd"])
    return k["L"] * per_layer + 2 * n * k["d"] * head_outputs


def prefill_flops(conf: dict, n: int) -> int:
    """A causal prefill of ``n`` prompt tokens, logits at the last one."""
    k = dims(conf)
    per_layer = (2 * n * layer_matmul_params(conf)
                 + 2 * k["hq"] * k["hd"] * n * (n + 1))
    return k["L"] * per_layer + 2 * k["d"] * k["V"]


def decode_flops(conf: dict, keys: int) -> int:
    """One decoded token whose query sees ``keys`` cached positions
    (itself included), with its logits."""
    k = dims(conf)
    per_layer = 2 * layer_matmul_params(conf) + 4 * k["hq"] * k["hd"] * keys
    return k["L"] * per_layer + 2 * k["d"] * k["V"]


def weight_bytes(conf: dict) -> int:
    """Every weight once, in the configuration's dtype: the layers, their
    norms and biases, the embedding, and an output head when untied."""
    k = dims(conf)
    b = DTYPE_BYTES[conf["torch_dtype"]]
    bias = (k["hq"] + 2 * k["hkv"]) * k["hd"] if conf.get("qkv_bias") else 0
    norms = 2 * k["d"] * (2 if conf.get("norm") == "layernorm" else 1)
    per_layer = layer_matmul_params(conf) + bias + norms
    head = 0 if conf["tie_word_embeddings"] else k["V"] * k["d"]
    return b * (k["L"] * per_layer + k["V"] * k["d"] + head + k["d"])


def kv_bytes_per_token(conf: dict) -> int:
    """Keys and values of one position over all layers."""
    k = dims(conf)
    return 2 * k["L"] * k["hkv"] * k["hd"] * DTYPE_BYTES[conf["torch_dtype"]]
