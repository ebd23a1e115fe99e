"""gector-base: the benchmark's weights for it and its plain float32
reference.

GECToR (arXiv:2005.12592) tags each token of a sentence with an edit, from
a BERT-base encoder and two linear heads: edit tags and error detection.
The encoder here is the one the program serves, which departs from BERT
as the configuration file's ``departures`` list: token embedding plus
learned absolute positions; per layer LayerNorm -> bidirectional
multi-head attention (no biases) -> residual add -> LayerNorm -> GELU
(tanh form) MLP -> residual add; a final LayerNorm; both heads at every
position. A sentence is run at the width of the bucket its batch was
padded to, with zero tokens after it, since the program's attention sees
that padding. Written in ``jax.numpy`` at float32 with ``highest`` matmul
precision; imports nothing of the program under test.

The weights are made here from the run's seed, in one jitted call, in the
tree layout and dtypes the program serves (bfloat16 matrices, float32
norms, heads and embedding tables holding bfloat16 values).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops
from harness.numerics import fp8_round, seed_key

VOCAB_PAD = 256
HEADS = ("tag", "detect")


def _shape(conf: dict) -> tuple:
    """Static description: widths, padded vocabulary, positions, tags,
    LayerNorm epsilon."""
    k = flops.dims(conf)
    vp = -(-k["V"] // VOCAB_PAD) * VOCAB_PAD
    return (k["L"], k["d"], k["hq"], k["hd"], k["F"], vp,
            conf["max_position_embeddings"], conf["n_tags"],
            conf["layer_norm_eps"])


@functools.partial(jax.jit, static_argnums=0)
def _make(shape, key):
    L, d, h, hd, F, vp, P, T, _ = shape
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, dtype=jnp.bfloat16):
        w = jax.random.truncated_normal(next(ks), -2.0, 2.0, shape,
                                        jnp.float32) / np.sqrt(fan_in)
        return w.astype(jnp.bfloat16).astype(dtype)

    def norm(shape):
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(ks), shape),
                "bias": 0.1 * jax.random.normal(next(ks), shape)}

    enc = {
        "embed": {"table": dense((vp, d), d, jnp.float32)},
        "pos_embed": {"table": dense((P, d), d, jnp.float32)},
        "final_norm": norm((d,)),
        "blocks": {"blk0": {
            "norm1": norm((L, d)),
            "attn": {"wq": dense((L, d, h, hd), d),
                     "wk": dense((L, d, h, hd), d),
                     "wv": dense((L, d, h, hd), d),
                     "wo": dense((L, h, hd, d), h * hd)},
            "norm2": norm((L, d)),
            "mlp": {"w_up": dense((L, d, F), d),
                    "w_down": dense((L, F, d), F)}}},
    }
    return {"encoder": enc,
            "detect_head": {"w": dense((d, 2), d, jnp.float32)},
            "label_head": {"w": dense((d, T), d, jnp.float32)}}


def make_weights(conf: dict, seed: int):
    """The served weights of ``seed``, on the default device."""
    return _make(_shape(conf), seed_key(seed))


# ---------------------------------------------------------------- reference
def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _heads(shape, w, tokens, precision):
    """(tag logits (B, S, T), detect logits (B, S, 2)) of a padded batch."""
    L, d, h, hd, F, vp, P, T, eps = shape
    q8 = fp8_round if precision == "fp8" else (lambda t: t)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    w = jax.tree.map(lambda t: t.astype(jnp.float32), w)
    e = w["encoder"]
    S = tokens.shape[1]
    x = e["embed"]["table"][tokens] + e["pos_embed"]["table"][:S][None]

    def layer(x, p):
        a = p["attn"]
        y = _ln(x, p["norm1"], eps)
        q = mm("bsd,dhk->bshk", y, a["wq"])
        k = mm("bsd,dhk->bshk", y, a["wk"])
        v = mm("bsd,dhk->bshk", y, a["wv"])
        s = mm("bqhk,bthk->bhqt", q, k) / np.sqrt(hd)
        o = mm("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        y = _ln(x, p["norm2"], eps)
        u = jax.nn.gelu(mm("bsd,df->bsf", y, p["mlp"]["w_up"]),
                        approximate=True)
        return x + mm("bsf,fd->bsd", u, p["mlp"]["w_down"]), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, e["blocks"]["blk0"])
        x = _ln(x, e["final_norm"], eps)
        return (mm("bsd,dt->bst", x, w["label_head"]["w"]),
                mm("bsd,dt->bst", x, w["detect_head"]["w"]))


def heads(conf: dict, w, tokens: np.ndarray, precision: str = "f32"):
    """Reference (tag, detect) logits of a zero-padded batch (B, S), as
    numpy; ``precision="fp8"`` computes every product in float8."""
    tag, det = _heads(_shape(conf), w, jnp.asarray(tokens, jnp.int32),
                      precision)
    return np.asarray(tag), np.asarray(det)

