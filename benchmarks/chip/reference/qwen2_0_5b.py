"""qwen2-0.5b: the benchmark's weights for it and its plain float32
reference.

Qwen2 (arXiv:2407.10671) is decoder-only: token embedding, then per layer
RMSNorm -> grouped-query attention with biased Q/K/V projections and
rotary positions (rotate-half, base ``rope_theta``) -> residual add ->
RMSNorm -> SwiGLU MLP -> residual add; a final RMSNorm and logits through
the tied embedding. The reference is written from that description in
``jax.numpy`` at float32 with ``highest`` matmul precision, one sequence at
a time, and imports nothing of the program under test.

The weights are made here from the run's seed, in one jitted call, in the
tree layout and dtypes the program serves (bfloat16 matrices and biases,
float32 norm scales, a float32 embedding table holding bfloat16 values,
its rows padded to a multiple of 256). The reference makes them again from
the seed after the program's state is freed, and reads them in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops
from harness.numerics import fp8_round, seed_key

VOCAB_PAD = 256


def _shape(conf: dict) -> tuple:
    """The static description of the model that the jitted functions take:
    widths, padded vocabulary, RMSNorm epsilon and rotary base."""
    k = flops.dims(conf)
    vp = -(-k["V"] // VOCAB_PAD) * VOCAB_PAD
    return (k["L"], k["d"], k["hq"], k["hkv"], k["hd"], k["F"], k["V"], vp,
            conf["rms_norm_eps"], conf["rope_theta"])


@functools.partial(jax.jit, static_argnums=0)
def _make(shape, key):
    L, d, hq, hkv, hd, F, V, vp, _, _ = shape
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, dtype=jnp.bfloat16):
        w = jax.random.truncated_normal(next(ks), -2.0, 2.0, shape,
                                        jnp.float32) / np.sqrt(fan_in)
        return w.astype(dtype)

    def normal(shape, scale, dtype=jnp.bfloat16):
        return (scale * jax.random.normal(next(ks), shape)).astype(dtype)

    def norm_scale(shape):
        return 1.0 + normal(shape, 0.1, jnp.float32)

    table = dense((vp, d), d).astype(jnp.float32)
    blk = {
        "norm1": {"scale": norm_scale((L, d))},
        "attn": {"wq": dense((L, d, hq, hd), d),
                 "wk": dense((L, d, hkv, hd), d),
                 "wv": dense((L, d, hkv, hd), d),
                 "wo": dense((L, hq, hd, d), hq * hd),
                 "bq": normal((L, hq, hd), 0.1),
                 "bk": normal((L, hkv, hd), 0.1),
                 "bv": normal((L, hkv, hd), 0.1)},
        "norm2": {"scale": norm_scale((L, d))},
        "mlp": {"w_in": dense((L, d, 2, F), d),
                "w_down": dense((L, F, d), F)},
    }
    return {"embed": {"table": table},
            "final_norm": {"scale": norm_scale((d,))},
            "blocks": {"blk0": blk}}


def make_weights(conf: dict, seed: int):
    """The served weights of ``seed``, on the default device."""
    return _make(_shape(conf), seed_key(seed))


# ---------------------------------------------------------------- reference
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, base):
    hd = x.shape[-1]
    freqs = 1.0 / base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(shape, w, tokens, precision):
    """Logits (S, V) of one causal sequence ``tokens`` (S,)."""
    L, d, hq, hkv, hd, F, V, vp, eps, base = shape
    q8 = fp8_round if precision == "fp8" else (lambda t: t)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    w = jax.tree.map(lambda t: t.astype(jnp.float32), w)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"]["table"][tokens]

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["norm1"]["scale"], eps)
        q = mm("sd,dhk->shk", h, a["wq"]) + a["bq"]
        k = mm("sd,dhk->shk", h, a["wk"]) + a["bk"]
        v = mm("sd,dhk->shk", h, a["wv"]) + a["bv"]
        q, k = _rope(q, pos, base), _rope(k, pos, base)
        q = q.reshape(S, hkv, hq // hkv, hd)
        s = mm("qhgk,thk->hgqt", q, k) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("hgqt,thk->qhgk", jax.nn.softmax(s, -1), v)
        x = x + mm("shk,hkd->sd", o.reshape(S, hq, hd), a["wo"])
        h = _rms(x, p["norm2"]["scale"], eps)
        gu = mm("sd,dcf->scf", h, p["mlp"]["w_in"])
        x = x + mm("sf,fd->sd", jax.nn.silu(gu[:, 0]) * gu[:, 1],
                   p["mlp"]["w_down"])
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, w["blocks"]["blk0"])
        x = _rms(x, w["final_norm"]["scale"], eps)
        return mm("sd,vd->sv", x, w["embed"]["table"][:V])


@functools.partial(jax.jit, static_argnums=0)
def _gaps(shape, w, seq, start, stop):
    """Per position t in [start, stop): how far the reference logit of
    ``seq[t + 1]`` lies below the reference's best at t."""
    lg = _logits(shape, w, seq, "f32")
    nxt = jnp.roll(seq, -1)
    gap = lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
    t = jnp.arange(seq.shape[0])
    return jnp.where((t >= start) & (t < stop), gap, 0.0).max()


@functools.partial(jax.jit, static_argnums=(0, 5))
def _control_gaps(shape, w, seq, start, stop, precision):
    """As ``_gaps``, for the token that the reference computed at
    ``precision`` puts first at each position."""
    ref = _logits(shape, w, seq, "f32")
    low = _logits(shape, w, seq, precision)
    pick = low.argmax(-1)
    gap = ref.max(-1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    t = jnp.arange(seq.shape[0])
    return jnp.where((t >= start) & (t < stop), gap, 0.0).max()


def served_gap(conf: dict, w, prompt, served, length: int,
               control: str | None = None) -> float:
    """Widest gap, over the served tokens of one request, by which a served
    token's reference logit lies below the reference's best; with
    ``control``, of the token the reference at that precision puts first.
    ``length`` pads every sequence to one shape (a causal model's logits
    do not see the padding)."""
    seq = np.zeros(length, np.int32)
    full = np.concatenate([prompt, served]).astype(np.int32)
    seq[:len(full)] = full
    start, stop = len(prompt) - 1, len(prompt) - 1 + len(served)
    shape = _shape(conf)
    if control is None:
        out = _gaps(shape, w, jnp.asarray(seq), start, stop)
    else:
        out = _control_gaps(shape, w, jnp.asarray(seq), start, stop, control)
    return float(out)

