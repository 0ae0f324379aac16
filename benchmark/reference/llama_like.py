"""The plain reference: a llama-like decoder in straightforward ``jax.numpy``.

One reference module among any number: a configuration names its own in its
file's ``reference`` key and ``check.py`` calls the four functions of the
interface (``benchmark/README.md``): ``init_weights``, ``hidden``,
``logits``, ``quantize``.

float32 activations, every product at precision ``highest``, no cache, no
kernels, no batching: one sequence in, the logits at every position out.
It follows the published block (RMSNorm, rotary positions in the split-half
convention, grouped-query attention, SwiGLU) with the two switches the
benchmark's configurations use: per-head q/k RMSNorm before the rotation
(``model_type`` ``qwen3``) and a sliding window (``sliding_window``, a
position attends the last W positions, itself included).

It imports nothing of the program and takes nothing the program made.  The
weights are drawn here from the same recipe the program states for a model
served without a checkpoint: ``jax.random.split(PRNGKey(seed), 12)``, each
matrix ``normal(key, shape, float32) / sqrt(fan_in)`` rounded to bfloat16
(the type they are served in), norms all ones.  The bfloat16 values ARE the
model; the reference upcasts them and computes in float32.

``quantize`` makes the control: the same weights rounded per output channel
to float8 (e4m3) and back, a precision below the one the configurations
state.  (Weight-only int8 was read once and is not told from bfloat16 by this
comparison: PERF.md section 2.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    d = hf.get("head_dim") or hf["hidden_size"] // heads
    return {"h": hf["hidden_size"], "i": hf["intermediate_size"], "l": hf["num_hidden_layers"],
            "heads": heads, "kv": hf.get("num_key_value_heads", heads), "d": d,
            "v": hf["vocab_size"], "qk_norm": hf.get("model_type") == "qwen3",
            "tied": bool(hf.get("tie_word_embeddings", False)),
            "window": hf.get("sliding_window") or None,
            "eps": hf.get("rms_norm_eps", 1e-5), "theta": hf.get("rope_theta", 10000.0)}


def _draw(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(jnp.bfloat16)


_draw_jit = jax.jit(_draw, static_argnums=(1, 2))


def init_weights(hf: dict, seed: int) -> dict:
    """bfloat16 weights, layer-stacked, one jitted draw per matrix."""
    c = dims(hf)
    keys = jax.random.split(jax.random.PRNGKey(seed), 12)
    h, i, l, qd, kvd = c["h"], c["i"], c["l"], c["heads"] * c["d"], c["kv"] * c["d"]
    w = {
        "embed": _draw_jit(keys[0], (c["v"], h), 1.0),
        "wq": _draw_jit(keys[1], (l, h, qd), float(h)),
        "wk": _draw_jit(keys[2], (l, h, kvd), float(h)),
        "wv": _draw_jit(keys[3], (l, h, kvd), float(h)),
        "wo": _draw_jit(keys[4], (l, qd, h), float(qd)),
        "w_gate": _draw_jit(keys[5], (l, h, i), float(h)),
        "w_up": _draw_jit(keys[6], (l, h, i), float(h)),
        "w_down": _draw_jit(keys[7], (l, i, h), float(i)),
    }
    if not c["tied"]:
        w["lm_head"] = _draw_jit(keys[8], (h, c["v"]), float(h))
    return w


def _round_fp8(w):
    """Through float8 (e4m3, three bits of mantissa), scaled per output
    channel to the type's range, and back to bfloat16."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 240.0
    # reduce_precision and not a cast there and back: XLA may drop a pair of
    # converts as excess precision (it did, on the chip)
    q = jax.lax.reduce_precision(w32 / jnp.maximum(scale, 1e-30), exponent_bits=4, mantissa_bits=3)
    return (q * scale).astype(jnp.bfloat16)


_ROUND = {"fp8": jax.jit(_round_fp8)}


def quantize(leaves: dict, kind: str, hf: dict) -> dict:
    """The control's form of ``leaves`` (any subset of the weights, by name):
    every matrix a token multiplies against (the blocks and the output head;
    a tied head is the embedding) through ``kind`` and back; an embedding
    that is only looked up stays."""
    tied = bool(hf.get("tie_word_embeddings", False))
    return {k: (v if k == "embed" and not tied else _ROUND[kind](v)) for k, v in leaves.items()}


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HIGHEST)


def _layer(x, w, cos, sin, mask, c):
    t = x.shape[0]
    a = _rms(x, c["eps"])
    q = _mm(a, w["wq"]).reshape(t, c["heads"], c["d"])
    k = _mm(a, w["wk"]).reshape(t, c["kv"], c["d"])
    v = _mm(a, w["wv"]).reshape(t, c["kv"], c["d"])
    if c["qk_norm"]:
        q, k = _rms(q, c["eps"]), _rms(k, c["eps"])
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    g = c["heads"] // c["kv"]
    qg = q.reshape(t, c["kv"], g, c["d"])
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HIGHEST) / math.sqrt(c["d"])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST).reshape(t, -1)
    x = x + _mm(o, w["wo"])
    m = _rms(x, c["eps"])
    return x + _mm(jax.nn.silu(_mm(m, w["w_gate"])) * _mm(m, w["w_up"]), w["w_down"])


def _head(x, embed, lm_head, c):
    x = _rms(x, c["eps"])
    if c["tied"]:
        return jnp.matmul(x, embed.astype(jnp.float32).T, precision=HIGHEST)
    return _mm(x, lm_head)


def hidden(weights: dict, hf: dict, ids):
    """The trunk: the last block's output ``[len(ids), hidden]`` (float32)
    for the sequence ``ids``.  Layer by layer, so that only one layer's
    float32 copy is alive at a time."""
    c = dims(hf)
    ids = jnp.asarray(ids, jnp.int32)
    t = ids.shape[0]
    pos = jnp.arange(t)
    half = c["d"] // 2
    freqs = 1.0 / (c["theta"] ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    diff = pos[:, None] - pos[None, :]
    mask = diff >= 0
    if c["window"]:
        mask = mask & (diff < c["window"])
    frozen = tuple(sorted((k, v) for k, v in c.items()))
    x = weights["embed"][ids].astype(jnp.float32)
    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    for layer in range(c["l"]):
        x = _layer_jit(x, {n: weights[n][layer] for n in names}, cos, sin, mask, frozen)
    return x


def logits(weights: dict, hf: dict, x):
    """Final norm and output head over rows ``x`` of ``hidden``'s output:
    ``[len(x), vocab]`` float32."""
    frozen = tuple(sorted((k, v) for k, v in dims(hf).items()))
    return _head_jit(x, weights["embed"], weights.get("lm_head"), frozen)


def forward(weights: dict, hf: dict, ids, rows=None):
    """Logits for the sequence ``ids`` at the positions ``rows`` (default:
    all): the two halves together, for a caller that wants few rows once."""
    x = hidden(weights, hf, ids)
    return logits(weights, hf, x if rows is None else x[jnp.asarray(rows, jnp.int32)])


_layer_jit = jax.jit(lambda x, w, cos, sin, mask, frozen: _layer(x, w, cos, sin, mask, dict(frozen)),
                     static_argnums=(5,))
_head_jit = jax.jit(lambda x, e, lm, frozen: _head(x, e, lm, dict(frozen)), static_argnums=(3,))
