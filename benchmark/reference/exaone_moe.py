"""The plain reference of ``exaone_moe`` (K-EXAONE-236B-A23B), given the
same share of each layer as the program: straightforward ``jax.numpy``.

float32 activations, every product at precision ``highest``, no cache, no
kernels, no batching, no sorting: one sequence in, every position's hidden
state out.  The layer, for input ``x`` ``[T, hidden]`` (RMSNorm eps from the
file, no bias anywhere):

1. ``h = RMSNorm(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``; q and k
   RMS-normalised over the 128 of every head (learned weight, all ones
   here).
2. ``layer_types[l] == sliding_attention``: rotary embedding on q and k
   (``rope_parameters.rope_theta``, all of the head, split-half), key j
   visible to query i iff ``i - sliding_window < j <= i``.
   ``full_attention``: NO rotation, causal.  Scale ``head_dim ** -0.5``,
   softmax in float32.
3. ``x = x + concat_heads(attn) Wo``.
4. ``h = RMSNorm(x)``.  ``mlp_layer_types[l] == dense``: ``x = x +
   Wdown(silu(Wgate h) * (Wup h))``.  ``sparse``: ``s = sigmoid(h Wr)`` over
   ALL ``num_experts x expert_parallel_size`` experts, in float32; ``sel =
   top-k(s + b)``; ``g = routed_scaling_factor x s[sel] / sum(s[sel])``;
   ``x = x + Shared(h) + sum over the selected experts HELD HERE of g_e x
   Expert_e(h)``.  The experts held are ``num_experts`` from
   ``expert_parallel_rank x num_experts``; what the absent ones would add
   is left out, as in the program, and the partial sum goes on.
5. After the last layer (``logits``): RMSNorm, then the untied head.

It imports nothing of the program and takes nothing the program made.  The
weights are drawn here from the recipe the program states for this family
served without a checkpoint (models/exaone_moe.py ``init_params``): one key
a leaf out of ``split(PRNGKey(seed), 24)``, layer ``l`` of a stacked leaf
from ``fold_in(key, l)``, ``normal / sqrt(fan_in)`` rounded to bfloat16 (the
router and its bias, ``0.01 x normal``, stay float32), norms all ones (and so left out here).
The flat dict names a layer's leaf ``<group><l>.<leaf>``.

``quantize`` makes the control: every matrix a token multiplies against
through float8 (e4m3) and back; the looked-up embedding, the float32 router
and its bias stay.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

ATTN = ("wq", "wk", "wv", "wo")
DENSE = (*ATTN, "w_gate", "w_up", "w_down")
SPARSE = (*ATTN, "w_router", "router_bias", "w_gate", "w_up", "w_down",
          "ws_gate", "ws_up", "ws_down")


def dims(hf: dict) -> dict:
    layers = hf["num_hidden_layers"]
    held = hf["num_experts"]
    return {
        "h": hf["hidden_size"], "i": hf["intermediate_size"], "l": layers,
        "heads": hf["num_attention_heads"], "kv": hf["num_key_value_heads"],
        "d": hf["head_dim"], "v": hf["vocab_size"],
        "eps": hf.get("rms_norm_eps", 1e-5),
        "theta": float(hf["rope_parameters"]["rope_theta"]),
        "window": hf["sliding_window"],
        "attn": tuple(hf["layer_types"][:layers]),
        "mlp": tuple(hf["mlp_layer_types"][:layers]),
        "held": held, "experts": held * hf.get("expert_parallel_size", 1),
        "first": hf.get("expert_parallel_rank", 0) * held,
        "k": hf["num_experts_per_tok"], "mi": hf["moe_intermediate_size"],
        "shared": hf.get("num_shared_experts", 0),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "norm": bool(hf.get("norm_topk_prob", True)),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)


_draw_jit = jax.jit(_draw, static_argnums=(1, 2, 3))


def _groups(c: dict):
    """``(group, its layers, (leaf, one layer's shape, fan_in, dtype) ...)``
    in the order the program draws them."""
    h, qd, kvd = c["h"], c["heads"] * c["d"], c["kv"] * c["d"]
    bf, f32 = jnp.bfloat16, jnp.float32
    attn = (("wq", (h, qd), h, bf), ("wk", (h, kvd), h, bf), ("wv", (h, kvd), h, bf),
            ("wo", (qd, h), qd, bf))
    i, e, mi = c["i"], c["held"], c["mi"]
    si = mi * max(c["shared"], 1)
    dense = (*attn, ("w_gate", (h, i), h, bf), ("w_up", (h, i), h, bf), ("w_down", (i, h), i, bf))
    sparse = (*attn, ("w_router", (h, c["experts"]), h, f32), ("router_bias", (c["experts"],), 1.0, f32),
              ("w_gate", (e, h, mi), h, bf), ("w_up", (e, h, mi), h, bf), ("w_down", (e, mi, h), mi, bf),
              ("ws_gate", (h, si), h, bf), ("ws_up", (h, si), h, bf), ("ws_down", (si, h), si, bf))
    nd = sum(m == "dense" for m in c["mlp"])
    return (("dense", nd, dense), ("sparse", c["l"] - nd, sparse))


def init_weights(hf: dict, seed: int) -> dict:
    """A flat dict: ``embed``, ``lm_head`` and ``<group><l>.<leaf>`` for layer
    ``l`` of the dense and of the sparse layers, one jitted draw each."""
    c = dims(hf)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 24))
    w = {"embed": _draw_jit(next(keys), (c["v"], c["h"]), 1.0, jnp.bfloat16),
         "lm_head": _draw_jit(next(keys), (c["h"], c["v"]), float(c["h"]), jnp.bfloat16)}
    for group, count, leaves in _groups(c):
        if not count:
            continue
        for leaf, shape, fan_in, dtype in leaves:
            key = next(keys)
            for layer in range(count):
                drawn = _draw_jit(jax.random.fold_in(key, layer), shape, float(fan_in), dtype)
                w[f"{group}{layer}.{leaf}"] = 0.01 * drawn if leaf == "router_bias" else drawn
    return w


def _round_fp8(w):
    """Through float8 (e4m3), scaled per output channel to the type's range,
    and back to bfloat16 (``reduce_precision``: XLA may drop a pair of
    converts as excess precision)."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 240.0
    q = jax.lax.reduce_precision(w32 / jnp.maximum(scale, 1e-30), exponent_bits=4, mantissa_bits=3)
    return (q * scale).astype(jnp.bfloat16)


_ROUND = {"fp8": jax.jit(_round_fp8)}


def quantize(leaves: dict, kind: str, hf: dict) -> dict:
    """The control's form of ``leaves``: every matrix a token multiplies
    against through ``kind`` and back; the looked-up embedding and the
    float32 router with its bias stay as they are."""
    keep = ("embed", "w_router", "router_bias")
    return {k: (v if k.rsplit(".", 1)[-1] in keep else _ROUND[kind](v)) for k, v in leaves.items()}


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HIGHEST)


def _gated(m, gate, up, down):
    return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)


def _attention(x, w, cos, sin, diff, window, c):
    t = x.shape[0]
    a = _rms(x, c["eps"])
    q = _rms(_mm(a, w["wq"]).reshape(t, c["heads"], c["d"]), c["eps"])
    k = _rms(_mm(a, w["wk"]).reshape(t, c["kv"], c["d"]), c["eps"])
    v = _mm(a, w["wv"]).reshape(t, c["kv"], c["d"])
    mask = diff >= 0
    if window:      # a sliding layer: rotated, and the last ``window`` keys only
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        mask = mask & (diff < window)
    qg = q.reshape(t, c["kv"], c["heads"] // c["kv"], c["d"])
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HIGHEST) / math.sqrt(c["d"])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST).reshape(t, -1)
    return x + _mm(o, w["wo"])


def _dense_layer(x, w, cos, sin, diff, window, c):
    x = _attention(x, w, cos, sin, diff, window, c)
    m = _rms(x, c["eps"])
    return x + _gated(m, w["w_gate"], w["w_up"], w["w_down"])


def _sparse_layer(x, w, cos, sin, diff, window, c):
    x = _attention(x, w, cos, sin, diff, window, c)
    m = _rms(x, c["eps"])
    scores = jax.nn.sigmoid(_mm(m, w["w_router"]))                    # [T, E] over ALL experts
    _, chosen = jax.lax.top_k(scores + w["router_bias"][None, :], c["k"])
    g = jnp.take_along_axis(scores, chosen, axis=-1)
    if c["norm"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = c["scale"] * g
    out = _gated(m, w["ws_gate"], w["ws_up"], w["ws_down"]) if c["shared"] else jnp.zeros_like(m)
    for e in range(c["held"]):      # every held expert over every token, then its weight or 0
        weight = jnp.sum(jnp.where(chosen == c["first"] + e, g, 0.0), axis=-1, keepdims=True)
        out = out + weight * _gated(m, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    return x + out


def hidden(weights: dict, hf: dict, ids):
    """The trunk: the last block's output ``[len(ids), hidden]`` (float32)
    for the sequence ``ids``, layer by layer."""
    c = dims(hf)
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    half = c["d"] // 2
    freqs = 1.0 / (c["theta"] ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    diff = pos[:, None] - pos[None, :]
    frozen = tuple(sorted((k, v) for k, v in c.items()))
    x = weights["embed"][ids].astype(jnp.float32)
    seen = {"dense": 0, "sparse": 0}
    for attn, mlp in zip(c["attn"], c["mlp"]):
        names, layer = (DENSE, _dense_jit) if mlp == "dense" else (SPARSE, _sparse_jit)
        w = {n: weights[f"{mlp}{seen[mlp]}.{n}"] for n in names}
        seen[mlp] += 1
        x = layer(x, w, cos, sin, diff, c["window"] if attn == "sliding_attention" else 0, frozen)
    return x


def logits(weights: dict, hf: dict, x):
    """Final norm and the untied output head over rows ``x`` of ``hidden``'s
    output: ``[len(x), vocab]`` float32."""
    return _head_jit(x, weights["lm_head"], dims(hf)["eps"])


def forward(weights: dict, hf: dict, ids, rows=None):
    """The two halves together, for tests."""
    x = hidden(weights, hf, ids)
    return logits(weights, hf, x if rows is None else x[jnp.asarray(rows, jnp.int32)])


def _jit(layer):
    return jax.jit(lambda x, w, cos, sin, diff, window, frozen:
                   layer(x, w, cos, sin, diff, window, dict(frozen)), static_argnums=(5, 6))


_dense_jit, _sparse_jit = _jit(_dense_layer), _jit(_sparse_layer)
_head_jit = jax.jit(lambda x, lm, eps: _mm(_rms(x, eps), lm), static_argnums=(2,))
