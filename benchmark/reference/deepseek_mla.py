"""The plain reference of the ``deepseek_v2`` / ``deepseek_v3`` family
(Moonlight-16B-A3B): latent attention computed DECOMPRESSED, sigmoid or
softmax routing over all experts, shared experts.  Straightforward
``jax.numpy``: float32 activations, every product at precision ``highest``,
no cache, no kernels, no batching, nothing absorbed: one sequence in, every
position's hidden state out.  The layer, for input ``x`` ``[T, hidden]``
(RMSNorm eps from the file, learned norm weights all ones and so left out,
no bias anywhere):

1. ``a = RMSNorm(x)``.  ``q = a Wq`` (or ``RMSNorm(a Wdq) Wuq`` where the
   file has a ``q_lora_rank``), a head ``[q_nope (qk_nope_head_dim) | q_rope
   (qk_rope_head_dim)]``.  ``[c | k_rope] = a Wdkv`` (``kv_lora_rank`` |
   ``qk_rope_head_dim``); ``c <- RMSNorm(c)``.  ``q_rope`` of every head and
   the ONE ``k_rope`` are rotated at the position (``rope_theta``, split-half
   pairs, no scaling).  ``k_nope_h = c Wuk_h``, ``v_h = c Wuv_h``.
2. ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) /
   sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal, softmax in float32;
   ``x = x + concat_h(sum_s p_h v_h) Wo``.  (A head at a time: 16 heads'
   scores of 8,192 x 8,192 are 4.3 GB beside 10.9 GB of weights.)
3. ``u = RMSNorm(x)``.  A layer before ``first_k_dense_replace``: ``x = x +
   Wdown(silu(Wgate u) * Wup u)``.  Otherwise ``s = sigmoid(u Wr)`` in
   float32 over all ``n_routed_experts`` (``scoring_func`` ``softmax``: the
   softmax); the ``num_experts_per_tok`` largest of ``s + b`` (``n_group`` =
   ``topk_group`` = 1: no group limit; more groups are refused); ``g =
   routed_scaling_factor x s_chosen / sum(s_chosen)`` (``norm_topk_prob``);
   ``x = x + Shared(u) + sum_e g_e Expert_e(u)``, the shared experts ONE
   gated MLP of width ``n_shared_experts x moe_intermediate_size``.
4. After the last layer (``logits``): RMSNorm, then the head (the embedding
   transposed where the file ties them).

**Departure from "every expert over every token":** the routing is computed
first and an expert runs over the rows that chose it (gathered, padded to a
length read on the host from the routing's counts; rows of the padding add
nothing).  64 experts over 8,192 rows each at ``highest`` are 9 TFLOP a layer
for a sum of which 6/64 is not multiplied by zero.  The sum is the same.

It imports nothing of the program and takes nothing the program made.  The
weights are drawn here from the recipe the program states for this family
served without a checkpoint (models/deepseek.py ``init_params``): one key a
drawn leaf out of ``split(PRNGKey(seed), 32)`` in the program's order, layer
``l`` of a stacked leaf from ``fold_in(key, l)``, ``normal / sqrt(fan_in)``
rounded to bfloat16, the selection bias ``0.01 x normal`` in float32.  The
flat dict names a layer's leaf ``<group><l>.<leaf>``.

``quantize`` makes the control: every matrix a token multiplies against
through float8 (e4m3) and back; the looked-up embedding (where it is not
also the head), the router and its bias stay.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_STEP = 1024     # an expert's rows are padded to a multiple of this (few lengths, few programs)


def dims(hf: dict) -> dict:
    if (hf.get("n_group") or 1) != 1 or (hf.get("topk_group") or 1) != 1:
        raise NotImplementedError("group-limited routing is not in this reference")
    if hf.get("rope_scaling"):
        raise NotImplementedError("rope_scaling is not in this reference")
    return {
        "h": hf["hidden_size"], "i": hf["intermediate_size"], "l": hf["num_hidden_layers"],
        "heads": hf["num_attention_heads"], "v": hf["vocab_size"],
        "q_lora": hf.get("q_lora_rank") or 0, "r": hf["kv_lora_rank"],
        "nope": hf["qk_nope_head_dim"], "rope": hf["qk_rope_head_dim"], "vd": hf["v_head_dim"],
        "eps": hf.get("rms_norm_eps", 1e-6), "theta": float(hf.get("rope_theta", 10000.0)),
        "dense": hf.get("first_k_dense_replace", 0),
        "experts": hf.get("n_routed_experts") or 1, "k": hf.get("num_experts_per_tok") or 1,
        "mi": hf.get("moe_intermediate_size") or hf["intermediate_size"],
        "shared": hf.get("n_shared_experts") or 0,
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "norm": bool(hf.get("norm_topk_prob", True)),
        "sigmoid": hf.get("scoring_func", "softmax") == "sigmoid",
        "tied": bool(hf.get("tie_word_embeddings", False)),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)


_draw_jit = jax.jit(_draw, static_argnums=(1, 2, 3))


def _groups(c: dict):
    """``(group, its layers, (leaf, one layer's shape, fan_in, dtype) ...)``
    in the order the program draws them."""
    h, r, heads = c["h"], c["r"], c["heads"]
    hd_q = heads * (c["nope"] + c["rope"])
    bf, f32 = jnp.bfloat16, jnp.float32
    attn = [("w_dkv", (h, r + c["rope"]), h, bf), ("w_uk", (r, heads * c["nope"]), r, bf),
            ("w_uv", (r, heads * c["vd"]), r, bf), ("wo", (heads * c["vd"], h), heads * c["vd"], bf)]
    if c["q_lora"]:
        attn += [("w_dq", (h, c["q_lora"]), h, bf), ("w_uq", (c["q_lora"], hd_q), c["q_lora"], bf)]
    else:
        attn += [("wq", (h, hd_q), h, bf)]
    i, e, mi = c["i"], c["experts"], c["mi"]
    dense = (*attn, ("w_gate", (h, i), h, bf), ("w_up", (h, i), h, bf), ("w_down", (i, h), i, bf))
    sparse = [*attn, ("w_router", (h, e), h, bf)]
    if c["sigmoid"]:
        sparse.append(("router_bias", (e,), 1.0, f32))
    sparse += [("w_gate", (e, h, mi), h, bf), ("w_up", (e, h, mi), h, bf), ("w_down", (e, mi, h), mi, bf)]
    si = c["shared"] * mi
    if si:
        sparse += [("ws_gate", (h, si), h, bf), ("ws_up", (h, si), h, bf), ("ws_down", (si, h), si, bf)]
    return (("dense", c["dense"], dense), ("sparse", c["l"] - c["dense"], tuple(sparse)))


def init_weights(hf: dict, seed: int) -> dict:
    """A flat dict: ``embed``, ``lm_head`` (untied) and ``<group><l>.<leaf>``
    for layer ``l`` of the dense and of the sparse layers, one jitted draw
    each."""
    c = dims(hf)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    w = {"embed": _draw_jit(next(keys), (c["v"], c["h"]), 1.0, jnp.bfloat16)}
    if not c["tied"]:
        w["lm_head"] = _draw_jit(next(keys), (c["h"], c["v"]), float(c["h"]), jnp.bfloat16)
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
    against through ``kind`` and back; the looked-up embedding (unless it is
    the head too), the router and its bias stay as they are."""
    keep = {"w_router", "router_bias"} | (set() if dims(hf)["tied"] else {"embed"})
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


def _attention(x, w, cos, sin, c):
    """Latent attention, decompressed: every head's own keys and values."""
    t, heads = x.shape[0], c["heads"]
    a = _rms(x, c["eps"])
    q = _mm(_rms(_mm(a, w["w_dq"]), c["eps"]), w["w_uq"]) if c["q_lora"] else _mm(a, w["wq"])
    q = q.reshape(t, heads, c["nope"] + c["rope"])
    q_nope, q_rope = q[..., : c["nope"]], _rope(q[..., c["nope"]:], cos[:, None], sin[:, None])
    dkv = _mm(a, w["w_dkv"])
    latent, k_rope = _rms(dkv[:, : c["r"]], c["eps"]), _rope(dkv[:, c["r"]:], cos, sin)
    k_nope = _mm(latent, w["w_uk"]).reshape(t, heads, c["nope"])
    v = _mm(latent, w["w_uv"]).reshape(t, heads, c["vd"])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scale = 1.0 / math.sqrt(c["nope"] + c["rope"])

    def head(of):
        qn, qr, kn, vh = of
        s = (jnp.matmul(qn, kn.T, precision=HIGHEST) + jnp.matmul(qr, k_rope.T, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HIGHEST)

    by_head = lambda z: z.transpose(1, 0, 2)  # noqa: E731
    o = jax.lax.map(head, (by_head(q_nope), by_head(q_rope), by_head(k_nope), by_head(v)))
    return x + _mm(by_head(o).reshape(t, -1), w["wo"])


def _dense_layer(x, w, cos, sin, c):
    x = _attention(x, w, cos, sin, c)
    u = _rms(x, c["eps"])
    return x + _gated(u, w["w_gate"], w["w_up"], w["w_down"])


def _route(x, w, cos, sin, c):
    """Attention, then the routing of every token: ``(x, u, chosen [T, k],
    g [T, k])``."""
    x = _attention(x, w, cos, sin, c)
    u = _rms(x, c["eps"])
    logits = _mm(u, w["w_router"])
    scores = jax.nn.sigmoid(logits) if c["sigmoid"] else jax.nn.softmax(logits, axis=-1)
    biased = scores + w["router_bias"][None, :] if c["sigmoid"] else scores
    _, chosen = jax.lax.top_k(biased, c["k"])
    g = jnp.take_along_axis(scores, chosen, axis=-1)
    if c["norm"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return x, u, chosen, c["scale"] * g


def _experts(x, u, chosen, g, w, rows_max, c):
    """``x + Shared(u) + sum_e g_e Expert_e(u)``, expert by expert over the
    rows that chose it (at most ``rows_max``, read from the routing on the
    host; the padding's rows are out of range and dropped)."""
    t = u.shape[0]
    out = _gated(u, w["ws_gate"], w["ws_up"], w["ws_down"]) if c["shared"] else jnp.zeros_like(u)

    def expert(e, out):
        hit = chosen == e
        weight = jnp.sum(jnp.where(hit, g, 0.0), axis=-1)
        (rows,) = jnp.nonzero(jnp.any(hit, axis=-1), size=rows_max, fill_value=t)
        y = _gated(u.at[rows].get(mode="fill", fill_value=0.0),
                   w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        scale = weight.at[rows].get(mode="fill", fill_value=0.0)
        return out.at[rows].add(scale[:, None] * y, mode="drop")

    return x + jax.lax.fori_loop(0, c["experts"], expert, out)


def hidden(weights: dict, hf: dict, ids):
    """The trunk: the last block's output ``[len(ids), hidden]`` (float32)
    for the sequence ``ids``, layer by layer."""
    c = dims(hf)
    ids = jnp.asarray(ids, jnp.int32)
    half = c["rope"] // 2
    freqs = 1.0 / (c["theta"] ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = jnp.arange(ids.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    frozen = tuple(sorted(c.items()))
    x = weights["embed"][ids].astype(jnp.float32)
    for layer in range(c["l"]):
        group, index = ("dense", layer) if layer < c["dense"] else ("sparse", layer - c["dense"])
        prefix = f"{group}{index}."
        w = {k[len(prefix):]: v for k, v in weights.items() if k.startswith(prefix)}
        if group == "dense":
            x = _dense_jit(x, w, cos, sin, frozen)
            continue
        x, u, chosen, g = _route_jit(x, w, cos, sin, frozen)
        busiest = int(np.bincount(np.asarray(chosen).ravel(), minlength=c["experts"]).max())
        x = _experts_jit(x, u, chosen, g, w, -(-busiest // ROW_STEP) * ROW_STEP, frozen)
    return x


def logits(weights: dict, hf: dict, x):
    """Final norm and the output head over rows ``x`` of ``hidden``'s output:
    ``[len(x), vocab]`` float32."""
    c = dims(hf)
    if c["tied"]:
        return _tied_head_jit(x, weights["embed"], c["eps"])
    return _head_jit(x, weights["lm_head"], c["eps"])


def forward(weights: dict, hf: dict, ids, rows=None):
    """The two halves together, for tests."""
    x = hidden(weights, hf, ids)
    return logits(weights, hf, x if rows is None else x[jnp.asarray(rows, jnp.int32)])


_dense_jit = jax.jit(lambda x, w, cos, sin, frozen: _dense_layer(x, w, cos, sin, dict(frozen)),
                     static_argnums=(4,))
_route_jit = jax.jit(lambda x, w, cos, sin, frozen: _route(x, w, cos, sin, dict(frozen)),
                     static_argnums=(4,))
_experts_jit = jax.jit(lambda x, u, chosen, g, w, rows_max, frozen:
                       _experts(x, u, chosen, g, w, rows_max, dict(frozen)), static_argnums=(5, 6))
_head_jit = jax.jit(lambda x, lm, eps: _mm(_rms(x, eps), lm), static_argnums=(2,))
_tied_head_jit = jax.jit(lambda x, embed, eps: _mm(_rms(x, eps), embed.T), static_argnums=(2,))
