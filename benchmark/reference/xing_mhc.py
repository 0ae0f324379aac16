"""The plain reference of the ``xing4_0`` family (Xing4.0-29B-A4B): ``hc_mult``
residual streams a token, mixed token by token before and after every
sublayer (manifold-constrained hyper-connections, arXiv:2512.24880, after
Hyper-Connections, arXiv:2409.19606), around DeepSeek-V3 latent attention
with a compressed query and YaRN, and sigmoid-routed experts beside a shared
one.  Straightforward ``jax.numpy``: float32 activations, every product at
precision ``highest``, no cache, no kernels, no batching, nothing absorbed:
one sequence in, every position's hidden state out.

**The residual path.**  A token's state is ``X`` in ``R^{n x C}`` (``n`` =
``hc_mult``, ``C`` = ``hidden_size``); ``X_0`` is its embedding row ``n``
times.  Every sublayer ``f`` (attention or FFN) of every layer has its own
``phi [n C, 2n + n^2]``, ``alpha [3]`` and ``bias [2n + n^2]``, float32:

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     all n C values, no learned scale
    m      = x~ phi
    H_pre  = sigmoid(alpha[0] m[:n] + bias[:n])                in (0, 1)^n
    H_post = 2 sigmoid(alpha[1] m[n:2n] + bias[n:2n])          in (0, 2)^n
    M      = exp(clip(alpha[2] reshape(m[2n:], [n, n]) + reshape(bias[2n:], [n, n]), lo, hi))
    H_res  = hc_sinkhorn_iters x { M <- M / (rowsum(M) + hc_eps) ; M <- M / (colsum(M) + hc_eps) }
    h      = H_pre X                                           [C]
    X'     = H_res X + outer(H_post, f(RMSNorm(h)))

(``lo``, ``hi`` = ``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``.)  After the
last layer the ``n`` streams are summed, then the final RMSNorm and the head
(``logits``).  What the published config does not fix (the statistic's eps
and its having no scale, rows before columns, where ``hc_eps`` and the clamp
enter, replicate in and sum out) is listed under ``assumed`` in the
configuration's file.

**A sublayer**, for its input ``a = RMSNorm(h)`` (eps from the file, learned
norm weights all ones and so left out, no bias anywhere):

1. Attention.  ``q = RMSNorm(a Wdq) Wuq`` (``q_lora_rank``; or ``a Wq`` where
   the file has none), a head ``[q_nope (qk_nope_head_dim) | q_rope
   (qk_rope_head_dim)]``.  ``[c | k_rope] = a Wdkv`` (``kv_lora_rank`` |
   ``qk_rope_head_dim``); ``c <- RMSNorm(c)``.  ``q_rope`` of every head and
   the ONE ``k_rope`` are rotated at the position in split-half pairs, by
   YaRN's frequencies (``_frequencies``, written out here and nowhere
   shared).  ``k_nope_h = c Wuk_h``, ``v_h = c Wuv_h``.  ``score_h(t, s) =
   (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) x scale``, causal,
   softmax in float32, ``scale = (0.1 mscale_all_dim ln(factor) + 1)^2 /
   sqrt(qk_nope_head_dim + qk_rope_head_dim)``; the output is ``concat_h(sum_s
   p_h v_h) Wo``.  (A head at a time: 32 heads' scores of 8,192 x 8,192 are
   8.6 GB beside 11.3 GB of weights.)
2. FFN.  A layer before ``first_k_dense_replace``: ``Wdown(silu(Wgate a) *
   Wup a)``.  Otherwise ``s = sigmoid(a Wr)`` in float32 over all
   ``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b``
   (``n_group`` = ``topk_group`` = 1: no group limit; more groups are
   refused); ``g = routed_scaling_factor x s_chosen / sum(s_chosen)``
   (``norm_topk_prob``); the output is ``Shared(a) + sum_e g_e Expert_e(a)``,
   the shared experts ONE gated MLP of width ``n_shared_experts x
   moe_intermediate_size``.

**Departure from "every expert over every token":** the routing is computed
first and an expert runs over the rows that chose it (gathered, padded to a
length read on the host from the routing's counts; rows of the padding add
nothing).  The sum is the same.

It imports nothing of the program and takes nothing the program made.  The
weights are drawn here from the recipe the program states for this family
served without a checkpoint (models/deepseek.py ``init_params``): one key a
drawn leaf out of ``split(PRNGKey(seed), 32)`` in the program's order, layer
``l`` of a stacked leaf from ``fold_in(key, l)``, ``normal / sqrt(fan_in)``
rounded to bfloat16, the selection bias ``0.01 x normal`` in float32, and
LAST in each group the two sublayers' ``hc_phi`` (``normal / sqrt(n C)``) and
``hc_bias`` (standard normal), float32, ``hc_alpha`` all ones.  The flat dict
names a layer's leaf ``<group><l>.<leaf>``.

``quantize`` makes the control: every bfloat16 matrix a token multiplies
against through float8 (e4m3) and back; the looked-up embedding (where it is
not also the head), the router and its bias, and the float32 mixing leaves
stay.
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
    scaling = hf.get("rope_scaling") or {}
    if scaling and scaling.get("rope_type", scaling.get("type")) != "yarn":
        raise NotImplementedError("only YaRN rope_scaling is in this reference")
    if scaling and scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1):
        raise NotImplementedError("mscale != mscale_all_dim would scale the tables; not in this reference")
    return {
        "n": hf.get("hc_mult") or 1, "hc_iters": hf.get("hc_sinkhorn_iters", 20),
        "hc_eps": hf.get("hc_eps", 1e-6),
        "clamp": (float(hf.get("mhc_h_res_clamp_min", -30)), float(hf.get("mhc_h_res_clamp_max", 30))),
        "yarn": tuple(sorted((k, float(v)) for k, v in scaling.items() if k not in ("type", "rope_type"))),
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
    wide, outs = c["n"] * h, 2 * c["n"] + c["n"] ** 2
    streams = [("hc_phi", (2, wide, outs), wide, f32), ("hc_bias", (2, outs), 1.0, f32)] if c["n"] > 1 else []
    dense = (*attn, ("w_gate", (h, i), h, bf), ("w_up", (h, i), h, bf), ("w_down", (i, h), i, bf), *streams)
    sparse = [*attn, ("w_router", (h, e), h, bf)]
    if c["sigmoid"]:
        sparse.append(("router_bias", (e,), 1.0, f32))
    sparse += [("w_gate", (e, h, mi), h, bf), ("w_up", (e, h, mi), h, bf), ("w_down", (e, mi, h), mi, bf)]
    si = c["shared"] * mi
    if si:
        sparse += [("ws_gate", (h, si), h, bf), ("ws_up", (h, si), h, bf), ("ws_down", (si, h), si, bf)]
    sparse += streams
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
        if c["n"] > 1:
            for layer in range(count):
                w[f"{group}{layer}.hc_alpha"] = jnp.ones((2, 3), jnp.float32)
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
    the head too), the router and its bias and the float32 mixing leaves stay
    as they are."""
    keep = {"w_router", "router_bias", "hc_phi", "hc_alpha", "hc_bias"} | (
        set() if dims(hf)["tied"] else {"embed"})
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


def _attention(h, w, cos, sin, c):
    """Latent attention over ``RMSNorm(h)``, decompressed: every head's own
    keys and values.  The sublayer's output, nothing added."""
    t, heads = h.shape[0], c["heads"]
    a = _rms(h, c["eps"])
    q = _mm(_rms(_mm(a, w["w_dq"]), c["eps"]), w["w_uq"]) if c["q_lora"] else _mm(a, w["wq"])
    q = q.reshape(t, heads, c["nope"] + c["rope"])
    q_nope, q_rope = q[..., : c["nope"]], _rope(q[..., c["nope"]:], cos[:, None], sin[:, None])
    dkv = _mm(a, w["w_dkv"])
    latent, k_rope = _rms(dkv[:, : c["r"]], c["eps"]), _rope(dkv[:, c["r"]:], cos, sin)
    k_nope = _mm(latent, w["w_uk"]).reshape(t, heads, c["nope"])
    v = _mm(latent, w["w_uv"]).reshape(t, heads, c["vd"])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scale = _mscale(c) ** 2 / math.sqrt(c["nope"] + c["rope"])

    def head(of):
        qn, qr, kn, vh = of
        s = (jnp.matmul(qn, kn.T, precision=HIGHEST) + jnp.matmul(qr, k_rope.T, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HIGHEST)

    by_head = lambda z: z.transpose(1, 0, 2)  # noqa: E731
    o = jax.lax.map(head, (by_head(q_nope), by_head(q_rope), by_head(k_nope), by_head(v)))
    return _mm(by_head(o).reshape(t, -1), w["wo"])


def _mscale(c):
    """YaRN's temperature on the softmax scale: ``0.1 mscale_all_dim
    ln(factor) + 1`` (squared by the caller: q and k each carry it)."""
    yarn = dict(c["yarn"])
    if not yarn or yarn.get("factor", 1.0) <= 1.0:
        return 1.0
    return 0.1 * yarn.get("mscale_all_dim", 1.0) * math.log(yarn["factor"]) + 1.0


def _frequencies(c):
    """The ``qk_rope_head_dim / 2`` rotation frequencies.  Without scaling,
    ``theta^(-i / half)``.  YaRN: pair ``i`` turns ``orig x f_i / 2 pi``
    times over the original context; a pair that turns ``beta_fast`` times or
    more keeps its frequency, one that turns ``beta_slow`` times or fewer is
    slowed by ``factor``, and between the two pair indices where exactly
    ``beta_fast`` and ``beta_slow`` turns fit (the first rounded down, the
    second up, both held inside the table) a linear ramp blends the two."""
    half = c["rope"] // 2
    plain = 1.0 / (c["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    yarn = dict(c["yarn"])
    if not yarn:
        return plain

    def pair_turning(times):
        return half * math.log(yarn["original_max_position_embeddings"] / (times * 2 * math.pi)) / math.log(c["theta"])

    first = max(math.floor(pair_turning(yarn.get("beta_fast", 32.0))), 0)
    last = min(math.ceil(pair_turning(yarn.get("beta_slow", 1.0))), half - 1)
    slowed = jnp.clip((jnp.arange(half, dtype=jnp.float32) - first) / max(last - first, 1e-3), 0.0, 1.0)
    return plain * (1.0 - slowed) + plain / yarn["factor"] * slowed


def _coefficients(x, phi, alpha, bias, c):
    """One sublayer's ``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` from
    the streams ``x [T, n, C]``."""
    t, n = x.shape[0], c["n"]
    flat = x.reshape(t, -1)
    m = jnp.matmul(_rms(flat, c["eps"]), phi, precision=HIGHEST)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n: 2 * n] + bias[n: 2 * n])
    res = alpha[2] * m[:, 2 * n:].reshape(t, n, n) + bias[2 * n:].reshape(n, n)
    mat = jnp.exp(jnp.clip(res, *c["clamp"]))
    for _ in range(c["hc_iters"]):
        mat = mat / (jnp.sum(mat, axis=2, keepdims=True) + c["hc_eps"])
        mat = mat / (jnp.sum(mat, axis=1, keepdims=True) + c["hc_eps"])
    return h_pre, h_post, mat


def _mix_in(x, w, which, c):
    """``(h, out)`` for the layer's sublayer ``which`` (0 attention, 1 FFN):
    the sublayer's input ``h [T, C]`` and what ``_mix_out`` needs to write
    its output back.  One stream (``x [T, C]``): ``x`` itself and nothing.
    ``n`` streams (``x [T, n, C]``): ``H_pre X`` and ``(H_post, H_res)``."""
    if c["n"] == 1:
        return x, None
    h_pre, h_post, h_res = _coefficients(
        x, w["hc_phi"][which], w["hc_alpha"][which], w["hc_bias"][which], c)
    return jnp.einsum("tn,tnc->tc", h_pre, x, precision=HIGHEST), (h_post, h_res)


def _mix_out(x, y, out):
    """``x + y``, or ``H_res X + outer(H_post, y)``."""
    if out is None:
        return x + y
    h_post, h_res = out
    return jnp.einsum("tij,tjc->tic", h_res, x, precision=HIGHEST) + h_post[:, :, None] * y[:, None, :]


def _after_attention(x, w, cos, sin, c):
    h, out = _mix_in(x, w, 0, c)
    return _mix_out(x, _attention(h, w, cos, sin, c), out)


def _dense_layer(x, w, cos, sin, c):
    x = _after_attention(x, w, cos, sin, c)
    h, out = _mix_in(x, w, 1, c)
    return _mix_out(x, _gated(_rms(h, c["eps"]), w["w_gate"], w["w_up"], w["w_down"]), out)


def _route(x, w, cos, sin, c):
    """Attention, then the FFN's input and the routing of every token:
    ``(x, out, u, chosen [T, k], g [T, k])``, ``u`` the normalised input and
    ``out`` what ``_mix_out`` takes."""
    x = _after_attention(x, w, cos, sin, c)
    h, out = _mix_in(x, w, 1, c)
    u = _rms(h, c["eps"])
    logits = _mm(u, w["w_router"])
    scores = jax.nn.sigmoid(logits) if c["sigmoid"] else jax.nn.softmax(logits, axis=-1)
    biased = scores + w["router_bias"][None, :] if c["sigmoid"] else scores
    _, chosen = jax.lax.top_k(biased, c["k"])
    g = jnp.take_along_axis(scores, chosen, axis=-1)
    if c["norm"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return x, out, u, chosen, c["scale"] * g


def _experts(x, out, u, chosen, g, w, rows_max, c):
    """``x`` after the FFN ``Shared(u) + sum_e g_e Expert_e(u)``, expert by
    expert over the rows that chose it (at most ``rows_max``, read from the
    routing on the host; the padding's rows are out of range and dropped)."""
    t = u.shape[0]
    y = _gated(u, w["ws_gate"], w["ws_up"], w["ws_down"]) if c["shared"] else jnp.zeros_like(u)

    def expert(e, acc):
        hit = chosen == e
        weight = jnp.sum(jnp.where(hit, g, 0.0), axis=-1)
        (rows,) = jnp.nonzero(jnp.any(hit, axis=-1), size=rows_max, fill_value=t)
        ye = _gated(u.at[rows].get(mode="fill", fill_value=0.0),
                    w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        scale = weight.at[rows].get(mode="fill", fill_value=0.0)
        return acc.at[rows].add(scale[:, None] * ye, mode="drop")

    return _mix_out(x, jax.lax.fori_loop(0, c["experts"], expert, y), out)


def hidden(weights: dict, hf: dict, ids):
    """The trunk: the last block's output, its streams summed, ``[len(ids),
    hidden]`` (float32) for the sequence ``ids``, layer by layer."""
    c = dims(hf)
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = tables(hf, ids.shape[0])
    frozen = tuple(sorted(c.items()))
    x = weights["embed"][ids].astype(jnp.float32)
    if c["n"] > 1:
        x = jnp.repeat(x[:, None, :], c["n"], axis=1)
    for layer in range(c["l"]):
        group, index = ("dense", layer) if layer < c["dense"] else ("sparse", layer - c["dense"])
        prefix = f"{group}{index}."
        w = {k[len(prefix):]: v for k, v in weights.items() if k.startswith(prefix)}
        if group == "dense":
            x = _dense_jit(x, w, cos, sin, frozen)
            continue
        x, out, u, chosen, g = _route_jit(x, w, cos, sin, frozen)
        busiest = int(np.bincount(np.asarray(chosen).ravel(), minlength=c["experts"]).max())
        x = _experts_jit(x, out, u, chosen, g, w, -(-busiest // ROW_STEP) * ROW_STEP, frozen)
    return jnp.sum(x, axis=1) if c["n"] > 1 else x


def tables(hf: dict, length: int):
    """``(cos, sin)`` ``[length, qk_rope_head_dim / 2]`` float32 of positions
    ``0 .. length - 1``, unscaled (YaRN's temperature is on the softmax)."""
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * _frequencies(dims(hf))[None, :]
    return jnp.cos(ang), jnp.sin(ang)


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
_experts_jit = jax.jit(lambda x, out, u, chosen, g, w, rows_max, frozen:
                       _experts(x, out, u, chosen, g, w, rows_max, dict(frozen)), static_argnums=(6, 7))
_head_jit = jax.jit(lambda x, lm, eps: _mm(_rms(x, eps), lm), static_argnums=(2,))
_tied_head_jit = jax.jit(lambda x, embed, eps: _mm(_rms(x, eps), embed.T), static_argnums=(2,))
