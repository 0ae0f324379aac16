"""The plain reference of ``phi4flash`` (Phi-4-mini-flash-reasoning: SambaY
with differential attention, arXiv:2507.06607 and arXiv:2410.05258):
straightforward ``jax.numpy``.

float32 activations, every product at precision ``highest``, no cache, no
kernel, no chunked scan, no paired heads: one sequence in, every position's
hidden state out.  No position is encoded anywhere.  Every layer ``l`` of
``L``: ``x <- x + Mixer_l(LN(x))``, then ``x <- x + W_down(silu(W_gate LN(x))
* W_up LN(x))``; LayerNorm (mean and variance, eps ``layer_norm_eps``; its
weight one and bias zero under the seeded draw, and so left out here).
``Mixer_l(u)``:

- ``l`` even, ``l <= L/2``: Mamba-1 (arXiv:2312.00752).  ``[a; z] = W_in u``;
  ``a_t <- silu(sum_j w_c[j] a_{t-3+j} + b_c)`` (zeros before the first
  token); ``[r; B; C] = W_x a``; ``delta = softplus(W_dt r + b_dt)``; ``h_t =
  exp(delta_t A) h_{t-1} + (delta_t a_t) B_t^T`` with ``A = -exp(A_log)``, as
  a ``lax.scan`` over time; ``y_t = h_t C_t + D a_t``; out ``W_out(y *
  silu(z))``.  Layer ``L/2`` hands ``y`` on: the memory ``m``.
- ``l`` odd, ``l < L/2``: differential attention over the last
  ``sliding_window`` positions (a mask); ``l = L/2 + 1``: over every earlier
  position.  ``[q; k; v] = W_qkv u + b``; query heads ``2i``, ``2i+1`` are pair
  ``i`` and use key/value heads ``2j``, ``2j+1``, ``j = i // (query pairs a KV
  pair)``: ``s1 = softmax(q_2i k_2j^T / sqrt(d))``, ``s2 = softmax(q_2i+1
  k_2j+1^T / sqrt(d))``, ``V = [v_2j | v_2j+1]``, ``o_i = s1 V - lambda s2 V``,
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)``,
  ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_i <- RMSNorm(o_i) gamma (1 -
  lambda_init(l))``; the pairs side by side through ``W_o`` (+ bias).
- ``l`` even, ``l > L/2``: gated memory unit ``W_out(silu(W_in u) * m)``.
- ``l`` odd, ``l > L/2 + 1``: cross attention: ``q = W_q u + b`` only; the
  keys and values are layer ``L/2 + 1``'s; the same differential form.

After the last layer (``logits``): LayerNorm, then ``x E^T`` (tied).

Departures from the published description, each an assumption the
configuration's file lists: the sizes the config has no key for (``d_state``
16, ``d_conv`` 4, expand 2, ``dt_rank`` ceil(hidden / 16); a key
``mamba_<name>`` overrides one, for the tests' small size); the RMSNorm of a
pair has eps ``layer_norm_eps``; attention is computed a block of queries at
a time (the same numbers; so that ``[heads, t, t]`` scores need not exist).

It imports nothing of the program and takes nothing the program made.  The
weights are drawn here from the recipe the program states for the family
(models/phi4flash.py ``init_params``): one key a leaf out of
``split(PRNGKey(seed), 96)`` in the order of ``_TABLE`` (the embedding first;
a leaf that is not drawn takes its key all the same), layer ``l`` of a leaf
from ``fold_in(key, l)``.  The flat dict names a layer's leaf
``<group><l>.<leaf>``; leaves that are all ones or zeros are left out.

``quantize`` makes the control: every matrix a token multiplies against
through float8 (e4m3) and back, the tied embedding (the head) among them,
scaled per output channel; ``A_log``, ``D``, ``b_dt``, the taps, the
``lambda`` vectors stay.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def dims(hf: dict) -> dict:
    h, heads, layers = hf["hidden_size"], hf["num_attention_heads"], hf["num_hidden_layers"]
    expand = int(hf.get("mamba_expand", 2))
    return {
        "h": h, "i": hf["intermediate_size"], "l": layers, "heads": heads,
        "kv": hf["num_key_value_heads"], "d": h // heads, "v": hf["vocab_size"],
        "eps": hf.get("layer_norm_eps", 1e-5), "window": hf["sliding_window"],
        "n": int(hf.get("mamba_d_state", 16)), "taps": int(hf.get("mamba_d_conv", 4)),
        "di": expand * h, "r": int(hf.get("mamba_dt_rank") or math.ceil(h / 16)),
    }


def kind(c: dict, layer: int) -> str:
    half = c["l"] // 2
    if layer % 2 == 0:
        return "ssm" if layer <= half else "gmu"
    return "attn" if layer <= half + 1 else "cross"


def _table(c: dict) -> dict:
    """``group -> ((leaf, shape, how), ...)`` in the program's order; how: a
    fan-in (``normal / sqrt(fan_in)``, bfloat16) or the name of a float32
    rule; None: not drawn (ones or zeros), but its key is taken."""
    h, i, di, n, r, taps = c["h"], c["i"], c["di"], c["n"], c["r"], c["taps"]
    qd, kvd, d = c["heads"] * c["d"], c["kv"] * c["d"], c["d"]
    norms = (("ln1_w", None, None), ("ln1_b", None, None),
             ("ln2_w", None, None), ("ln2_b", None, None))
    mlp = (("w_gate", (h, i), h), ("w_up", (h, i), h), ("w_down", (i, h), i))
    diff = (("lq1", (d,), "lambda"), ("lk1", (d,), "lambda"), ("lq2", (d,), "lambda"),
            ("lk2", (d,), "lambda"), ("gamma", None, None))
    return {
        "ssm": (*norms, ("w_in", (h, 2 * di), h), ("conv_w", (taps, di), "conv"),
                ("conv_b", None, None), ("w_x", (di, r + 2 * n), di), ("w_dt", (r, di), r),
                ("b_dt", (di,), "b_dt"), ("a_log", None, None), ("d_skip", None, None),
                ("w_out", (di, h), di), *mlp),
        "attn": (*norms, ("wqkv", (h, qd + 2 * kvd), h), ("bqkv", None, None),
                 ("wo", (qd, h), qd), ("bo", None, None), *diff, *mlp),
        "gmu": (*norms, ("w_in", (h, di), h), ("w_out", (di, h), di), *mlp),
        "cross": (*norms, ("wq", (h, qd), h), ("bq", None, None), ("wo", (qd, h), qd),
                  ("bo", None, None), *diff, *mlp),
    }


def _draw(key, shape, how):
    f32 = jnp.float32
    if how == "b_dt":
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (math.log(0.1) - math.log(0.001))
                     + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if how == "lambda":
        return 0.1 * jax.random.normal(key, shape, f32)
    if how == "conv":
        return jax.random.normal(key, shape, f32) / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, f32) / math.sqrt(how)).astype(jnp.bfloat16)


_draw_jit = jax.jit(_draw, static_argnums=(1, 2))


def init_weights(hf: dict, seed: int) -> dict:
    c = dims(hf)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 96))
    w = {"embed": _draw_jit(next(keys), (c["v"], c["h"]), 1.0)}
    count = {g: sum(kind(c, layer) == g for layer in range(c["l"])) for g in ("ssm", "attn", "gmu", "cross")}
    for group, leaves in _table(c).items():
        for leaf, shape, how in leaves:
            key = next(keys)
            if how is None:
                continue
            for layer in range(count[group]):
                w[f"{group}{layer}.{leaf}"] = _draw_jit(jax.random.fold_in(key, layer), shape, how)
    return w


def _round_fp8(w, axis):
    """Through float8 (e4m3), scaled per output channel (the maximum over the
    input axis ``axis``) to the type's range, and back to bfloat16."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / 240.0
    q = jax.lax.reduce_precision(w32 / jnp.maximum(scale, 1e-30), exponent_bits=4, mantissa_bits=3)
    return (q * scale).astype(jnp.bfloat16)


_ROUND = {"fp8": jax.jit(_round_fp8, static_argnums=(1,))}
_MATRICES = ("w_in", "w_x", "w_dt", "w_out", "wqkv", "wq", "wo", "w_gate", "w_up", "w_down")


def quantize(leaves: dict, kind_: str, hf: dict) -> dict:
    """The control's form of ``leaves``: every matrix a token multiplies
    against through ``kind_`` and back.  The tied embedding is the head (a
    word's row is an output channel); the float32 leaves stay."""
    rounder = _ROUND[kind_]
    out = {}
    for name, value in leaves.items():
        if name == "embed":
            out[name] = rounder(value, -1)
        elif name.rsplit(".", 1)[-1] in _MATRICES:
            out[name] = rounder(value, -2)
        else:
            out[name] = value
    return out


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HIGHEST)


def _ln(x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def _mlp(x, w, c):
    u = _ln(x, c["eps"])
    return x + _mm(jax.nn.silu(_mm(u, w["w_gate"])) * _mm(u, w["w_up"]), w["w_down"])


def _ssm_layer(x, w, c):
    """Returns (x, the memory ``y``)."""
    t, di, n, r = x.shape[0], c["di"], c["n"], c["r"]
    u = _ln(x, c["eps"])
    az = _mm(u, w["w_in"])
    a, z = az[:, :di], az[:, di:]
    padded = jnp.concatenate([jnp.zeros((c["taps"] - 1, di), a.dtype), a])
    a = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + t] for j in range(c["taps"])))   # b_c = 0
    rbc = _mm(a, w["w_x"])
    delta = jax.nn.softplus(_mm(rbc[:, :r], w["w_dt"]) + w["b_dt"])
    b, cc = rbc[:, r:r + n], rbc[:, r + n:]
    a_neg = -jnp.arange(1, n + 1, dtype=jnp.float32)      # A = -exp(A_log), A_log = log(1 .. n)

    def step(h, row):
        a_t, delta_t, b_t, c_t = row
        h = jnp.exp(delta_t[:, None] * a_neg[None, :]) * h + (delta_t * a_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (a, delta, b, cc))
    y = y + a                                              # D = 1
    return _mlp(x + _mm(y * jax.nn.silu(z), w["w_out"]), w, c), y


def _lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _diff_attention(q, k, v, w, layer, window, c):
    """``q [t, heads, d]``, ``k``, ``v`` ``[t, kv, d]`` -> ``[t, heads x d]``."""
    t, d = q.shape[0], c["d"]
    pairs, kv_pairs = c["heads"] // 2, c["kv"] // 2
    init = _lambda_init(layer)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + init
    # [which of the pair, kv pair, query pairs of it, t, d]
    q = q.reshape(t, kv_pairs, pairs // kv_pairs, 2, d).transpose(3, 1, 2, 0, 4)
    k = k.reshape(t, kv_pairs, 2, d).transpose(2, 1, 0, 3)
    v = v.reshape(t, kv_pairs, 2 * d).transpose(1, 0, 2)       # [kv pair, t, 2d]
    key_pos = jnp.arange(t)
    block = t if t <= QUERY_BLOCK else math.gcd(t, QUERY_BLOCK)    # check.py pads to 256s

    def one(first):
        q_blk = jax.lax.dynamic_slice_in_dim(q, first, block, axis=3)
        diff = (first + jnp.arange(block))[:, None] - key_pos[None, :]
        mask = diff >= 0
        if window:
            mask = mask & (diff < window)
        s = jnp.einsum("hjgqd,hjsd->hjgqs", q_blk, k, precision=HIGHEST) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hjgqs,jse->hjgqe", p, v, precision=HIGHEST)
        o = o[0] - lam * o[1]                                  # [kv pair, g, block, 2d]
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c["eps"])
        return (o * (1.0 - init)).transpose(2, 0, 1, 3).reshape(block, -1)     # gamma = 1

    return jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, -1)


def _attn_layer(x, w, layer, window, c):
    """Returns (x, this layer's keys, values)."""
    t, qd, kvd = x.shape[0], c["heads"] * c["d"], c["kv"] * c["d"]
    qkv = _mm(_ln(x, c["eps"]), w["wqkv"])                   # b = 0
    q = qkv[:, :qd].reshape(t, c["heads"], c["d"])
    k = qkv[:, qd:qd + kvd].reshape(t, c["kv"], c["d"])
    v = qkv[:, qd + kvd:].reshape(t, c["kv"], c["d"])
    x = x + _mm(_diff_attention(q, k, v, w, layer, window, c), w["wo"])
    return _mlp(x, w, c), k, v


def _gmu_layer(x, w, m, c):
    u = _ln(x, c["eps"])
    return _mlp(x + _mm(jax.nn.silu(_mm(u, w["w_in"])) * m, w["w_out"]), w, c)


def _cross_layer(x, w, k, v, layer, c):
    q = _mm(_ln(x, c["eps"]), w["wq"]).reshape(x.shape[0], c["heads"], c["d"])
    return _mlp(x + _mm(_diff_attention(q, k, v, w, layer, 0, c), w["wo"]), w, c)


def _static(fn, *static):
    return jax.jit(lambda *a: fn(*a[:-1], dict(a[-1])), static_argnums=static)


_ssm_jit = _static(_ssm_layer, 2)
_attn_jit = _static(_attn_layer, 2, 3, 4)
_gmu_jit = _static(_gmu_layer, 3)
_cross_jit = _static(_cross_layer, 4, 5)


def hidden(weights: dict, hf: dict, ids):
    """The trunk: the last block's output ``[len(ids), hidden]`` (float32)."""
    c = dims(hf)
    frozen = tuple(sorted(c.items()))
    table = _table(c)
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    seen = {"ssm": 0, "attn": 0, "gmu": 0, "cross": 0}
    m = k = v = None
    for layer in range(c["l"]):
        group = kind(c, layer)
        w = {leaf: weights[f"{group}{seen[group]}.{leaf}"] for leaf, _, how in table[group] if how is not None}
        seen[group] += 1
        if group == "ssm":
            x, m = _ssm_jit(x, w, frozen)
        elif group == "attn":
            window = c["window"] if layer < c["l"] // 2 else 0
            x, k, v = _attn_jit(x, w, layer, window, frozen)
        elif group == "gmu":
            x = _gmu_jit(x, w, m, frozen)
        else:
            x = _cross_jit(x, w, k, v, layer, frozen)
    return x


def logits(weights: dict, hf: dict, x):
    """Final LayerNorm and the tied head over rows ``x`` of ``hidden``'s
    output: ``[len(x), vocab]`` float32."""
    return _head_jit(x, weights["embed"], dims(hf)["eps"])


def forward(weights: dict, hf: dict, ids, rows=None):
    """The two halves together, for tests."""
    x = hidden(weights, hf, ids)
    return logits(weights, hf, x if rows is None else x[jnp.asarray(rows, jnp.int32)])


_head_jit = jax.jit(
    lambda x, embed, eps: jnp.matmul(_ln(x, eps), embed.astype(jnp.float32).T, precision=HIGHEST),
    static_argnums=(2,),
)
