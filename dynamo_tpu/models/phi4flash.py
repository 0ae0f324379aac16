"""Phi-4-flash family (``model_type`` ``phi4flash``, Phi-4-mini-flash-reasoning;
arXiv:2507.06607 "Decoder-Hybrid-Decoder Architecture": SambaY with
differential attention).

Every layer ``l``: ``x <- x + Mixer_l(LN1(x))``, ``x <- x + W_down(silu(W_gate
LN2(x)) * W_up LN2(x))``; LayerNorm with bias; one LayerNorm and the tied
embedding as the head.  No positional encoding anywhere.  The mixer, by
index (``LayerKind.mixer``):

- ``ssm`` (0, 2, ..., L/2): Mamba-1 (ops/ssm.py): in-projection to ``[a; z]``,
  a causal depthwise convolution of ``d_conv`` taps and silu, ``[r; B; C] =
  W_x a``, ``delta = softplus(W_dt r + b_dt)``, the diagonal recurrence, ``y =
  h C + D a``, out ``W_out(y * silu(z))``.  The LAST of them (layer L/2)
  hands ``y`` on: the memory ``m``.  Its state and taps are cache leaves a
  LANE (``ssm``, ``conv``), not pages;
- ``attn`` (1, 3, ..., L/2 - 1: over the last ``sliding_window`` positions,
  pool ``window``; L/2 + 1: over every earlier position, the ONE layer of
  pool ``kv``): differential attention (arXiv:2410.05258): heads ``2i``,
  ``2i+1`` are pair ``i``, two softmaxes over the same values subtracted,
  ``o_i = s1 V - lambda s2 V``, an RMSNorm over the pair's 128 and the factor
  ``1 - lambda_init``;
- ``gmu`` (L/2 + 2, ...): gated memory unit ``W_out(silu(W_in u) * m)``, ``m``
  the memory of the SAME token.  No state, no cache;
- ``cross`` (L/2 + 3, ...): a query only; keys and values are layer L/2 + 1's
  pages (``LayerKind.writes`` false: it reads pool ``kv`` layer 0).

The layer loop is ``llama._scan_layer_runs`` over three runs of PAIRS:
``L/4 x (ssm, window attn)``, ``1 x (ssm, full attn)``, ``(L/4 - 1) x (gmu,
cross)``; what rides beside ``x`` (the memory, the lanes' state and taps) is
part of the loop's carry.

**Heads of 64 on kernels of 128.**  A key/value PAIR is stored as one
128-wide cache head (``[k_2j | k_2j+1]``, ``[v_2j | v_2j+1]``: half the
published KV heads, the same bytes a token); the kernels get query ``2i`` as
``[q | 0]`` and ``2i+1`` as ``[0 | q]`` (scaled so that their ``1/sqrt(128)``
comes to the published ``1/8``) and hand back ``s1 V`` and ``s2 V``; the
subtraction, the norm and the factor run in XLA (scope ``diff_merge``).
``num_kv_heads`` and ``head_dim`` of the config are the CACHE's (10 and 128
for the published 20 and 64).

Served without: prefix cache and chunked prefill (no continued prefill: the
state at a block boundary is not kept), speculation, pipelined decode, KV
transfer, a mesh, a checkpoint loader.  Weights come from the seed
(``init_params``; benchmark/reference/sambay.py repeats the recipe).
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.exaone_moe import window_pool_blocks  # noqa: F401 (the same rule, at this window)
from dynamo_tpu.models.llama import (
    KvPools,
    LayerKind,
    LayerRun,
    _logits,
    _mlp,
    _scan_layer_runs,
)
from dynamo_tpu.ops.attention import paged_decode_attention, ragged_paged_attention
from dynamo_tpu.ops.norms import layer_norm
from dynamo_tpu.ops.quant import mm
from dynamo_tpu.ops.ssm import (
    conv_state_out,
    conv_taps,
    selective_scan,
    selective_step,
    span_offsets,
)

PAIR = 2    # heads a differential pair, and published KV heads a cache head


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40         # query heads, each qk_dim wide
    num_kv_heads: int = 10      # CACHE heads: the published KV heads in pairs
    head_dim: int = 128         # a cache head's width: PAIR x qk_dim
    window: int = 512           # the window layers' (``sliding_window`` stays
    #                             None: no layer-blind code may apply it)
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    d_inner: int = 5120
    dt_rank: int = 160
    tie_word_embeddings: bool = True
    mlp_activation: str = "silu"
    dtype: Any = jnp.bfloat16

    @property
    def qk_dim(self) -> int:
        return self.head_dim // PAIR

    @property
    def ssm_layers(self) -> int:
        return self.num_layers // 4 + 1

    @property
    def window_layers(self) -> int:
        return self.num_layers // 4

    @property
    def cross_layers(self) -> int:
        return self.num_layers // 4 - 1

    # the layers whose launches walk the whole context: the one that writes
    # the full pool and those that only read it (engine's work counters)
    @property
    def full_layers(self) -> int:
        return 1 + self.cross_layers

    def layer_runs(self) -> tuple[LayerRun, ...]:
        quarter = self.num_layers // 4
        ssm = LayerKind(None, False, None, "ssm", mixer="ssm")
        window = LayerKind(self.window, False, "window", "attn")
        full = LayerKind(None, False, "kv", "attn")
        gmu = LayerKind(None, False, None, "gmu", mixer="gmu")
        cross = LayerKind(None, False, "kv", "cross", mixer="cross", writes=False)
        return (
            LayerRun((ssm, window), (0, 0), quarter, (0, 0)),
            LayerRun((ssm, full), (quarter, quarter), 1, (0, 0)),
            LayerRun((gmu, cross), (0, 0), quarter - 1, (0, 0)),
        )

    def depth(self, group: str, row):
        """The model's layer index of row ``row`` of ``params[group]``."""
        half = self.num_layers // 2
        return {"ssm": 2 * row, "attn": 2 * row + 1,
                "gmu": half + 2 + 2 * row, "cross": half + 3 + 2 * row}[group]

    @classmethod
    def from_hf_config(cls, config: dict | str | Path) -> "Phi4FlashConfig":
        if not isinstance(config, dict):
            config = json.loads(Path(config).read_text())
        layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
        kv_heads = config.get("num_key_value_heads", heads)
        h = config["hidden_size"]
        if config.get("mb_per_layer", 2) != 2:
            raise NotImplementedError(
                f"phi4flash mb_per_layer {config['mb_per_layer']}: only the "
                "alternating pattern (2) is built"
            )
        if layers % 4 or layers < 8:
            raise NotImplementedError(
                f"phi4flash depth {layers}: the self-decoder and the "
                "cross-decoder are halves of whole (mixer, attention) pairs, "
                "at least two each"
            )
        if heads % (PAIR * PAIR) or kv_heads % PAIR or h % heads:
            raise NotImplementedError(
                f"phi4flash heads {heads}/{kv_heads}: differential pairs need "
                "an even number of KV heads and query pairs"
            )
        if config.get("mlp_bias") or config.get("lm_head_bias"):
            raise NotImplementedError("phi4flash mlp_bias / lm_head_bias")
        if config.get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"phi4flash hidden_act {config['hidden_act']!r}")
        if not config.get("tie_word_embeddings", True):
            raise NotImplementedError("phi4flash with an untied head")
        expand = int(config.get("mamba_expand", 2))
        return cls(
            vocab_size=config["vocab_size"], hidden_size=h,
            intermediate_size=config["intermediate_size"], num_layers=layers,
            num_heads=heads, num_kv_heads=kv_heads // PAIR,
            head_dim=PAIR * (h // heads), window=int(config["sliding_window"]),
            max_position_embeddings=config.get("max_position_embeddings", 4096),
            layer_norm_eps=config.get("layer_norm_eps", 1e-5),
            d_state=int(config.get("mamba_d_state", 16)),
            d_conv=int(config.get("mamba_d_conv", 4)),
            d_inner=expand * h,
            dt_rank=int(config.get("mamba_dt_rank") or math.ceil(h / 16)),
        )


def lambda_init(depth):
    """The differential term's start value by the layer's index."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _leaves(cfg: Phi4FlashConfig) -> dict:
    """``group -> (layers, ((leaf, one layer's shape, how it is drawn), ...))``
    in the order ``init_params`` draws them.  How: a number is the fan-in of
    ``normal / sqrt(fan_in)`` in the model's dtype; a string names a float32
    leaf (``ones``, ``zeros``, ``a_log``, ``b_dt``, ``lambda``)."""
    h, i, di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    n, r, taps = cfg.d_state, cfg.dt_rank, cfg.d_conv
    qd, kvd, qk = cfg.num_heads * cfg.qk_dim, cfg.num_kv_heads * cfg.head_dim, cfg.qk_dim
    norms = (("ln1_w", (h,), "ones"), ("ln1_b", (h,), "zeros"),
             ("ln2_w", (h,), "ones"), ("ln2_b", (h,), "zeros"))
    mlp = (("w_gate", (h, i), h), ("w_up", (h, i), h), ("w_down", (i, h), i))
    diff = (("lq1", (qk,), "lambda"), ("lk1", (qk,), "lambda"),
            ("lq2", (qk,), "lambda"), ("lk2", (qk,), "lambda"),
            ("gamma", (cfg.head_dim,), "ones"))
    return {
        "ssm": (cfg.ssm_layers, (
            *norms, ("w_in", (h, 2 * di), h), ("conv_w", (taps, di), "conv"),
            ("conv_b", (di,), "zeros"), ("w_x", (di, r + 2 * n), di),
            ("w_dt", (r, di), r), ("b_dt", (di,), "b_dt"),
            ("a_log", (n, di), "a_log"), ("d_skip", (di,), "ones"),
            ("w_out", (di, h), di), *mlp)),
        "attn": (cfg.window_layers + 1, (
            *norms, ("wqkv", (h, qd + 2 * kvd), h), ("bqkv", (qd + 2 * kvd,), "zeros"),
            ("wo", (qd, h), qd), ("bo", (h,), "zeros"), *diff, *mlp)),
        "gmu": (cfg.cross_layers, (
            *norms, ("w_in", (h, di), h), ("w_out", (di, h), di), *mlp)),
        "cross": (cfg.cross_layers, (
            *norms, ("wq", (h, qd), h), ("bq", (qd,), "zeros"),
            ("wo", (qd, h), qd), ("bo", (h,), "zeros"), *diff, *mlp)),
    }


def _draw(key, shape, how, dtype):
    f32 = jnp.float32
    if how == "ones":
        return jnp.ones(shape, f32)
    if how == "zeros":
        return jnp.zeros(shape, f32)
    if how == "a_log":      # A = -(1 .. d_state) in every channel
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))[:, None], shape)
    if how == "b_dt":       # the inverse softplus of a log-uniform step in [0.001, 0.1]
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (math.log(0.1) - math.log(0.001))
                     + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if how == "lambda":
        return 0.1 * jax.random.normal(key, shape, f32)
    if how == "conv":       # the taps: normal / sqrt(taps), float32
        return jax.random.normal(key, shape, f32) / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, f32) / math.sqrt(how)).astype(dtype)


def init_params(cfg: Phi4FlashConfig, rng: jax.Array) -> dict:
    """Random weights from the seed: ``split(rng, 96)`` hands every leaf its
    key in the order of ``_leaves`` (the embedding first); layer ``l`` of a
    stacked leaf is drawn from ``fold_in(key, l)``.  Projections ``normal /
    sqrt(fan_in)`` in the model's dtype; the state-space layer's ``A_log =
    log(1 .. d_state)``, ``D = 1``, ``b_dt`` the inverse softplus of a
    log-uniform step in [0.001, 0.1], its taps ``normal / sqrt(d_conv)``, the
    four ``lambda`` vectors ``0.1 x normal``, ``gamma`` and the norms' weights
    one, every bias zero: all float32."""
    keys = iter(jax.random.split(rng, 96))
    params: dict = {
        "embed": _draw(next(keys), (cfg.vocab_size, cfg.hidden_size), 1.0, cfg.dtype),
        "final_norm_w": jnp.ones((cfg.hidden_size,), jnp.float32),
        "final_norm_b": jnp.zeros((cfg.hidden_size,), jnp.float32),
    }
    for group, (layers, leaves) in _leaves(cfg).items():
        params[group] = {}
        for leaf, shape, how in leaves:
            key = next(keys)
            params[group][leaf] = jnp.stack([
                _draw(jax.random.fold_in(key, layer), shape, how, cfg.dtype)
                for layer in range(layers)
            ])
    return params


def param_counts(cfg: Phi4FlashConfig) -> dict:
    """``params``: every parameter held (the tied embedding once);
    ``matrix``: those in the model's dtype, which one token multiplies
    against (the embedding as the head); ``float32``: the rest."""
    matrix = small = 0
    for layers, leaves in _leaves(cfg).values():
        for _, shape, how in leaves:
            if isinstance(how, str):
                small += layers * math.prod(shape)
            else:
                matrix += layers * math.prod(shape)
    matrix += cfg.vocab_size * cfg.hidden_size
    small += 2 * cfg.hidden_size
    return {"params": matrix + small, "matrix": matrix, "float32": small}


def param_specs(cfg: Phi4FlashConfig) -> dict:
    """Every leaf whole on every chip: no mesh is built for the family."""
    specs: dict = {"embed": P(None, None), "final_norm_w": P(None), "final_norm_b": P(None)}
    for group, (_, leaves) in _leaves(cfg).items():
        specs[group] = {leaf: P(*(None,) * (1 + len(shape))) for leaf, shape, _ in leaves}
    return specs


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

# the cache leaves that are a LANE's, not pages (engine._pages leaves them alone)
LANE_LEAVES = ("ssm", "conv")


def init_kv_cache(cfg: Phi4FlashConfig, num_blocks: int, block_size: int, dtype=None,
                  *, window_blocks: int | None = None, lanes: int = 1) -> dict:
    """``k`` / ``v``: the ONE full layer's pages (``num_blocks``); ``wk`` /
    ``wv``: the window layers' (``window_blocks`` a layer); ``ssm`` ``[state
    layers, lanes, d_state, d_inner]`` float32 and ``conv`` ``[state layers,
    lanes, d_conv - 1, d_inner]``: each lane's recurrent state and taps.

    A page is stored as ROWS, ``[block_size x heads, 1, width]`` (row =
    position x heads + head: what the kernels fold a page to).  As
    ``[block_size, heads, width]`` with 10 heads the chip's tiles of 8 rows
    would pad every position to 16 heads, and each step program would copy
    both pools into the kernels' form and back (1.9 GB of copies a decode
    step in the first compile for the chip)."""
    dtype = dtype or cfg.dtype
    tail = (block_size * cfg.num_kv_heads, 1, cfg.head_dim)
    if window_blocks is None:
        window_blocks = num_blocks
    full, window = (1, num_blocks, *tail), (cfg.window_layers, window_blocks, *tail)
    return {
        "k": jnp.zeros(full, dtype), "v": jnp.zeros(full, dtype),
        "wk": jnp.zeros(window, dtype), "wv": jnp.zeros(window, dtype),
        "ssm": jnp.zeros((cfg.ssm_layers, lanes, cfg.d_state, cfg.d_inner), jnp.float32),
        "conv": jnp.zeros((cfg.ssm_layers, lanes, cfg.d_conv - 1, cfg.d_inner), cfg.dtype),
    }


def kv_cache_specs(cfg: Phi4FlashConfig) -> dict:
    pages = P(None, None, None, None, None)
    state = P(None, None, None, None)
    return {"k": pages, "v": pages, "wk": pages, "wv": pages, "ssm": state, "conv": state}


def make_rope_tables(cfg: Phi4FlashConfig):
    """No position is encoded: the step programs' two table arguments are
    one number each."""
    return jnp.zeros((1, 1), jnp.float32), jnp.zeros((1, 1), jnp.float32)


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _Rows:
    """What a forward knows of its flat rows, for the mixers that keep a
    state a lane."""

    lane: jnp.ndarray       # [rows] the row's lane, in range
    live: jnp.ndarray       # [rows] whether the row is a token
    pos: jnp.ndarray        # [rows] its position
    off: jnp.ndarray        # [rows] rows of its own span before it
    one_a_lane: bool        # a decode step: row i is lane i's one token


def _ssm_mixer(cfg, u, w, rows: _Rows, ssm, conv):
    """Mamba-1 over a step's rows; returns (out, memory, ssm, conv)."""
    f32, di, n = jnp.float32, cfg.d_inner, cfg.d_state
    az = mm(u, w["w_in"])
    a, z = az[:, :di], az[:, di:]
    taps = conv_taps(a, conv, rows.lane, rows.off, rows.pos == rows.off, cfg.d_conv)
    conv = conv_state_out(taps, conv, rows.lane, rows.live)
    conv_w = w["conv_w"]
    a = jax.nn.silu(sum(
        tap.astype(f32) * conv_w[j] for j, tap in enumerate(taps)
    ) + w["conv_b"])
    rbc = mm(a.astype(u.dtype), w["w_x"]).astype(f32)
    r, b, c = rbc[:, :cfg.dt_rank], rbc[:, cfg.dt_rank:cfg.dt_rank + n], rbc[:, cfg.dt_rank + n:]
    delta = jax.nn.softplus(mm(r.astype(u.dtype), w["w_dt"]).astype(f32) + w["b_dt"])
    a_neg = -jnp.exp(w["a_log"])
    fresh = rows.pos == 0
    if rows.one_a_lane:
        y, ssm = selective_step(a, delta, b, c, a_neg, rows.live, fresh, ssm)
    else:
        y, ssm = selective_scan(a, delta, b, c, a_neg, rows.lane, rows.live, fresh, ssm)
    y = y + w["d_skip"] * a
    out = mm((y * jax.nn.silu(z.astype(f32))).astype(u.dtype), w["w_out"])
    return out, y.astype(u.dtype), ssm, conv


def _paired_queries(cfg, q):
    """``[rows, heads x qk_dim]`` -> ``[rows, heads, head_dim]``: query ``2i``
    in the first half of its pair's width, ``2i+1`` in the second, zeros in
    the other, times sqrt(PAIR) (the kernels scale by ``1 / sqrt(head_dim)``;
    the model's scale is ``1 / sqrt(qk_dim)``)."""
    rows = q.shape[0]
    q = q.reshape(rows, cfg.num_heads // PAIR, PAIR, 1, cfg.qk_dim)
    place = (jnp.eye(PAIR, dtype=jnp.float32) * math.sqrt(PAIR)).astype(q.dtype)
    return (q * place[:, :, None]).reshape(rows, cfg.num_heads, cfg.head_dim)


def _diff_merge(cfg, out, w, depth):
    """``out [rows, heads, head_dim]`` (``s1 V`` at head ``2i``, ``s2 V`` at
    ``2i+1``) -> ``[rows, heads x qk_dim]``: the pair's difference, its
    RMSNorm over ``head_dim`` and the factor ``1 - lambda_init``."""
    with jax.named_scope("diff_merge"):
        f32 = jnp.float32
        rows = out.shape[0]
        init = lambda_init(depth)
        lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) - jnp.exp(jnp.sum(w["lq2"] * w["lk2"]))
               + init)
        o = out.astype(f32).reshape(rows, cfg.num_heads // PAIR, PAIR, cfg.head_dim)
        o = o[:, :, 0] - lam * o[:, :, 1]
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.layer_norm_eps)
        o = o * w["gamma"] * (1.0 - init)
        return o.reshape(rows, -1).astype(out.dtype)


def _layer(cfg: Phi4FlashConfig, attend, rows: _Rows, carry, w, k_pages, v_pages, at):
    """One layer, whatever its mixer.  ``carry``: ``x``, the memory ``m``, the
    lanes' ``ssm`` and ``conv`` leaves (all state layers), and ``kept`` (what
    a forward without a paged read hands from the full layer to the cross
    layers; None otherwise).  ``attend(q, k, v, k_pages, v_pages, at, kept)
    -> (out, k_pages, v_pages, kept)`` is the forward's own (``k`` and ``v``
    None for a layer that writes nothing)."""
    x, mixer = carry["x"], at.kind.mixer
    t = x.shape[0]
    u = layer_norm(x, w["ln1_w"], w["ln1_b"], cfg.layer_norm_eps)
    carry = dict(carry)
    if mixer == "ssm":
        layer = w.index
        take = lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)  # noqa: E731
        out, carry["m"], ssm, conv = _ssm_mixer(
            cfg, u, w, rows, take(carry["ssm"]), take(carry["conv"]))
        carry["ssm"] = jax.lax.dynamic_update_index_in_dim(carry["ssm"], ssm, layer, 0)
        carry["conv"] = jax.lax.dynamic_update_index_in_dim(carry["conv"], conv, layer, 0)
    elif mixer == "gmu":
        with jax.named_scope("gmu"):
            out = mm(jax.nn.silu(mm(u, w["w_in"])) * carry["m"], w["w_out"])
    else:
        qd, kvd = cfg.num_heads * cfg.qk_dim, cfg.num_kv_heads * cfg.head_dim
        if mixer == "cross":
            q, k, v = mm(u, w["wq"]) + w["bq"].astype(u.dtype), None, None
        else:
            qkv = mm(u, w["wqkv"]) + w["bqkv"].astype(u.dtype)
            q = qkv[:, :qd]
            k = qkv[:, qd:qd + kvd].reshape(t, cfg.num_kv_heads, cfg.head_dim)
            v = qkv[:, qd + kvd:].reshape(t, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope("cross_attn" if mixer == "cross" else "self_attn"):
            attn, k_pages, v_pages, carry["kept"] = attend(
                _paired_queries(cfg, q), k, v, k_pages, v_pages, at, carry["kept"])
        merged = _diff_merge(cfg, attn, w, cfg.depth(at.kind.group, w.index))
        out = mm(merged, w["wo"]) + w["bo"].astype(u.dtype)
    x = x + out.astype(x.dtype)
    with jax.named_scope("mlp"):
        x = x + _mlp(
            layer_norm(x, w["ln2_w"], w["ln2_b"], cfg.layer_norm_eps),
            w["w_gate"], w["w_up"], w["w_down"], cfg.mlp_activation,
        )
    carry["x"] = x
    return carry, k_pages, v_pages


def _trunk(params, cfg: Phi4FlashConfig, token_ids, kv_cache, rows: _Rows, attend,
           kept=None):
    """Embedding, the three runs, the final norm: ``(x [rows, hidden], cache)``."""
    x = params["embed"][token_ids].astype(cfg.dtype)
    carry = {
        "x": x, "m": jnp.zeros((x.shape[0], cfg.d_inner), cfg.dtype),
        "ssm": kv_cache["ssm"], "conv": kv_cache["conv"], "kept": kept,
    }
    pages = {name: leaf for name, leaf in kv_cache.items() if name not in LANE_LEAVES}

    def layer(carry, w, k_pages, v_pages, at):
        return _layer(cfg, attend, rows, carry, w, k_pages, v_pages, at)

    carry, pages = _scan_layer_runs(layer, carry, params, pages, cfg.layer_runs())
    x = layer_norm(carry["x"], params["final_norm_w"], params["final_norm_b"],
                   cfg.layer_norm_eps)
    return x, {**pages, "ssm": carry["ssm"], "conv": carry["conv"]}


# ---------------------------------------------------------------------------
# Forwards
# ---------------------------------------------------------------------------

_QUERY_BLOCK = 512


def _dense_attention(q, k, v, seq_len, window):
    """Causal attention of a prompt's rows over its own keys, a block of
    queries at a time (a window layer needs ``window`` keys a query, the full
    layer must not hold ``[heads, t, t]`` scores)."""
    t, heads, d = q.shape
    kvh = k.shape[1]
    block = min(_QUERY_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, kvh, heads // kvh, d)
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    key_pos = jnp.arange(t)

    def one(args):
        q_blk, first = args
        q_pos = first + jnp.arange(block)
        diff = q_pos[:, None] - key_pos[None, :]
        mask = (diff >= 0) & (key_pos[None, :] < seq_len)
        if window is not None:
            mask = mask & (diff < window)
        s = jnp.einsum("qkgd,skd->kgqs", q_blk.astype(jnp.float32), k32) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v32)

    out = jax.lax.map(one, (qb, jnp.arange(qb.shape[0]) * block))
    return out.reshape(-1, heads, d)[:t].astype(q.dtype)


def _block_size(cfg: Phi4FlashConfig, kv_cache: dict) -> int:
    """Tokens a page (the pages are stored as rows: ``init_kv_cache``)."""
    return kv_cache["k"].shape[2] // cfg.num_kv_heads


def _as_pages(cfg: Phi4FlashConfig, pages, block_size: int):
    """Stored pages ``[n, block_size x heads, 1, width]`` as the kernels take
    them, ``[n, block_size, heads, width]`` (the same bytes: the kernels fold
    the two axes again at once)."""
    return pages.reshape(pages.shape[0], block_size, cfg.num_kv_heads, cfg.head_dim)


def _write_kv(cfg, at, block_size, k_pages, v_pages, k, v, slots):
    """Write rows' keys and values ``[rows, heads, width]`` at ``slots``: the
    token slots of THIS layer's pool (``block x block_size + offset``; at or
    past the layer's end: no token, nothing written)."""
    heads = cfg.num_kv_heads
    total = k_pages.shape[0] * k_pages.shape[1]
    first = jnp.where(
        slots < at.num_blocks * block_size, (slots + at.base * block_size) * heads, total
    )
    rows = first[:, None] + jnp.arange(heads, dtype=first.dtype)

    def put(pages, new):
        flat = pages.reshape(total, 1, cfg.head_dim)
        new = new.reshape(-1, heads, 1, cfg.head_dim).astype(pages.dtype)
        return flat.at[rows].set(new, mode="drop").reshape(pages.shape)

    with jax.named_scope("kv_write"):
        return put(k_pages, k), put(v_pages, v)


def _pool_slots(at, block_size, slots, tables, lane, pos, live):
    """``slots`` (the host's, of the full pool) or, for a layer of the window
    pool, the slots of the tokens at ``pos`` of ``lane`` in THAT pool."""
    if at.kind.pool != "window":
        return slots
    block = tables.window[lane, pos // block_size]
    return jnp.where(
        live, block * block_size + pos % block_size, at.num_blocks * block_size
    )


def phi4flash_forward_prefill(
    params: dict, cfg: Phi4FlashConfig,
    token_ids: jnp.ndarray,     # [seq_pad] int32
    kv_cache: dict,
    block_ids: KvPools,         # [max_blocks] int32 of each pool
    seq_len: jnp.ndarray,       # scalar int32: valid tokens
    start_pos: jnp.ndarray,     # scalar int32: 0 (no continued prefill)
    cos, sin, *, lane,
) -> tuple[jnp.ndarray, dict]:
    """One prompt whole, from position 0, into lane ``lane``'s state and its
    pages.  Returns (last-token logits [vocab], new cache)."""
    s = token_ids.shape[0]
    bs = _block_size(cfg, kv_cache)
    t = jnp.arange(s, dtype=jnp.int32)
    live = t < seq_len
    rows = _Rows(jnp.full((s,), lane, jnp.int32), live, t, t, False)

    def attend(q, k, v, k_pages, v_pages, at, kept):
        if at.kind.writes:
            ids = at.pick(block_ids)
            slots = jnp.where(live, ids[t // bs] * bs + t % bs, at.num_blocks * bs)
            k_pages, v_pages = _write_kv(cfg, at, bs, k_pages, v_pages, k, v, slots)
            if at.kind.pool == "kv":    # the cross layers attend these rows
                kept = (k, v)
        else:
            k, v = kept
        with jax.named_scope("attn"):
            out = _dense_attention(q, k, v, seq_len, at.kind.window)
        return out, k_pages, v_pages, kept

    none = jnp.zeros((s, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
    x, kv_cache = _trunk(params, cfg, token_ids, kv_cache, rows, attend, (none, none))
    logits = _logits(params, cfg, x[jnp.maximum(seq_len - 1, 0)][None])[0]
    return logits.astype(jnp.float32), kv_cache


def phi4flash_forward_decode(
    params: dict, cfg: Phi4FlashConfig,
    token_ids: jnp.ndarray,     # [lanes] int32
    kv_cache: dict,
    block_tables: KvPools,      # [lanes, max_blocks] int32 of each pool
    context_lens: jnp.ndarray,  # [lanes] int32 INCLUDING this token (0: no token)
    slot_ids: jnp.ndarray,      # [lanes] int32 flat slot of the full pool
    cos, sin, *, attention: str = "jax",
) -> tuple[jnp.ndarray, dict]:
    """One token a lane.  Returns (logits [lanes, vocab], cache)."""
    lanes = token_ids.shape[0]
    bs = _block_size(cfg, kv_cache)
    lane = jnp.arange(lanes, dtype=jnp.int32)
    positions = jnp.maximum(context_lens - 1, 0)
    live = context_lens > 0
    rows = _Rows(lane, live, positions, jnp.zeros_like(lane), True)

    def attend(q, k, v, k_pages, v_pages, at, kept):
        if at.kind.writes:
            slots = _pool_slots(at, bs, slot_ids, block_tables, lane, positions, live)
            k_pages, v_pages = _write_kv(cfg, at, bs, k_pages, v_pages, k, v, slots)
        tables, window = at.pick(block_tables), at.kind.window
        with jax.named_scope("attn"):
            if attention.startswith("pallas"):
                from dynamo_tpu.ops.pallas import paged_attention_decode

                k_read, v_read, tables = at.on_chip(k_pages, v_pages, tables)
                out = paged_attention_decode(
                    q, _as_pages(cfg, k_read, bs), _as_pages(cfg, v_read, bs), tables,
                    context_lens, interpret=attention == "pallas_interpret",
                    sliding_window=window,
                )
            else:
                out = paged_decode_attention(
                    q, _as_pages(cfg, k_pages, bs), _as_pages(cfg, v_pages, bs),
                    at.blocks(tables), context_lens, sliding_window=window,
                )
        return out, k_pages, v_pages, kept

    x, kv_cache = _trunk(params, cfg, token_ids, kv_cache, rows, attend)
    return _logits(params, cfg, x).astype(jnp.float32), kv_cache


def phi4flash_forward_unified(
    params: dict, cfg: Phi4FlashConfig,
    token_ids: jnp.ndarray,     # [T] int32: flat ragged token batch
    kv_cache: dict,
    block_tables: KvPools, context_lens, token_pos, token_slot, token_lane,
    span_lane: KvPools, span_first: KvPools, span_count: KvPools, kv_steps: KvPools,
    sample_rows, cos, sin, *, attention: str = "jax", tb_tokens: int = 8,
) -> tuple[jnp.ndarray, dict]:
    """Prompt spans and decode rows of different lanes in one launch
    (``llama.llama_forward_unified``'s arguments).  Returns (logits [lanes,
    vocab] at each lane's last row, cache)."""
    lanes = context_lens.shape[0]
    bs = _block_size(cfg, kv_cache)
    positions = jnp.maximum(token_pos, 0)
    live = (token_pos >= 0) & (token_lane >= 0) & (token_lane < lanes)
    lane = jnp.clip(token_lane, 0, lanes - 1)
    rows = _Rows(lane, live, positions, span_offsets(token_lane, token_pos, live), False)

    def attend(q, k, v, k_pages, v_pages, at, kept):
        if at.kind.writes:
            slots = _pool_slots(at, bs, token_slot, block_tables, lane, positions, live)
            k_pages, v_pages = _write_kv(cfg, at, bs, k_pages, v_pages, k, v, slots)
        tables, window = at.blocks(at.pick(block_tables)), at.kind.window
        k_read, v_read = _as_pages(cfg, k_pages, bs), _as_pages(cfg, v_pages, bs)
        with jax.named_scope("attn"):
            if attention.startswith("pallas"):
                from dynamo_tpu.ops.pallas import ragged_paged_attention as ragged_kernel

                out = ragged_kernel(
                    q, k_read, v_read, token_lane, token_pos, tables,
                    at.pick(span_lane), at.pick(span_first), at.pick(span_count),
                    at.pick(kv_steps), tb_tokens=tb_tokens,
                    interpret=attention == "pallas_interpret", sliding_window=window,
                )
            else:
                out = ragged_paged_attention(
                    q, k_read, v_read, tables, context_lens, token_lane, token_pos,
                    sliding_window=window,
                )
        return out, k_pages, v_pages, kept

    x, kv_cache = _trunk(params, cfg, token_ids, kv_cache, rows, attend)
    return _logits(params, cfg, x[sample_rows]).astype(jnp.float32), kv_cache
