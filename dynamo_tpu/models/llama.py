"""Llama-family model (Llama 2/3, DeepSeek-R1-Distill-Llama, Qwen2-class
geometries via config).

TPU-first design decisions:
- layer weights stacked on a leading axis and iterated with ``lax.scan``: one
  compiled layer body for each RUN of alike layers.  A llama-like model is
  one run (one body regardless of depth); a model whose layers differ in
  kind (``LayerKind``: window or full attention, rotated or not, which
  cache pool, dense MLP or experts: models/exaone_moe.py) is a few runs,
  one scan each, inside the same step program (``_scan_layer_runs``);
- tensor parallelism by sharding annotation only: params carry
  ``PartitionSpec``s over mesh axis ``tp``; XLA/GSPMD inserts the
  all-reduces (no hand-written collectives in the model);
- paged KV cache, stored ``[layers, num_blocks, block_size, kv_heads,
  head_dim]``; a forward views it as flat pages ``[layers * num_blocks,
  ...]`` and carries THAT through the layer scan (``_scan_layers``): every
  layer scatters into and attends over the one donated buffer at its own
  page offset, nothing is copied or stacked back (the one-query decode
  kernel alone reads a slice of its layer, where that stays in the chip's
  fast memory: ``_LayerPages.on_chip``);
- bf16 params/activations, fp32 softmax/norms.

The reference has no model code (engines own it); this replaces the
vLLM/TRT-LLM model layer for the native TPU engine (SURVEY.md §2.3).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from collections.abc import Mapping
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.attention import (
    dense_causal_attention,
    gather_prefix_kv,
    paged_decode_attention,
    paged_window_attention,  # noqa: F401 — re-exported for tests
    prefill_attention_with_prefix,
    ragged_paged_attention,
    window_attention,
    write_decode_kv,
    write_prefill_kv,
)
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.quant import mm
from dynamo_tpu.ops.rope import apply_rope, rope_table


@dataclass(frozen=True)
class LayerKind:
    """What one layer of the shared block is, where a model's layers differ
    (static: a run of alike layers compiles to one scan body)."""

    window: int | None      # its attention's sliding window (None = full causal)
    rope: bool              # whether q and k are rotated
    # "kv": the full-length cache pool | "window": the window pool | None: a
    # mixer that keeps no pages (a state-space layer, a gated memory unit)
    pool: str | None
    group: str              # the stacked weights its layers lie in (``params[group]``)
    # WHICH MIXER the layer runs, for a family whose block has several
    # (models/phi4flash.py); the shared block knows only "attn"
    mixer: str = "attn"
    # whether the layer WRITES its pool's pages; one that does not reads the
    # pages of the pool's layer ``pool_start`` (its run's every step the same)
    writes: bool = True


@dataclass(frozen=True)
class LayerRun:
    """``count`` consecutive layers of one kind: rows ``start ...`` of
    ``params[kind.group]``, layers ``pool_start ...`` of their cache pool.

    Where a model's pattern ALTERNATES (state-space, attention, state-space,
    ...), a run is ``count`` PERIODS: ``kind``, ``start`` and ``pool_start``
    are then tuples, one entry a layer of the period, and one scan step runs
    the period's layers in order (32 runs of one layer would compile 32
    bodies; three runs of pairs compile three).  A period touches ONE pool."""

    kind: LayerKind | tuple[LayerKind, ...]
    start: int | tuple[int, ...]
    count: int
    pool_start: int | tuple[int, ...]

    @property
    def period(self) -> tuple[tuple[LayerKind, int, int], ...]:
        """``(kind, start, pool_start)`` of each layer of one step."""
        if isinstance(self.kind, LayerKind):
            return ((self.kind, self.start, self.pool_start),)
        return tuple(zip(self.kind, self.start, self.pool_start))


class KvPools(NamedTuple):
    """One value for each of the two cache pools of a model with window
    layers (block tables, prefill block ids, the ragged kernel's span
    lists): a layer takes its pool's (``_LayerPages.pick``).  A model with
    one pool hands plain arrays."""

    full: Any
    window: Any


# the leaves of a cache with a window pool: pool name -> (keys, values)
POOL_LEAVES = {"kv": ("k", "v"), "window": ("wk", "wv")}


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    # qkv projection biases (Qwen2-family geometry; llama proper has none)
    attention_bias: bool = False
    # per-head RMSNorm on q/k after projection, before rope (Qwen3 geometry)
    qk_norm: bool = False
    # HF rope_scaling dict: "linear" | "llama3" | "yarn" (ops/rope.py)
    rope_scaling: Any = None
    # Mistral-style sliding-window attention: each token attends at most
    # the last `sliding_window` positions (None = full attention).  v1
    # keeps all KV blocks resident (correctness first); freeing blocks
    # that scrolled out of the window is a future memory optimization.
    sliding_window: int | None = None
    # MLP gate activation: "silu" (llama/qwen/mistral) or "gelu_tanh"
    # (gemma GeGLU)
    mlp_activation: str = "silu"
    # input-embedding scale (gemma multiplies by sqrt(hidden_size) at the
    # input ONLY — the tied unembedding stays unscaled, so this cannot be
    # baked into the weights)
    embed_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf_config(cls, config: dict | str | Path) -> "LlamaConfig":
        if not isinstance(config, dict):
            config = json.loads(Path(config).read_text())
        heads = config["num_attention_heads"]
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=config.get("num_key_value_heads", heads),
            head_dim=config.get("head_dim") or config["hidden_size"] // heads,
            max_position_embeddings=config.get("max_position_embeddings", 4096),
            rms_norm_eps=config.get("rms_norm_eps", 1e-5),
            rope_theta=config.get("rope_theta", 10000.0),
            tie_word_embeddings=config.get("tie_word_embeddings", False),
            attention_bias=config.get("attention_bias", False),
            qk_norm=config.get("qk_norm", config.get("model_type") == "qwen3"),
            rope_scaling=config.get("rope_scaling"),
            # qwen2-family checkpoints ship sliding_window alongside
            # use_sliding_window: false — only honor the window when HF
            # transformers would (otherwise full attention + Pallas kernel)
            sliding_window=cls._resolve_sliding_window(config),
        )

    @staticmethod
    def _resolve_sliding_window(config: dict) -> int | None:
        """The ONE window of a llama-like config, by HF transformers' rules.

        qwen2-family configs pair ``sliding_window`` with
        ``use_sliding_window`` and ``max_window_layers``: layers with index
        >= max_window_layers use the window, layers below it use full
        attention.  A ``LlamaConfig`` is one run of alike layers, so:
        - use_sliding_window false, or max_window_layers >= num layers
          (no layer windowed): full attention everywhere;
        - max_window_layers <= 0 (every layer windowed), or the key absent
          (mistral-style configs window every layer): uniform window;
        - a genuine mixed split is refused: the shared block serves a
          per-layer split through layer kinds (``LayerKind``, as
          models/exaone_moe.py builds them from ``layer_types``), and no
          family maps ``max_window_layers`` onto them yet.
        """
        window = config.get("sliding_window") or None
        if window is None or not config.get("use_sliding_window", True):
            return None
        mwl = config.get("max_window_layers")
        if mwl is None or mwl <= 0:
            return window
        if mwl >= config["num_hidden_layers"]:
            return None
        raise NotImplementedError(
            f"per-layer sliding-window split (max_window_layers={mwl} < "
            f"num_hidden_layers={config['num_hidden_layers']}) is not "
            "mapped onto layer kinds for this family (models/llama.py "
            "LayerKind; models/exaone_moe.py serves a per-layer split)"
        )

    # --- what a family supplies to the shared forwards -----------------------
    def ffn(self, w: dict, x: jnp.ndarray, valid=None) -> jnp.ndarray:
        """This family's feed-forward over one layer's weights ``w``: the
        gated MLP.  The block every forward below runs (``_block``) asks the
        config for it, so a family whose FFN differs overrides this method
        (models/mixtral.py, models/exaone_moe.py) and shares the forwards as
        they are.  ``valid`` [tokens] marks the rows that are real tokens
        (an expert layer skips the others; a per-token MLP has no use for
        it).  An FFN may return ``(out, stats)``: the layer loop adds
        ``stats`` up into the cache's ``moe_stats`` leaf."""
        return _mlp(x, w["w_gate"], w["w_up"], w["w_down"], self.mlp_activation)

    def layer_runs(self) -> tuple[LayerRun, ...] | None:
        """The runs of alike layers, for a model whose layers differ in
        kind; None for one run of ``num_layers`` alike layers over one cache
        pool (``_scan_layers``, every llama-like family)."""
        return None

    # --- presets (geometries for serving + bench; weights are loaded or
    # random-initialized — no checkpoints ship with the framework) ---------
    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(hidden_size=8192, intermediate_size=28672, num_layers=80, num_heads=64)

    @classmethod
    def llama32_3b(cls) -> "LlamaConfig":
        return cls(
            hidden_size=3072, intermediate_size=8192, num_layers=28, num_heads=24,
            num_kv_heads=8, head_dim=128, rope_theta=500000.0, tie_word_embeddings=True,
        )

    @classmethod
    def llama32_1b(cls) -> "LlamaConfig":
        return cls(
            hidden_size=2048, intermediate_size=8192, num_layers=16, num_heads=32,
            num_kv_heads=8, head_dim=64, rope_theta=500000.0, tie_word_embeddings=True,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 512) -> "LlamaConfig":
        """Test geometry: 2 layers, 4 heads — runs on the CPU mesh."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=16, max_position_embeddings=2048,
            rope_theta=10000.0, tie_word_embeddings=True, dtype=jnp.float32,
        )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(cfg: LlamaConfig, rng: jax.Array) -> dict:
    """Random-init parameter pytree (layer-stacked)."""
    keys = jax.random.split(rng, 12)
    h, i, l_ = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    params = {
        "embed": norm_init(keys[0], (cfg.vocab_size, h), 1.0),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((l_, h), cfg.dtype),
            "wq": norm_init(keys[1], (l_, h, qd), h),
            "wk": norm_init(keys[2], (l_, h, kvd), h),
            "wv": norm_init(keys[3], (l_, h, kvd), h),
            "wo": norm_init(keys[4], (l_, qd, h), qd),
            "mlp_norm": jnp.ones((l_, h), cfg.dtype),
            "w_gate": norm_init(keys[5], (l_, h, i), h),
            "w_up": norm_init(keys[6], (l_, h, i), h),
            "w_down": norm_init(keys[7], (l_, i, h), i),
        },
    }
    if cfg.attention_bias:
        params["layers"]["bq"] = jnp.zeros((l_, qd), cfg.dtype)
        params["layers"]["bk"] = jnp.zeros((l_, kvd), cfg.dtype)
        params["layers"]["bv"] = jnp.zeros((l_, kvd), cfg.dtype)
    if cfg.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((l_, cfg.head_dim), cfg.dtype)
        params["layers"]["k_norm"] = jnp.ones((l_, cfg.head_dim), cfg.dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm_init(keys[8], (h, cfg.vocab_size), h)
    return params


def param_specs(cfg: LlamaConfig) -> dict:
    """PartitionSpecs over mesh axes: 'tp' shards heads/vocab within a
    layer, 'pp' shards the stacked layer axis into pipeline stages (a no-op
    on pp=1 meshes).  GSPMD derives the collectives; this is the whole
    TP implementation, and the pipeline runner consumes the same pp-sharded
    leaves via shard_map (parallel/pipeline.py)."""
    specs = {
        "embed": P("tp", None),          # vocab-sharded
        "final_norm": P(None),
        "layers": {
            "attn_norm": P("pp", None),
            "wq": P("pp", None, "tp"),   # head-sharded
            "wk": P("pp", None, "tp"),
            "wv": P("pp", None, "tp"),
            "wo": P("pp", "tp", None),   # row-parallel → all-reduce
            "mlp_norm": P("pp", None),
            "w_gate": P("pp", None, "tp"),
            "w_up": P("pp", None, "tp"),
            "w_down": P("pp", "tp", None),
        },
    }
    if cfg.attention_bias:
        specs["layers"]["bq"] = P("pp", "tp")
        specs["layers"]["bk"] = P("pp", "tp")
        specs["layers"]["bv"] = P("pp", "tp")
    if cfg.qk_norm:
        specs["layers"]["q_norm"] = P("pp", None)
        specs["layers"]["k_norm"] = P("pp", None)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")  # vocab-sharded logits
    return specs


def kv_cache_spec() -> P:
    """KV cache: layer axis on 'pp' (pipeline stages), kv heads on 'tp'."""
    return P("pp", None, None, "tp", None)


def init_kv_cache(cfg: LlamaConfig, num_blocks: int, block_size: int, dtype=None):
    shape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    dtype = dtype or cfg.dtype
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _embed(params, cfg: LlamaConfig, token_ids) -> jnp.ndarray:
    x = params["embed"][token_ids].astype(cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    return x


def _mlp(x, gate, up, down, activation: str = "silu"):
    if activation == "gelu_tanh":  # gemma GeGLU (HF gelu_pytorch_tanh)
        act = jax.nn.gelu(mm(x, gate), approximate=True)
    elif activation == "silu":
        act = jax.nn.silu(mm(x, gate))
    else:
        # a typo'd activation must not silently run silu into wrong logits
        raise ValueError(f"unknown mlp_activation {activation!r}")
    return mm(act * mm(x, up), down)


def _split_on_activation(rows: int, hidden: int) -> bool:
    """Whether a step program of ``rows`` rows splits a projection's columns
    into heads on the ACTIVATION (``_qkv``).  Left free, XLA folds the
    reshape into the product: a convolution with the head axis a spatial
    dimension, whose weight operand must be hidden-minor, so every layer of
    every step slices ``wq``, ``wk`` and ``wv`` into fast memory and
    transposes them there (31.5 MB a ``qwen3-4b`` layer, 50.3 MB a
    ``mistral-7b`` one) before it multiplies.  With few rows the activation
    is the small thing to turn (16 x 4,096 bf16 = 128 KB) and the weight is
    read where it lies in the layer stack, as ``wo`` and the MLP's three are.
    Both relayouts grow with the projection's columns, so rows against hidden
    decides.  Fitted on a v5e (scripts/qkv_split_bench.py, whole forwards;
    the table is in PERF.md section 6, PR 48): the split on the activation
    saved 40-58 us a ``qwen3-4b`` layer from 16 to 1,024 rows and 16 at
    2,048, and LOST 334 at 4,096 (q's float32 relayout, 67 MB behind the q/k
    norm, no longer fits fast memory); 69-177 us a ``mistral-7b`` layer at
    every width up to 4,096.  The line sits where every measured point on
    its near side is a gain and the cliff is on the far side."""
    return 2 * rows <= hidden


def _qkv(attn_in, w, cfg: LlamaConfig):
    """Project+bias+head-split (+ Qwen3 per-head q/k RMSNorm, pre-rope);
    shared by prefill/decode/trunk.  Projections run through ``mm`` so
    int8-quantized weights (ops/quant.py) drop in transparently."""
    s = attn_in.shape[0]
    q_proj = mm(attn_in, w["wq"])
    k_proj = mm(attn_in, w["wk"])
    v_proj = mm(attn_in, w["wv"])
    if cfg.attention_bias:
        q_proj, k_proj, v_proj = q_proj + w["bq"], k_proj + w["bk"], v_proj + w["bv"]
    if _split_on_activation(s, attn_in.shape[1]):
        q_proj, k_proj, v_proj = jax.lax.optimization_barrier((q_proj, k_proj, v_proj))
    q = q_proj.reshape(s, cfg.num_heads, cfg.head_dim)
    k = k_proj.reshape(s, cfg.num_kv_heads, cfg.head_dim)
    v = v_proj.reshape(s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
    return q, k, v


def _valid_rows(cfg: LlamaConfig, rows: jnp.ndarray):
    """``rows`` (which of a step's rows are real tokens) for a config whose
    FFN wants them, None for a per-token MLP: a llama-like step program
    then holds nothing of it."""
    return rows if getattr(cfg, "ffn_wants_valid_rows", False) else None


def _block(cfg: LlamaConfig, attend, x: jnp.ndarray, w: dict, *cache, valid=None):
    """THE transformer block of the llama-geometry families, dense and
    sparse-expert alike: every forward below runs this one body per layer.

    ``attend(q, k, v, *cache) -> (attn, *pages)`` is the forward's own, the
    only thing that differs between them: it rotates q and k at its
    positions (in the shape it has them: decode inserts a sequence axis,
    verify folds ``[batch, window]``), writes K and V into what the layer
    loop handed this layer (``cache``: ``_scan_layers``' pages and where they
    lie; nothing for a forward without a cache) and calls its attention.
    ``q/k/v`` come in and ``attn`` goes out token-major ``[tokens, heads,
    head_dim]``; the written pages are handed back behind the new ``x``.
    The FFN is the family's (``cfg.ffn``); where it also returns counters
    (an expert layer's routing), they follow the pages."""
    attn_in = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv(attn_in, w, cfg)
    attn, *pages = attend(q, k, v, *cache)
    x = x + mm(attn.reshape(x.shape[0], -1), w["wo"])
    mlp_in = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mlp"):
        out = cfg.ffn(w, mlp_in, valid)
    if isinstance(out, tuple):
        return x + out[0], *pages, out[1]
    return x + out, *pages


def llama_forward_trunk(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,  # [seq_pad] int32
    seq_len: jnp.ndarray,    # scalar int32
    cos: jnp.ndarray,
    sin: jnp.ndarray,
) -> jnp.ndarray:
    """Trunk-only forward (no KV cache, no LM head): final hidden states
    [seq_pad, hidden].  Used by the embedding engine."""
    x = _embed(params, cfg, token_ids)
    positions = jnp.arange(token_ids.shape[0], dtype=jnp.int32)

    def attend(q, k, v):
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        with jax.named_scope("attn"):
            attn = dense_causal_attention(
                q[None], k[None], v[None], seq_len[None],
                sliding_window=cfg.sliding_window,
            )[0]
        return (attn,)

    x, _ = jax.lax.scan(
        lambda x, w: (*_block(cfg, attend, x, w), None), x, params["layers"]
    )
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def _logits(params, cfg, x):
    with jax.named_scope("logits"):
        if cfg.tie_word_embeddings:
            return x @ params["embed"].T.astype(x.dtype)
        return mm(x, params["lm_head"])


# what XLA's memory-space assignment will hold of one value in a v5e's
# 128 MiB of fast memory beside a decode step's activations: 75.5 MB (K and V
# of a `qwen3-4b` layer at 1,152 blocks) stays there, 168 MB (`mistral-7b` at
# 2,560) does not (traces of PR 32 and PR 33, PERF.md section 5)
_ON_CHIP_PAGES_BYTES = 96 << 20


@dataclass(frozen=True)
class _LayerPages:
    """Where one layer's pages lie in the cache viewed as flat pages
    ``[layers * num_blocks, block_size, kv_heads, head_dim]``."""

    base: jnp.ndarray   # scalar int32: layer * num_blocks, its first page
    num_blocks: int     # pages of ONE layer
    block_size: int
    num_layers: int
    # the layer's kind where a model's layers differ (None: a llama-like
    # model, every layer as its config says)
    kind: LayerKind | None = None

    def window(self, cfg: LlamaConfig) -> int | None:
        """This layer's sliding window."""
        return cfg.sliding_window if self.kind is None else self.kind.window

    @property
    def rope(self) -> bool:
        return self.kind is None or self.kind.rope

    def pick(self, value):
        """This layer's pool's part of a per-pool value (``KvPools``)."""
        if not isinstance(value, KvPools):
            return value
        return value.window if self.kind.pool == "window" else value.full

    def pool_slots(self, slots, tables, lane, pos, live):
        """The flat slots this layer writes: ``slots`` (the host's, of the
        full pool) or, for a layer of the window pool, the slots of the
        tokens at ``pos`` of ``lane`` in THAT pool (where ``live``;
        elsewhere out of its range), derived here from its block tables so
        that a step ships one set of slots."""
        if not isinstance(tables, KvPools) or self.kind.pool != "window":
            return slots
        block = tables.window[lane, pos // self.block_size]
        return jnp.where(
            live, block * self.block_size + pos % self.block_size,
            self.num_blocks * self.block_size,
        )

    def blocks(self, ids: jnp.ndarray) -> jnp.ndarray:
        """A layer's block ids (any shape: a prefill's ids, the lanes'
        tables) as pages of the flat cache.  Added here, in XLA: the
        attention kernels see plain page numbers."""
        return ids + self.base

    def on_chip(self, k_pages, v_pages, block_tables):
        """What the one-query decode kernel reads: ``(k, v, tables)``.

        That kernel issues a page copy in every grid step and does next to
        nothing with it, so it runs at the speed its pages arrive: 0.24 ms a
        launch from the chip's fast memory against 0.47 ms from HBM at
        ``qwen3-4b.chat``'s shapes (PERF.md section 6, PR 33).  XLA keeps a
        layer's pages in fast memory when they are a value of their own and
        fit there, so where K and V of ONE layer fit together the kernel
        gets a slice of the carry, block tables as they are; where they do
        not (the slice would go back to HBM and buy nothing) it reads the
        flat pages in place, tables offset."""
        layer_bytes = (k_pages.nbytes + v_pages.nbytes) // self.num_layers
        if layer_bytes > _ON_CHIP_PAGES_BYTES:
            return k_pages, v_pages, self.blocks(block_tables)
        take = lambda pages: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            pages, self.base, self.num_blocks
        )
        return take(k_pages), take(v_pages), block_tables

    def slots(self, ids: jnp.ndarray) -> jnp.ndarray:
        """A layer's flat slots as slots of the flat cache.  A slot out of
        the layer's range (a pad token, an inactive lane) is sent out of
        range of the WHOLE cache, where the scatter drops it: merely offset
        it would land in the next layer's first page."""
        per_layer = self.num_blocks * self.block_size
        return jnp.where(
            ids < per_layer, ids + self.base * self.block_size,
            self.num_layers * per_layer,
        )


def _scan_layers(layer, x, layers: dict, kv_cache: dict):
    """The layer loop of every forward that takes the stacked KV cache.

    ``kv_cache["k"]`` / ``["v"]`` ``[L, N, bs, kvh, d]`` ride the scan as a
    CARRY, viewed as flat pages ``[L * N, bs, kvh, d]`` (a reshape of two
    unsharded leading axes; the ``tp`` spec on the kv-head axis stands), and
    are reshaped back once after the loop: the buffer a jitted step donates
    is the buffer it returns.  (As per-layer scan inputs and stacked outputs
    the cache was copied whole, K and V, in every step program, and each
    layer sliced out and back.)

    ``layer(x, w, k_pages, v_pages, at) -> (x, k_pages, v_pages)`` writes and
    reads layer ``l`` through ``at`` (``_LayerPages``): block ids and tables
    through ``at.blocks``, write slots through ``at.slots``."""
    k, v = kv_cache["k"], kv_cache["v"]
    num_layers, num_blocks, block_size = k.shape[:3]
    flat = (num_layers * num_blocks, *k.shape[2:])

    def body(carry, layer_in):
        x, k_pages, v_pages = carry
        w, index = layer_in
        at = _LayerPages(index * num_blocks, num_blocks, block_size, num_layers)
        return layer(x, w, k_pages, v_pages, at), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, k.reshape(flat), v.reshape(flat)),
        (layers, jnp.arange(num_layers, dtype=jnp.int32)),
    )
    return x, {"k": k_pages.reshape(k.shape), "v": v_pages.reshape(v.shape)}


class _LayerOf(Mapping):
    """One layer's weights out of a group's stacked leaves, each taken when
    it is asked for (``w["wq"]``: a dynamic slice XLA reads in place, as a
    scan's own: tests/ops/test_chip_compile.py holds the cells' compiled
    decode steps to it for all seven weights of a layer,
    ``test_a_decode_step_reads_its_projection_weights_where_they_lie``).
    ``w.stacked(name)`` hands the whole stack and the layer's
    index instead, for a kernel that reads its layer where it lies
    (ops/moe.py ``grouped_matmul``)."""

    def __init__(self, leaves: dict, index):
        self._leaves, self._index = leaves, index

    @property
    def index(self):
        """The layer's row in its group's stacks."""
        return self._index

    def __getitem__(self, name):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self._index, keepdims=False),
            self._leaves[name],
        )

    def __iter__(self):
        return iter(self._leaves)

    def __len__(self):
        return len(self._leaves)

    def stacked(self, name):
        return self._leaves[name], self._index


def layer_bank(w, name: str):
    """A layer's expert bank for the grouped product: the stack and the
    layer's index where the layer loop hands a view of the stack
    (``_LayerOf``), the layer's own array otherwise."""
    return w.stacked(name) if isinstance(w, _LayerOf) else w[name]


def _scan_layer_runs(layer, x, params: dict, kv_cache: dict, runs):
    """The layer loop of a model whose layers differ in kind: one scan a run
    (``LayerRun``), each over the layers' indices, its body taking layer
    ``i``'s weights out of ``params[kind.group]`` (``_LayerOf``) and writing and reading
    the pages of the run's pool (``POOL_LEAVES``) as ``_scan_layers`` does
    (a run of PERIODS runs the period's layers in order in one body, each of
    its own kind and group; ``x`` may be any pytree a family's ``layer``
    carries beside the activations):
    a pool's flat pages are the carry of each of its runs' scans, one buffer
    from the donated argument to the result.  Counters an FFN returns
    (``_block``) are added into ``kv_cache["moe_stats"]``."""
    pools = {
        name: kv_cache[leaves[0]].shape
        for name, leaves in POOL_LEAVES.items() if leaves[0] in kv_cache
    }
    # (each leaf by its own shape: a latent cache's two leaves differ in width)
    flat = {
        name: tuple(
            kv_cache[leaf].reshape(shape[0] * shape[1], *kv_cache[leaf].shape[2:])
            for leaf in POOL_LEAVES[name]
        )
        for name, shape in pools.items()
    }
    stats = kv_cache.get("moe_stats")
    for run in runs:
        period = run.period
        pool = next(kind.pool for kind, _, _ in period if kind.pool is not None)
        num_layers, num_blocks, block_size = pools[pool][:3]

        def body(carry, index, period=period,
                 num_layers=num_layers, num_blocks=num_blocks, block_size=block_size):
            x, k_pages, v_pages, stats = carry
            for j, (kind, _, _) in enumerate(period):
                w = _LayerOf(params[kind.group], index[2 * j])
                at = _LayerPages(
                    index[2 * j + 1] * num_blocks, num_blocks, block_size, num_layers, kind
                )
                x, k_pages, v_pages, *counted = layer(x, w, k_pages, v_pages, at)
                if counted and stats is not None:
                    stats = stats + counted[0]
            return (x, k_pages, v_pages, stats), None

        # a step's (weights row, pool layer) of each layer of the period; a
        # layer that writes nothing stays on the pool layer it reads
        first = [n for _, start, pool_start in period for n in (start, pool_start)]
        stride = [n for kind, _, _ in period for n in (1, int(kind.writes))]
        steps = jnp.arange(run.count, dtype=jnp.int32)[:, None]
        if any(n != 1 for n in stride):
            steps = steps * jnp.asarray(stride, jnp.int32)
        steps = steps + jnp.asarray(first, jnp.int32)
        (x, *pages, stats), _ = jax.lax.scan(
            body, (x, *flat[pool], stats), steps
        )
        flat[pool] = tuple(pages)
    out = dict(kv_cache)
    for name in pools:
        for leaf, pages in zip(POOL_LEAVES[name], flat[name]):
            out[leaf] = pages.reshape(kv_cache[leaf].shape)
    if stats is not None:
        out["moe_stats"] = stats
    return x, out


def _layers(cfg: LlamaConfig, layer, x, params: dict, kv_cache: dict):
    """Every cache-carrying forward's layer loop: one run of alike layers
    (``_scan_layers``) or the config's runs (``_scan_layer_runs``)."""
    runs = cfg.layer_runs()
    if runs is None:
        return _scan_layers(layer, x, params["layers"], kv_cache)
    return _scan_layer_runs(layer, x, params, kv_cache, runs)


def llama_forward_prefill(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,   # [seq_pad] int32
    kv_cache: dict,           # {"k","v"}: [L, N, bs, kvh, d]
    block_ids: jnp.ndarray,   # [max_blocks] int32
    seq_len: jnp.ndarray,     # scalar int32: valid tokens
    start_pos: jnp.ndarray,   # scalar int32: absolute position offset (chunked prefill)
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    sp_mesh=None,
) -> tuple[jnp.ndarray, dict]:
    """Single-sequence prefill.  Returns (last-token logits [vocab], new cache).

    ``sp_mesh``: a mesh whose ``sp`` axis shards the sequence — prefill
    attention runs as ring attention (ops/ring_attention.py), K/V chunks
    rotating over ICI, enabling prompts beyond one chip's activation memory
    (sequence/context parallelism; the reference has none, SURVEY.md §2.5)."""
    x = _embed(params, cfg, token_ids)  # [s, h]
    return llama_forward_prefill_embeds(
        params, cfg, x, kv_cache, block_ids, seq_len, start_pos, cos, sin,
        sp_mesh=sp_mesh,
    )


def llama_forward_prefill_embeds(
    params: dict,
    cfg: LlamaConfig,
    input_embeds: jnp.ndarray,  # [seq_pad, hidden] — e.g. image patches + text
    kv_cache: dict,
    block_ids: jnp.ndarray,
    seq_len: jnp.ndarray,
    start_pos: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    sp_mesh=None,
) -> tuple[jnp.ndarray, dict]:
    """Prefill from pre-computed input embeddings (multimodal prompts:
    vision-encoder patch embeddings concatenated with text token
    embeddings, LLaVA-style).  ``sp_mesh``: see llama_forward_prefill."""
    s = input_embeds.shape[0]
    x = input_embeds.astype(cfg.dtype)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)

    if sp_mesh is not None:
        if cfg.sliding_window is not None:
            # ring attention has no sliding-window mask: shards would
            # silently compute full attention (the engine fences this too,
            # but direct model-level callers deserve the same guard)
            raise NotImplementedError(
                "sequence parallelism does not compose with sliding-window "
                "attention: ring attention computes the full causal mask"
            )
        from dynamo_tpu.ops.ring_attention import ring_attention

    def attend(q, k, v, k_pages, v_pages, at):
        if at.rope:
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
        with jax.named_scope("kv_write"):
            pages = write_prefill_kv(
                k_pages, v_pages, k, v, at.blocks(at.pick(block_ids)), seq_len
            )
        with jax.named_scope("attn"):
            if sp_mesh is not None:
                attn = ring_attention(q[None], k[None], v[None], seq_len, sp_mesh)[0]
            else:
                attn = dense_causal_attention(
                    q[None], k[None], v[None], seq_len[None],
                    sliding_window=at.window(cfg),
                )[0]
        return attn, *pages

    layer = partial(_block, cfg, attend, valid=_valid_rows(cfg, jnp.arange(s) < seq_len))
    x, kv_cache = _layers(cfg, layer, x, params, kv_cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = x[jnp.maximum(seq_len - 1, 0)]
    logits = _logits(params, cfg, last[None])[0]
    return logits.astype(jnp.float32), kv_cache


def llama_forward_prefill_with_prefix(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,       # [tail_pad] int32 — the uncached tail
    kv_cache: dict,
    full_block_ids: jnp.ndarray,  # [max_blocks] int32 — whole table (prefix+tail)
    tail_block_ids: jnp.ndarray,  # [max_blocks] int32 — table from the first tail block
    tail_len: jnp.ndarray,        # scalar int32: valid tail tokens
    start_pos: jnp.ndarray,       # scalar int32: cached prefix length (block-aligned)
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    sp_mesh=None,
) -> tuple[jnp.ndarray, dict]:
    """Continued prefill over a reused prefix: the tail's queries attend to
    the resident prefix KV (gathered from the paged cache) plus themselves,
    and only the tail's K/V are written.  Serves both prefix-cache hits and
    chunked prefill (reference intent: vLLM prefix caching / chunked
    prefill; block reuse lib/llm/src/block_manager/pool.rs:447-466).

    ``sp_mesh``: the tail attends via ring attention over the ``sp`` axis
    while each shard merges the replicated resident prefix into its online
    softmax (ops/ring_attention.ring_attention_with_prefix) — prefix
    caching and chunked prefill compose with sequence parallelism."""
    s = token_ids.shape[0]
    x = _embed(params, cfg, token_ids)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)

    if sp_mesh is not None:
        if cfg.sliding_window is not None:
            raise NotImplementedError(
                "sequence parallelism does not compose with sliding-window "
                "attention: ring attention computes the full causal mask"
            )
        from dynamo_tpu.ops.ring_attention import ring_attention_with_prefix

    def attend(q, k, v, k_pages, v_pages, at):
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        # gather the resident prefix BEFORE writing the tail (the mask in
        # the attention op drops everything past start_pos anyway)
        k_prefix, v_prefix = gather_prefix_kv(
            k_pages, v_pages, at.blocks(full_block_ids)
        )
        with jax.named_scope("kv_write"):
            pages = write_prefill_kv(
                k_pages, v_pages, k, v, at.blocks(tail_block_ids), tail_len
            )
        if sp_mesh is not None:
            attn = ring_attention_with_prefix(
                q[None], k[None], v[None], k_prefix[None], v_prefix[None],
                start_pos, tail_len, sp_mesh,
            )[0]
        else:
            attn = prefill_attention_with_prefix(
                q, k, v, k_prefix, v_prefix, start_pos, tail_len,
                sliding_window=cfg.sliding_window,
            )
        return attn, *pages

    layer = partial(_block, cfg, attend, valid=_valid_rows(cfg, jnp.arange(s) < tail_len))
    x, kv_cache = _layers(cfg, layer, x, params, kv_cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = x[jnp.maximum(tail_len - 1, 0)]
    logits = _logits(params, cfg, last[None])[0]
    return logits.astype(jnp.float32), kv_cache


def llama_forward_decode(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,     # [batch] int32 — last sampled token per seq
    kv_cache: dict,
    block_tables: jnp.ndarray,  # [batch, max_blocks] int32
    context_lens: jnp.ndarray,  # [batch] int32 length INCLUDING this token
    slot_ids: jnp.ndarray,      # [batch] int32 flat cache slot for this token
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    attention: str = "jax",     # "jax" | "pallas" | "pallas_interpret"
    tp_mesh=None,
) -> tuple[jnp.ndarray, dict]:
    """Batched single-token decode.  Returns (logits [batch, vocab], cache).

    ``attention="pallas"`` uses the Pallas paged-attention kernel (no
    materialized page gather); with ``tp_mesh`` the kernel runs under
    shard_map per tp shard — queries sharded on the head axis, cache on the
    kv-head axis (head order is kv-major, so contiguous head chunks align
    with their kv heads) — and GSPMD handles everything around it.
    "jax" is the portable gather-based fallback.
    """
    x = _embed(params, cfg, token_ids)  # [b, h]
    positions = jnp.maximum(context_lens - 1, 0)      # this token's position

    live = context_lens > 0

    def paged(q, k_pages, v_pages, at):
        lane_tables = at.pick(block_tables)
        window = at.window(cfg)
        if attention.startswith("pallas"):
            from dynamo_tpu.ops.pallas import paged_attention_decode

            interpret = attention == "pallas_interpret"
            k_read, v_read, tables = at.on_chip(k_pages, v_pages, lane_tables)
            if tp_mesh is not None and tp_mesh.shape.get("tp", 1) > 1:
                kernel = jax.shard_map(
                    lambda q_, k_, v_, bt, cl: paged_attention_decode(
                        q_, k_, v_, bt, cl, interpret=interpret,
                        sliding_window=window,
                    ),
                    mesh=tp_mesh,
                    in_specs=(
                        P(None, "tp", None),        # q: heads sharded
                        P(None, None, "tp", None),  # cache: kv heads sharded
                        P(None, None, "tp", None),
                        P(),
                        P(),
                    ),
                    out_specs=P(None, "tp", None),
                    check_vma=False,  # pallas_call outputs carry no vma info
                )
                return kernel(q, k_read, v_read, tables, context_lens)
            return paged_attention_decode(
                q, k_read, v_read, tables, context_lens,
                interpret=interpret, sliding_window=window,
            )
        return paged_decode_attention(
            q, k_pages, v_pages, at.blocks(lane_tables), context_lens,
            sliding_window=window,
        )

    def attend(q, k, v, k_pages, v_pages, at):
        if at.rope:
            # apply_rope expects a seq axis: insert and drop it
            q = apply_rope(q[:, None], positions[:, None], cos, sin)[:, 0]
            k = apply_rope(k[:, None], positions[:, None], cos, sin)[:, 0]
        slots = at.pool_slots(
            slot_ids, block_tables, jnp.arange(token_ids.shape[0]), positions, live
        )
        with jax.named_scope("kv_write"):
            pages = write_decode_kv(k_pages, v_pages, k, v, at.slots(slots))
        with jax.named_scope("attn"):
            return paged(q, *pages, at), *pages

    layer = partial(_block, cfg, attend, valid=_valid_rows(cfg, live))
    x, kv_cache = _layers(cfg, layer, x, params, kv_cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _logits(params, cfg, x)
    return logits.astype(jnp.float32), kv_cache


def llama_forward_unified(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,     # [T] int32 — flat ragged token batch
    kv_cache: dict,
    block_tables: jnp.ndarray,  # [lanes, max_blocks] int32
    context_lens: jnp.ndarray,  # [lanes] int32 incl. each lane's span end
    token_pos: jnp.ndarray,     # [T] int32 absolute position (-1 = pad)
    token_slot: jnp.ndarray,    # [T] int32 flat cache slot (OOB = pad)
    token_lane: jnp.ndarray,    # [T] int32 owning lane (OOB = pad)
    span_lane: jnp.ndarray,     # [T] int32 (pack_spans): block t's span s at t*tb+s
    span_first: jnp.ndarray,     # [T] int32 first page ordinal of the span
    span_count: jnp.ndarray,     # [T] int32 pages in the span (0 = unused)
    kv_steps: jnp.ndarray,      # [T // tb_tokens] int32 KV steps per token block
    sample_rows: jnp.ndarray,   # [lanes] int32 flat index of span's LAST token
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    attention: str = "jax",     # "jax" | "pallas" | "pallas_interpret"
    tb_tokens: int = 8,
) -> tuple[jnp.ndarray, dict]:
    """Ragged unified-batch forward: one launch computes chunked-prefill
    spans AND decode tokens from different sequences, each token at its own
    absolute position (Ragged Paged Attention, arxiv 2604.15464).  Every
    token's K/V scatters into its cache slot like decode, attention reads
    the paged cache per lane (resident prefixes included — this path also
    subsumes the continued-prefill-with-prefix program), and the logits are
    gathered at each lane's LAST span row: [lanes, vocab], one sample row
    per sequence regardless of how many tokens it contributed.  One weight
    stream from HBM serves the whole mixed batch — the dispatch-count win
    that removes the engine's prefill/decode phase split."""
    x = _embed(params, cfg, token_ids)  # [t, h]
    positions = jnp.maximum(token_pos, 0)

    lanes = context_lens.shape[0]
    live = (token_pos >= 0) & (token_lane >= 0) & (token_lane < lanes)

    def ragged(q, k_pages, v_pages, at):
        tables = at.blocks(at.pick(block_tables))
        if attention.startswith("pallas"):
            from dynamo_tpu.ops.pallas import (
                ragged_paged_attention as ragged_kernel,
            )

            return ragged_kernel(
                q, k_pages, v_pages, token_lane, token_pos, tables,
                at.pick(span_lane), at.pick(span_first), at.pick(span_count),
                at.pick(kv_steps),
                tb_tokens=tb_tokens,
                interpret=attention == "pallas_interpret",
                sliding_window=at.window(cfg),
            )
        return ragged_paged_attention(
            q, k_pages, v_pages, tables, context_lens, token_lane,
            token_pos, sliding_window=at.window(cfg),
        )

    def attend(q, k, v, k_pages, v_pages, at):
        if at.rope:
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
        slots = at.pool_slots(
            token_slot, block_tables, jnp.clip(token_lane, 0, lanes - 1),
            positions, live,
        )
        # every token writes before anyone reads: span tokens see their own
        # in-window predecessors through the cache (pads scatter-drop)
        with jax.named_scope("kv_write"):
            pages = write_decode_kv(k_pages, v_pages, k, v, at.slots(slots))
        with jax.named_scope("attn"):
            return ragged(q, *pages, at), *pages

    layer = partial(_block, cfg, attend, valid=_valid_rows(cfg, live))
    x, kv_cache = _layers(cfg, layer, x, params, kv_cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    rows = x[sample_rows]  # [lanes, h] — junk for hole lanes, caller-gated
    logits = _logits(params, cfg, rows)
    return logits.astype(jnp.float32), kv_cache


def llama_forward_verify(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,     # [batch, w] int32 — window: last accepted
                                # token then draft tokens
    kv_cache: dict,
    block_tables: jnp.ndarray,  # [batch, max_blocks] int32
    context_lens: jnp.ndarray,  # [batch] int32 INCLUDING the window's last token
    slot_ids: jnp.ndarray,      # [batch, w] int32 flat cache slots per position
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    attention: str = "jax",     # "jax" | "pallas" | "pallas_interpret"
) -> tuple[jnp.ndarray, dict]:
    """Speculative-verification forward: score all w window positions in one
    pass (logits [batch, w, vocab]).  The whole window's K/V is written like
    decode; rejected positions' cache entries are overwritten when the
    sequence continues (slots derive from the accepted length).  One weight
    stream from HBM scores w tokens — the bandwidth economics of
    speculative decoding on TPU.  ``attention="pallas"`` runs the
    multi-query paged kernel (no materialized page gather)."""
    b, w_len = token_ids.shape
    x = _embed(params, cfg, token_ids.reshape(-1))  # [b*w, h]
    positions = jnp.maximum(
        context_lens[:, None] - w_len + jnp.arange(w_len)[None, :], 0
    )  # [b, w]
    flat_slots = slot_ids.reshape(-1)

    def attend(q, k, v, k_pages, v_pages, at):
        # rotate as [batch, window]; K and V go to their slots flat
        q = apply_rope(q.reshape(b, w_len, cfg.num_heads, cfg.head_dim), positions, cos, sin)
        k = apply_rope(k.reshape(b, w_len, cfg.num_kv_heads, cfg.head_dim), positions, cos, sin)
        v = v.reshape(b, w_len, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope("kv_write"):
            pages = write_decode_kv(
                k_pages, v_pages, k.reshape(b * w_len, cfg.num_kv_heads, cfg.head_dim),
                v.reshape(b * w_len, cfg.num_kv_heads, cfg.head_dim),
                at.slots(flat_slots),
            )
        with jax.named_scope("attn"):
            attn = window_attention(
                attention, q, *pages, at.blocks(block_tables), context_lens,
                sliding_window=cfg.sliding_window,
            )
        return attn, *pages

    layer = partial(_block, cfg, attend)
    x, kv_cache = _layers(cfg, layer, x, params, kv_cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _logits(params, cfg, x).reshape(b, w_len, -1)
    return logits.astype(jnp.float32), kv_cache


def llama_forward_decode_pp(
    params: dict,
    cfg: LlamaConfig,
    token_ids: jnp.ndarray,
    kv_cache: dict,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    slot_ids: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    pp_mesh,
    microbatches: int | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Batched decode with the layer stack pipelined over the ``pp`` mesh
    axis (parallel/pipeline.py): stage s holds layers [s*L/S, (s+1)*L/S)
    and their KV-cache slice; microbatches stream through the stages over
    ICI.  Embedding and the LM head run replicated outside the pipeline.
    Matches llama_forward_decode exactly (same block)."""
    x = _embed(params, cfg, token_ids)
    positions = jnp.maximum(context_lens - 1, 0)

    def attend(q, k, v, k_layer, v_layer, aux_mb):
        pos_mb, slots_mb, tables_mb, lens_mb = aux_mb
        q = apply_rope(q[:, None], pos_mb[:, None], cos, sin)[:, 0]
        k = apply_rope(k[:, None], pos_mb[:, None], cos, sin)[:, 0]
        with jax.named_scope("kv_write"):
            layer_kv = write_decode_kv(k_layer, v_layer, k, v, slots_mb)
        with jax.named_scope("attn"):
            attn = paged_decode_attention(
                q, *layer_kv, tables_mb, lens_mb,
                sliding_window=cfg.sliding_window,
            )
        return attn, *layer_kv

    def body(x_mb, aux_mb, w, layer_cache):
        x_mb, *layer_kv = _block(cfg, attend, x_mb, w, *layer_cache, aux_mb)
        return x_mb, tuple(layer_kv)

    from dynamo_tpu.parallel.pipeline import pipeline_layer_stack

    x, (new_k, new_v) = pipeline_layer_stack(
        body, x, (positions, slot_ids, block_tables, context_lens),
        params["layers"], (kv_cache["k"], kv_cache["v"]), pp_mesh,
        microbatches=microbatches,
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _logits(params, cfg, x)
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def phi3_config_from_hf(config: dict | str | Path) -> LlamaConfig:
    """Phi-3 = llama math with FUSED checkpoint tensors (qkv_proj,
    gate_up_proj — split in phi3_load_hf_weights) and an always-on
    sliding window.  The 128k 'longrope' variants are refused loudly:
    ops/rope.py has no longrope schedule yet."""
    if not isinstance(config, dict):
        config = json.loads(Path(config).read_text())
    scaling = config.get("rope_scaling") or {}
    kind = scaling.get("rope_type") or scaling.get("type")
    if kind in ("longrope", "su"):
        raise NotImplementedError(
            "phi3 longrope scaling is not implemented; the 4k-context "
            "variants (rope_scaling: null) are supported"
        )
    return LlamaConfig.from_hf_config(config)


def phi3_load_hf_weights(cfg: LlamaConfig, model_dir: str | Path) -> dict:
    """Split Phi-3's fused qkv_proj [q+k+v, h] and gate_up_proj [2i, h]
    into the standard per-projection names, then delegate to the base
    loader — the stacking/transpose/tie logic must not fork."""
    from dynamo_tpu.models.hf_io import read_safetensors

    tensors = dict(read_safetensors(model_dir))
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    inter = cfg.intermediate_size
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        qkv = tensors.pop(f"{p}.self_attn.qkv_proj.weight")
        tensors[f"{p}.self_attn.q_proj.weight"] = qkv[:qd]
        tensors[f"{p}.self_attn.k_proj.weight"] = qkv[qd : qd + kvd]
        tensors[f"{p}.self_attn.v_proj.weight"] = qkv[qd + kvd :]
        gate_up = tensors.pop(f"{p}.mlp.gate_up_proj.weight")
        tensors[f"{p}.mlp.gate_proj.weight"] = gate_up[:inter]
        tensors[f"{p}.mlp.up_proj.weight"] = gate_up[inter:]
    return load_hf_weights(cfg, model_dir, tensors=tensors)


def gemma_config_from_hf(config: dict | str | Path) -> LlamaConfig:
    """Gemma-1 = llama skeleton + GeGLU MLP, sqrt(hidden) input-embedding
    scale, and (1+w) RMSNorm weights (baked at load time,
    gemma_load_hf_weights).  Gemma always ties embeddings."""
    if not isinstance(config, dict):
        config = json.loads(Path(config).read_text())
    act = config.get("hidden_activation") or config.get("hidden_act") or "gelu_pytorch_tanh"
    if act not in ("gelu", "gelu_pytorch_tanh"):
        raise ValueError(f"unexpected gemma activation {act!r}")
    # delegate the shared fields (rope scaling, windows, biases, defaults)
    # and override only the gemma deltas — a field added to from_hf_config
    # must not silently go missing here
    import dataclasses

    return dataclasses.replace(
        LlamaConfig.from_hf_config(config),
        tie_word_embeddings=True,
        mlp_activation="gelu_tanh",
        embed_scale=float(config["hidden_size"]) ** 0.5,
    )


def gemma_load_hf_weights(cfg: LlamaConfig, model_dir: str | Path) -> dict:
    """Gemma checkpoints store RMSNorm weights as w with runtime (1 + w):
    bake the +1 in once so every forward path runs unchanged."""
    params = load_hf_weights(cfg, model_dir)
    plus_one = lambda t: (t.astype(jnp.float32) + 1.0).astype(t.dtype)  # noqa: E731
    layers = dict(params["layers"])
    layers["attn_norm"] = plus_one(layers["attn_norm"])
    layers["mlp_norm"] = plus_one(layers["mlp_norm"])
    return {**params, "layers": layers, "final_norm": plus_one(params["final_norm"])}


def make_rope_tables(cfg: LlamaConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    return rope_table(
        cfg.max_position_embeddings, cfg.head_dim, cfg.rope_theta,
        scaling=cfg.rope_scaling,
    )


# ---------------------------------------------------------------------------
# HF weight loading (safetensors) — for real checkpoints when present
# ---------------------------------------------------------------------------

_HF_LAYER_MAP = {
    "attn_norm": "model.layers.{i}.input_layernorm.weight",
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wk": "model.layers.{i}.self_attn.k_proj.weight",
    "wv": "model.layers.{i}.self_attn.v_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "mlp_norm": "model.layers.{i}.post_attention_layernorm.weight",
    "w_gate": "model.layers.{i}.mlp.gate_proj.weight",
    "w_up": "model.layers.{i}.mlp.up_proj.weight",
    "w_down": "model.layers.{i}.mlp.down_proj.weight",
}


def load_hf_weights(
    cfg: LlamaConfig, model_dir: str | Path, *, tensors: dict | None = None
) -> dict:
    """Load and stack HF llama safetensors into our layer-stacked pytree.
    (HF stores projections as [out, in]; ours are [in, out] → transpose.)
    ``tensors`` overrides the on-disk read for loaders that pre-process the
    checkpoint (phi3 splits its fused tensors, then delegates here)."""
    if tensors is None:
        from dynamo_tpu.models.hf_io import read_safetensors

        tensors = read_safetensors(model_dir)

    def get(name: str, transpose: bool = False):
        t = tensors[name]
        if transpose:
            t = t.T
        return jnp.asarray(t, cfg.dtype)

    layer_map = dict(_HF_LAYER_MAP)
    if cfg.attention_bias:
        layer_map.update(
            bq="model.layers.{i}.self_attn.q_proj.bias",
            bk="model.layers.{i}.self_attn.k_proj.bias",
            bv="model.layers.{i}.self_attn.v_proj.bias",
        )
    if cfg.qk_norm:
        layer_map.update(
            q_norm="model.layers.{i}.self_attn.q_norm.weight",
            k_norm="model.layers.{i}.self_attn.k_norm.weight",
        )
    layers: dict[str, list] = {k: [] for k in layer_map}
    for i in range(cfg.num_layers):
        for ours, theirs in layer_map.items():
            transpose = ours.startswith("w")
            layers[ours].append(get(theirs.format(i=i), transpose))
    params = {
        "embed": get("model.embed_tokens.weight"),
        "final_norm": get("model.norm.weight"),
        "layers": {k: jnp.stack(v) for k, v in layers.items()},
    }
    if not cfg.tie_word_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = get("lm_head.weight", transpose=True)
    return params
