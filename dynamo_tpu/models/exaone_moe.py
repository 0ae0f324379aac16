"""EXAONE-MoE family (``model_type`` ``exaone_moe``, K-EXAONE-236B-A23B).

The llama family's block and step programs (models/llama.py) with what this
architecture has of its own, all of it a matter of the layer's KIND
(``llama.LayerKind``), read per layer from the config:

- ``layer_types``: ``sliding_attention`` layers attend the last
  ``sliding_window`` positions, rotate q and k, and keep their keys and
  values in the WINDOW pool (``wk`` / ``wv``: blocks behind the window are
  released, engine/kv_manager.py); ``full_attention`` layers are causal over
  the whole context, do NOT rotate, and keep the full-length pool;
- ``mlp_layer_types``: ``dense`` layers (the leading one) run the gated MLP of
  width ``intermediate_size``; ``sparse`` layers route every token over ALL
  ``num_experts x expert_parallel_size`` experts by sigmoid scores and a
  selection bias, keep the assignments whose expert this chip holds
  (``num_experts`` of them, from ``expert_parallel_rank x num_experts``),
  and add the shared expert (ops/moe.py: no capacity, no drop).

The dense layers' weights are stacked under ``params["dense_layers"]``, the
sparse ones under ``params["layers"]``; the layer loop is one scan a run of
alike layers (``LlamaConfig.layer_runs`` -> ``llama._scan_layer_runs``).

q/k RMSNorm over every head, rotation on window layers only and the norms
BEFORE each sublayer are assumptions (the public config has no key for
them; benchmark/configs/k-exaone-236b-l8.json ``assumed``).  The
multi-token-prediction layer is not built.  No checkpoint loader: weights
come from the seed (``init_params``, the recipe
benchmark/reference/exaone_moe.py repeats).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import LayerKind, LayerRun, LlamaConfig, _mlp, layer_bank
from dynamo_tpu.ops.moe import MOE_STATS, moe_ffn
from dynamo_tpu.ops.quant import mm


@dataclass(frozen=True)
class ExaoneMoeConfig(LlamaConfig):
    qk_norm: bool = True
    # per layer: "sliding_attention" | "full_attention"
    layer_types: tuple[str, ...] = ()
    # per layer: "dense" | "sparse"
    mlp_layer_types: tuple[str, ...] = ()
    # the window of the sliding layers (``sliding_window``, the llama-like
    # families' ONE window, stays None: no layer-blind code may apply it)
    window: int = 128
    # experts HELD here; the router's width is num_experts x expert_parallel_size
    num_experts: int = 128
    expert_parallel_size: int = 1
    expert_parallel_rank: int = 0
    experts_per_token: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    # the grouped product's implementation (as ``MixtralConfig``'s): "auto"
    # is the platform's in ``ops/moe.py``; the engine, which alone knows of a
    # mesh, writes "xla" here under one.  The config is what reaches ``ffn``
    grouped_matmul: str = "auto"

    # the expert layer skips rows that are no token (llama._valid_rows)
    ffn_wants_valid_rows = True

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers or len(
            self.mlp_layer_types
        ) != self.num_layers:
            raise ValueError(
                "layer_types and mlp_layer_types must name every one of the "
                f"{self.num_layers} layers"
            )

    @property
    def num_experts_total(self) -> int:
        return self.num_experts * self.expert_parallel_size

    @property
    def first_expert(self) -> int:
        return self.expert_parallel_rank * self.num_experts

    @property
    def window_layers(self) -> int:
        return sum(t == "sliding_attention" for t in self.layer_types)

    @property
    def full_layers(self) -> int:
        return self.num_layers - self.window_layers

    @property
    def dense_layers(self) -> int:
        return sum(t == "dense" for t in self.mlp_layer_types)

    def layer_kinds(self) -> tuple[LayerKind, ...]:
        return tuple(
            LayerKind(
                window=self.window if a == "sliding_attention" else None,
                rope=a == "sliding_attention",
                pool="window" if a == "sliding_attention" else "kv",
                group="dense_layers" if m == "dense" else "layers",
            )
            for a, m in zip(self.layer_types, self.mlp_layer_types)
        )

    def layer_runs(self) -> tuple[LayerRun, ...]:
        runs: list[LayerRun] = []
        seen: dict[str, int] = {}   # group or pool -> layers of it so far
        for kind in self.layer_kinds():
            start, pool_start = seen.get(kind.group, 0), seen.get(kind.pool, 0)
            last = runs[-1] if runs else None
            if last is not None and last.kind == kind:
                runs[-1] = LayerRun(kind, last.start, last.count + 1, last.pool_start)
            else:
                runs.append(LayerRun(kind, start, 1, pool_start))
            seen[kind.group] = start + 1
            seen[kind.pool] = pool_start + 1
        return tuple(runs)

    def ffn(self, w: dict, x: jnp.ndarray, valid=None):
        """A dense layer's gated MLP, or a sparse layer's shared expert plus
        this chip's part of the routed sum (and the routing's counters)."""
        if "w_router" not in w:
            return _mlp(x, w["w_gate"], w["w_up"], w["w_down"], self.mlp_activation)
        with jax.named_scope("moe"):
            routed, stats = moe_ffn(
                x, w["w_router"],
                *(layer_bank(w, name) for name in ("w_gate", "w_up", "w_down")),
                top_k=self.experts_per_token,
                router_bias=w["router_bias"], scoring="sigmoid_noaux",
                n_group=self.n_group, topk_group=self.topk_group,
                norm_topk_prob=self.norm_topk_prob,
                first_expert=self.first_expert, valid=valid,
                impl=self.grouped_matmul, with_stats=True,
            )
            out = routed * jnp.asarray(self.routed_scaling_factor, routed.dtype)
            if self.num_shared_experts:
                out = out + mm(
                    jax.nn.silu(mm(x, w["ws_gate"])) * mm(x, w["ws_up"]),
                    w["ws_down"],
                )
        return out, stats

    @classmethod
    def tiny(cls, vocab_size: int = 512, **overrides) -> "ExaoneMoeConfig":
        """Test geometry: the published pattern (a leading dense layer, LLLG)
        at toy widths, window 8, 2 of 8 experts held."""
        fields = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=2048, rope_theta=10000.0,
            dtype=jnp.float32,
            layer_types=("sliding_attention",) * 3 + ("full_attention",)
            + ("sliding_attention",) * 3 + ("full_attention",),
            mlp_layer_types=("dense",) + ("sparse",) * 7,
            window=8, num_experts=2, expert_parallel_size=4,
            experts_per_token=3, moe_intermediate_size=32,
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def from_hf_config(cls, config: dict | str | Path) -> "ExaoneMoeConfig":
        if not isinstance(config, dict):
            config = json.loads(Path(config).read_text())
        layers = config["num_hidden_layers"]
        heads = config["num_attention_heads"]
        rope = config.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"exaone_moe rope_type {rope.get('rope_type')!r} is not implemented"
            )
        if config.get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError("exaone_moe routes by sigmoid scores")
        layer_types = tuple(config["layer_types"][:layers])
        mlp_types = tuple(
            (config.get("mlp_layer_types") or (
                ["dense"] * config.get("first_k_dense_replace", 0) + ["sparse"] * layers
            ))[:layers]
        )
        windows = {
            w for t, w in zip(layer_types, config.get("sliding_windows") or [])
            if t == "sliding_attention"
        } or {config["sliding_window"]}
        if len(windows) != 1:
            raise NotImplementedError(
                f"exaone_moe window layers of different widths: {sorted(windows)}"
            )
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=config.get("num_key_value_heads", heads),
            head_dim=config.get("head_dim") or config["hidden_size"] // heads,
            max_position_embeddings=config.get("max_position_embeddings", 4096),
            rms_norm_eps=config.get("rms_norm_eps", 1e-5),
            rope_theta=float(rope.get("rope_theta", config.get("rope_theta", 1e6))),
            tie_word_embeddings=config.get("tie_word_embeddings", False),
            layer_types=layer_types,
            mlp_layer_types=mlp_types,
            window=int(windows.pop()),
            num_experts=config["num_experts"],
            expert_parallel_size=int(config.get("expert_parallel_size", 1)),
            expert_parallel_rank=int(config.get("expert_parallel_rank", 0)),
            experts_per_token=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            num_shared_experts=config.get("num_shared_experts", 0),
            norm_topk_prob=config.get("norm_topk_prob", True),
            routed_scaling_factor=float(config.get("routed_scaling_factor", 1.0)),
            n_group=config.get("n_group", 1),
            topk_group=config.get("topk_group", 1),
        )


def init_params(cfg: ExaoneMoeConfig, rng: jax.Array) -> dict:
    """Random weights from the seed: every leaf has its own key of
    ``split(rng, 24)`` (in the order drawn here), a leaf stacked over layers
    draws layer ``l`` of its stack from ``fold_in(key, l)`` (so that nobody
    has to hold a whole stack in float32), each matrix ``normal /
    sqrt(fan_in)``, the selection bias ``0.01 x normal`` (float32, like the
    router: SMALL, because a trained bias is what balances the experts'
    load; at ``0.1 x normal`` the busiest expert drew four times the mean
    and a chip's share of the assignments swung 12.0-14.2% with the seed,
    its step time with it), norms all ones.  The reference
    (benchmark/reference/exaone_moe.py) repeats this recipe key for key."""
    keys = iter(jax.random.split(rng, 24))
    h, v = cfg.hidden_size, cfg.vocab_size
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim

    def one(key, shape, fan_in, dtype):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)

    def draw(shape, fan_in, dtype=cfg.dtype):
        """One leaf stacked over ``shape[0]`` layers."""
        key = next(keys)
        return jnp.stack([
            one(jax.random.fold_in(key, layer), shape[1:], fan_in, dtype)
            for layer in range(shape[0])
        ])

    def attention(n):
        return {
            "attn_norm": jnp.ones((n, h), cfg.dtype),
            "wq": draw((n, h, qd), h),
            "wk": draw((n, h, kvd), h),
            "wv": draw((n, h, kvd), h),
            "wo": draw((n, qd, h), qd),
            "q_norm": jnp.ones((n, cfg.head_dim), cfg.dtype),
            "k_norm": jnp.ones((n, cfg.head_dim), cfg.dtype),
            "mlp_norm": jnp.ones((n, h), cfg.dtype),
        }

    params = {
        "embed": one(next(keys), (v, h), 1.0, cfg.dtype),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "lm_head": one(next(keys), (h, v), h, cfg.dtype),
    }
    nd, ns = cfg.dense_layers, cfg.num_layers - cfg.dense_layers
    if nd:
        i = cfg.intermediate_size
        params["dense_layers"] = {
            **attention(nd),
            "w_gate": draw((nd, h, i), h),
            "w_up": draw((nd, h, i), h),
            "w_down": draw((nd, i, h), i),
        }
    if ns:
        e, mi = cfg.num_experts, cfg.moe_intermediate_size
        si = mi * max(cfg.num_shared_experts, 1)
        params["layers"] = {
            **attention(ns),
            "w_router": draw((ns, h, cfg.num_experts_total), h, jnp.float32),
            "router_bias": 0.01 * draw((ns, cfg.num_experts_total), 1.0, jnp.float32),
            "w_gate": draw((ns, e, h, mi), h),
            "w_up": draw((ns, e, h, mi), h),
            "w_down": draw((ns, e, mi, h), mi),
            "ws_gate": draw((ns, h, si), h),
            "ws_up": draw((ns, h, si), h),
            "ws_down": draw((ns, si, h), si),
        }
    if cfg.tie_word_embeddings:
        del params["lm_head"]
    return params


def param_specs(cfg: ExaoneMoeConfig) -> dict:
    """Attention head-sharded over 'tp', expert banks over 'ep' (their FFN
    width over 'tp'), as the sparse-expert family's."""
    attention = {
        "attn_norm": P(None, None),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "q_norm": P(None, None),
        "k_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    specs = {"embed": P(None, None), "final_norm": P(None)}
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    if cfg.dense_layers:
        specs["dense_layers"] = {
            **attention,
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        }
    if cfg.num_layers - cfg.dense_layers:
        specs["layers"] = {
            **attention,
            "w_router": P(None, None, None),
            "router_bias": P(None, None),
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
            "ws_gate": P(None, None, "tp"),
            "ws_up": P(None, None, "tp"),
            "ws_down": P(None, "tp", None),
        }
    return specs


def window_pool_blocks(cfg: ExaoneMoeConfig, lanes: int, max_len: int, block_size: int) -> int:
    """Blocks of the window pool: ONE prompt served whole (it holds all its
    blocks for the step that computes it) beside every lane's window, the
    block its next token starts and the one the window's tail still
    touches, and the allocator's watermark."""
    a_prompt = -(-max_len // block_size)
    a_lane = -(-cfg.window // block_size) + 2
    return a_prompt + lanes * a_lane + max(1, (a_prompt + lanes * a_lane) // 100)


def init_kv_cache(cfg: ExaoneMoeConfig, num_blocks: int, block_size: int, dtype=None,
                  *, window_blocks: int | None = None) -> dict:
    """Two pools: ``k`` / ``v`` hold the full-attention layers' pages
    (``num_blocks`` a layer, a block for every 16 tokens of context), ``wk``
    / ``wv`` the window layers' (``window_blocks`` a layer: blocks behind the
    window are released).  ``moe_stats`` collects the expert layers'
    counters (ops/moe.py ``MOE_STATS``) until the engine takes them."""
    dtype = dtype or cfg.dtype
    tail = (block_size, cfg.num_kv_heads, cfg.head_dim)
    if window_blocks is None:
        window_blocks = num_blocks
    full = (cfg.full_layers, num_blocks, *tail)
    window = (cfg.window_layers, window_blocks, *tail)
    return {
        "k": jnp.zeros(full, dtype), "v": jnp.zeros(full, dtype),
        "wk": jnp.zeros(window, dtype), "wv": jnp.zeros(window, dtype),
        "moe_stats": jnp.zeros((len(MOE_STATS),), jnp.int32),
    }


def kv_cache_specs(cfg: ExaoneMoeConfig) -> dict:
    pages = P(None, None, None, "tp", None)
    return {"k": pages, "v": pages, "wk": pages, "wv": pages, "moe_stats": P(None)}
