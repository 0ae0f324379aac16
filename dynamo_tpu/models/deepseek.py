"""DeepSeek-class model: Multi-head Latent Attention (MLA) + fine-grained
MoE (DeepSeek-V2/V3/R1 geometries).

The reference's flagship wide-EP deployment is DeepSeek-R1 served through
SGLang+DeepEP across 48+ GPUs (reference: examples/sglang/README.md:105,
container/Dockerfile.sglang-deepep); here the model is native to the TPU
engine and its parallelism is sharding annotations over mesh axes ``tp``
(attention heads, shared-expert FFN) and ``ep`` (routed experts) — GSPMD
emits the collectives.

MLA, TPU-first:
- The KV cache stores only the **compressed latent** per token: ``c_kv``
  (kv_lora_rank wide) plus the shared rope key (qk_rope_head_dim wide) —
  e.g. 512+64 floats/token vs 2*8*128 for Llama-70B-class GQA, a ~4.5x
  HBM saving that directly raises achievable batch (decode on TPU is HBM
  bandwidth-bound).
- Decode attends **in latent space** ("absorbed" form): q_nope is folded
  through the k up-projection once per step (one small einsum), scores are
  taken against the latent cache directly, and the context is decompressed
  through the v up-projection after the softmax — no per-token K/V
  decompression, so the cache read stays at latent width.
- Prefill decompresses K/V for the current chunk only (dense causal
  attention on the MXU) while writing latents to the paged cache.

Cache layout keeps the engine's {"k", "v"} leaves (layers first, blocks
second), so paged bookkeeping, extract/inject, offload and disagg KV
shipping work unchanged, in the one layout the MLA kernels copy in place
(``init_kv_cache``, ``rope_page_width``):
    k: [layers, num_blocks, block_size, kv_lora_rank]      (latent)
    v: [layers, num_blocks, block_size, 128-lane tiles]    (rope key, zeros behind)
The layer loop is the shared one (llama._scan_layer_runs): the dense layers
then the sparse ones, each a scan whose carry is the cache's flat pages.

Residual path: one stream a token, or (``hc_mult`` > 1, ``model_type``
``xing4_0``) ``hc_mult`` streams mixed token by token before and after every
sublayer (``_residual``, ops/hyper_connections.py); the streams ride the
layer loop as ``[rows, hc_mult x hidden]`` and take no cache.

Routing: V2-style renormalized softmax top-k, or V3/R1 aux-free sigmoid
routing (e_score_correction_bias steers selection only, group-limited
top-k) behind ``scoring_func="sigmoid"``.  Long context: YaRN rope scaling
via the HF ``rope_scaling`` dict, including the mscale attention-temperature
correction (``attn_scale``).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import LayerKind, LayerRun, _scan_layer_runs, layer_bank
from dynamo_tpu.ops import hyper_connections as hc
from dynamo_tpu.ops.attention import NEG_INF, position_major_to_batch
from dynamo_tpu.ops.moe import MOE_STATS, moe_ffn
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.quant import mm
from dynamo_tpu.ops.rope import apply_rope, rope_table


@dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 102400
    hidden_size: int = 2048
    num_layers: int = 27
    num_heads: int = 16
    # MLA geometry
    q_lora_rank: int = 0              # 0 = direct q projection (V2-Lite)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # FFN geometry
    intermediate_size: int = 10944    # dense layers
    first_k_dense: int = 1            # leading dense (non-MoE) layers
    moe_intermediate_size: int = 1408  # per routed expert
    num_experts: int = 64
    experts_per_token: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 1.0
    # V3/R1 aux-free routing: sigmoid scores + e_score_correction_bias +
    # group-limited top-k; V2 uses plain renormalized softmax
    scoring_func: str = "softmax"     # "softmax" | "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    # common
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # HF rope_scaling dict; "yarn" also corrects the attention temperature
    # (mscale) — see attn_scale
    rope_scaling: Any = None
    tie_word_embeddings: bool = False
    # residual streams a token (1 = the plain residual) and how their
    # stream-to-stream matrix is made doubly stochastic (ops/hyper_connections.py)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    dtype: Any = jnp.bfloat16
    # the grouped product's implementation (as ``MixtralConfig``'s): "auto"
    # is the platform's in ``ops/moe.py``; the engine, which alone knows of a
    # mesh, writes "xla" here under one
    grouped_matmul: str = "auto"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        from dynamo_tpu.ops.rope import yarn_mscale

        m = yarn_mscale(self.rope_scaling)
        return (self.qk_head_dim ** -0.5) * m * m

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    def layer_runs(self) -> tuple[LayerRun, ...]:
        """The leading dense layers (``params["dense_layers"]``), then the
        sparse ones (``params["moe_layers"]``), over the one cache pool."""
        kind = lambda group: LayerKind(window=None, rope=True, pool="kv", group=group)  # noqa: E731
        kd = self.first_k_dense
        runs = (
            LayerRun(kind("dense_layers"), 0, kd, 0),
            LayerRun(kind("moe_layers"), 0, self.num_moe_layers, kd),
        )
        return tuple(run for run in runs if run.count)

    @classmethod
    def from_hf_config(cls, config: dict | str | Path) -> "DeepseekConfig":
        if not isinstance(config, dict):
            config = json.loads(Path(config).read_text())
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            q_lora_rank=config.get("q_lora_rank") or 0,
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            intermediate_size=config["intermediate_size"],
            first_k_dense=config.get("first_k_dense_replace", 0),
            moe_intermediate_size=config.get("moe_intermediate_size", 0)
            or config["intermediate_size"],
            num_experts=config.get("n_routed_experts", 0) or 1,
            experts_per_token=config.get("num_experts_per_tok", 1) or 1,
            n_shared_experts=config.get("n_shared_experts", 0) or 0,
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0),
            scoring_func=config.get("scoring_func", "softmax"),
            n_group=config.get("n_group", 1) or 1,
            topk_group=config.get("topk_group", 1) or 1,
            norm_topk_prob=config.get("norm_topk_prob", True),
            max_position_embeddings=config.get("max_position_embeddings", 4096),
            rms_norm_eps=config.get("rms_norm_eps", 1e-6),
            rope_theta=config.get("rope_theta", 10000.0),
            rope_scaling=config.get("rope_scaling"),
            tie_word_embeddings=config.get("tie_word_embeddings", False),
            hc_mult=config.get("hc_mult") or 1,
            hc_sinkhorn_iters=config.get("hc_sinkhorn_iters", 20),
            hc_eps=config.get("hc_eps", 1e-6),
            mhc_h_res_clamp_min=config.get("mhc_h_res_clamp_min", -30.0),
            mhc_h_res_clamp_max=config.get("mhc_h_res_clamp_max", 30.0),
        )

    # --- presets ----------------------------------------------------------
    @classmethod
    def deepseek_v2_lite(cls) -> "DeepseekConfig":
        return cls()  # the defaults above are the 16B V2-Lite geometry

    @classmethod
    def deepseek_v3(cls) -> "DeepseekConfig":
        """671B/R1 geometry (config shape only; serving it needs multi-host)."""
        return cls(
            vocab_size=129280, hidden_size=7168, num_layers=61, num_heads=128,
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, intermediate_size=18432,
            first_k_dense=3, moe_intermediate_size=2048, num_experts=256,
            experts_per_token=8, n_shared_experts=1, routed_scaling_factor=2.5,
            scoring_func="sigmoid", n_group=8, topk_group=4,
        )

    @classmethod
    def tiny_mla(cls, vocab_size: int = 512) -> "DeepseekConfig":
        """Test geometry: runs on the CPU mesh; exercises q-lora, dense+MoE
        layer mix, and ep/tp-shardable expert counts."""
        return cls(
            vocab_size=vocab_size, hidden_size=64, num_layers=3, num_heads=4,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            first_k_dense=1, moe_intermediate_size=48, num_experts=4,
            experts_per_token=2, n_shared_experts=1,
            max_position_embeddings=2048, tie_word_embeddings=True,
            dtype=jnp.float32,
        )

    @classmethod
    def tiny_xing(cls, vocab_size: int = 512) -> "DeepseekConfig":
        """Test geometry of the family with residual streams: four of them,
        two dense and three sparse layers, a compressed query, YaRN."""
        return cls(
            vocab_size=vocab_size, hidden_size=256, num_layers=5, num_heads=4,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            first_k_dense=2, moe_intermediate_size=48, num_experts=8,
            experts_per_token=2, n_shared_experts=1, routed_scaling_factor=2.0,
            scoring_func="sigmoid", max_position_embeddings=2048,
            rope_scaling={
                "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
                "mscale": 1, "mscale_all_dim": 1,
                "original_max_position_embeddings": 64,
            },
            hc_mult=4, dtype=jnp.float32,
        )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, fan_in, dtype):
    """One drawn array, as ONE program (the division by a constant and the
    rounding fused: the reference compiles the same expression, and the two
    agree to the bit; a layer's experts never exist in float32)."""
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)


def init_params(cfg: DeepseekConfig, rng: jax.Array) -> dict:
    """Random weights from the seed, the recipe a model served without a
    checkpoint gets (and benchmark/reference/deepseek_mla.py repeats key for
    key): every leaf that is drawn has its own key of ``split(rng, 32)``, in
    the order drawn here (embedding, head, then the dense group's attention
    leaves and MLP, then the sparse group's attention leaves, router, bias,
    expert banks and shared expert); a leaf stacked over layers draws layer
    ``l`` of its stack from ``fold_in(key, l)``, so that nobody holds a whole
    stack in float32 (a layer's 64 experts are 0.74 GB there); each matrix
    ``normal / sqrt(fan_in)`` in ``cfg.dtype`` (the embedding: fan-in 1);
    the selection bias ``0.01 x normal`` in float32 (small: a trained bias is
    what balances the experts' load, models/exaone_moe.py); norms all ones.
    With residual streams (``hc_mult`` > 1) each group draws two more keys
    LAST, for the two sublayers' ``hc_phi`` (``normal / sqrt(n x hidden)``)
    and ``hc_bias`` (standard normal), float32, ``hc_alpha`` all ones: the
    dynamic and the static part of every coefficient are both of order one,
    so a program that skipped the mixing would be a different model (a
    trained checkpoint starts near the identity, ``alpha`` 0.01)."""
    keys = iter(jax.random.split(rng, 32))
    h, v = cfg.hidden_size, cfg.vocab_size
    hd_q = cfg.num_heads * cfg.qk_head_dim
    r = cfg.kv_lora_rank

    def one(key, shape, fan_in, dtype):
        return _draw(key, tuple(shape), float(fan_in), dtype)

    def draw(shape, fan_in, dtype=cfg.dtype):
        """One leaf stacked over ``shape[0]`` layers."""
        key = next(keys)
        return jnp.stack([
            one(jax.random.fold_in(key, layer), shape[1:], fan_in, dtype)
            for layer in range(shape[0])
        ])

    def attention(n):
        leaves = {
            "attn_norm": jnp.ones((n, h), cfg.dtype),
            "mlp_norm": jnp.ones((n, h), cfg.dtype),
            "kv_norm": jnp.ones((n, r), cfg.dtype),
            "w_dkv": draw((n, h, r + cfg.qk_rope_head_dim), h),
            "w_uk": draw((n, r, cfg.num_heads * cfg.qk_nope_head_dim), r),
            "w_uv": draw((n, r, cfg.num_heads * cfg.v_head_dim), r),
            "wo": draw((n, cfg.num_heads * cfg.v_head_dim, h), cfg.num_heads * cfg.v_head_dim),
        }
        if cfg.q_lora_rank:
            leaves["w_dq"] = draw((n, h, cfg.q_lora_rank), h)
            leaves["q_norm"] = jnp.ones((n, cfg.q_lora_rank), cfg.dtype)
            leaves["w_uq"] = draw((n, cfg.q_lora_rank, hd_q), cfg.q_lora_rank)
        else:
            leaves["wq"] = draw((n, h, hd_q), h)
        return leaves

    def streams(n):
        """The two sublayers' mixing leaves of ``n`` layers (attention's at
        index 0, the FFN's at 1)."""
        if cfg.hc_mult == 1:
            return {}
        wide, outs = cfg.hc_mult * h, hc.coefficient_count(cfg.hc_mult)
        return {
            "hc_phi": draw((n, 2, wide, outs), wide, jnp.float32),
            "hc_alpha": jnp.ones((n, 2, 3), jnp.float32),
            "hc_bias": draw((n, 2, outs), 1.0, jnp.float32),
        }

    params: dict = {
        "embed": one(next(keys), (v, h), 1.0, cfg.dtype),
        "final_norm": jnp.ones((h,), cfg.dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = one(next(keys), (h, v), h, cfg.dtype)
    kd, km = cfg.first_k_dense, cfg.num_moe_layers
    if kd:
        i = cfg.intermediate_size
        params["dense_layers"] = {
            **attention(kd),
            "w_gate": draw((kd, h, i), h),
            "w_up": draw((kd, h, i), h),
            "w_down": draw((kd, i, h), i),
            **streams(kd),
        }
    if km:
        mi, e = cfg.moe_intermediate_size, cfg.num_experts
        si = cfg.n_shared_experts * mi
        moe = {**attention(km), "w_router": draw((km, h, e), h)}
        if cfg.scoring_func == "sigmoid":
            moe["router_bias"] = 0.01 * draw((km, e), 1.0, jnp.float32)
        moe.update(
            w_gate=draw((km, e, h, mi), h),
            w_up=draw((km, e, h, mi), h),
            w_down=draw((km, e, mi, h), mi),
        )
        if si:
            moe.update(
                ws_gate=draw((km, h, si), h),
                ws_up=draw((km, h, si), h),
                ws_down=draw((km, si, h), si),
            )
        moe.update(streams(km))
        params["moe_layers"] = moe
    return params


def _attn_specs(cfg: DeepseekConfig) -> dict:
    specs = {
        "attn_norm": P(None, None),
        "w_dkv": P(None, None, None),   # latent path replicated (MQA-like)
        "kv_norm": P(None, None),
        "w_uk": P(None, None, "tp"),    # head-sharded up-projections
        "w_uv": P(None, None, "tp"),
        "wo": P(None, "tp", None),      # row-parallel → all-reduce
    }
    if cfg.q_lora_rank:
        specs["w_dq"] = P(None, None, None)
        specs["q_norm"] = P(None, None)
        specs["w_uq"] = P(None, None, "tp")
    else:
        specs["wq"] = P(None, None, "tp")
    if cfg.hc_mult > 1:
        # a token's coefficients need its whole row: replicated
        specs["hc_phi"] = P(None, None, None, None)
        specs["hc_alpha"] = P(None, None, None)
        specs["hc_bias"] = P(None, None, None)
    return specs


def param_specs(cfg: DeepseekConfig) -> dict:
    specs: dict = {
        "embed": P(None, None),
        "final_norm": P(None),
    }
    if cfg.first_k_dense:
        dense = _attn_specs(cfg)
        dense.update(
            mlp_norm=P(None, None),
            w_gate=P(None, None, "tp"),
            w_up=P(None, None, "tp"),
            w_down=P(None, "tp", None),
        )
        specs["dense_layers"] = dense
    if cfg.num_moe_layers:
        moe = _attn_specs(cfg)
        moe.update(
            mlp_norm=P(None, None),
            w_router=P(None, None, None),
            **(
                {"router_bias": P(None, None)}
                if cfg.scoring_func == "sigmoid" else {}
            ),
            # routed experts over 'ep', within-expert FFN over 'tp'
            w_gate=P(None, "ep", None, "tp"),
            w_up=P(None, "ep", None, "tp"),
            w_down=P(None, "ep", "tp", None),
        )
        if cfg.n_shared_experts:
            moe.update(
                ws_gate=P(None, None, "tp"),
                ws_up=P(None, None, "tp"),
                ws_down=P(None, "tp", None),
            )
        specs["moe_layers"] = moe
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


# ---------------------------------------------------------------------------
# KV cache: flat latent pages the kernels copy in place
# ---------------------------------------------------------------------------

_LANES = 128    # a TPU tile's minor axis


def rope_page_width(cfg: DeepseekConfig) -> int:
    """Width the rotated key is STORED at: ``qk_rope_head_dim`` rounded up to
    whole 128-lane tiles (64 -> 128, zeros behind the key).  A page is copied
    HBM -> VMEM whole, by DMA, and a DMA moves whole tiles: stored 64 wide the
    rope cache was zero-padded to 128, all of it, in every call of every
    layer (25-50 MB a layer-call at 11,008 blocks).  The zeros add exact
    zeros to the scores and cost 128 B a token-layer: 1,280 B at 512 + 64,
    where the published 1,152 B would not be read in place."""
    return -(-cfg.qk_rope_head_dim // _LANES) * _LANES


def init_kv_cache(cfg: DeepseekConfig, num_blocks: int, block_size: int, dtype=None):
    """``k``: the normalised latents ``[layers, blocks, block_size,
    kv_lora_rank]`` (keys AND values of every head); ``v``: the one rotated
    key all heads share, ``[layers, blocks, block_size, rope_page_width]``.
    No head axis: a unit axis second from last would be the tiled one.  The
    layer loop views both as flat pages ``[layers x blocks, block_size,
    width]`` (llama._scan_layer_runs), which is what the kernels read.
    ``moe_stats`` collects the expert layers' counters (ops/moe.py
    ``MOE_STATS``) until the engine takes them."""
    dtype = dtype or cfg.dtype
    page = (cfg.num_layers, num_blocks, block_size)
    cache = {
        "k": jnp.zeros((*page, cfg.kv_lora_rank), dtype),
        "v": jnp.zeros((*page, rope_page_width(cfg)), dtype),
    }
    if cfg.num_moe_layers:
        cache["moe_stats"] = jnp.zeros((len(MOE_STATS),), jnp.int32)
    return cache


def kv_cache_specs(cfg: DeepseekConfig) -> dict:
    # the latent is shared across heads — replicate across tp (it is ~4x
    # smaller than a GQA cache even unsharded)
    pages = P(None, None, None, None)
    specs = {"k": pages, "v": pages}
    if cfg.num_moe_layers:
        specs["moe_stats"] = P(None)
    return specs


def make_rope_tables(cfg: DeepseekConfig):
    # DeepSeek applies the YaRN temperature on the softmax scale
    # (attn_scale = mscale**2 / sqrt(d)), not baked into the tables
    return rope_table(
        cfg.max_position_embeddings, cfg.qk_rope_head_dim, cfg.rope_theta,
        scaling=cfg.rope_scaling, yarn_apply_attention_factor=False,
    )


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------
#
# One block (``_block``) and one layer loop (llama._scan_layer_runs: the dense
# run, then the sparse run, the cache's flat pages the scans' carry, written
# in place) for all five forwards.  A forward supplies ``attend(w, attn_in,
# ck_pages, kr_pages, at) -> (attn_out [rows, hidden], ck_pages, kr_pages)``:
# it rotates at its positions, writes its tokens' latents into the layer's
# pages (``at``: llama._LayerPages) and attends.  The prompt-only forwards
# decompress K and V of their own chunk; everything that reads the cache
# attends absorbed, in latent space.


def _project_q(w, x, cfg: DeepseekConfig):
    """x [t, h] → q [t, heads, qk_head_dim] (optionally through the q-lora
    bottleneck)."""
    t = x.shape[0]
    if cfg.q_lora_rank:
        q = mm(rms_norm(mm(x, w["w_dq"]), w["q_norm"], cfg.rms_norm_eps), w["w_uq"])
    else:
        q = mm(x, w["wq"])
    return q.reshape(t, cfg.num_heads, cfg.qk_head_dim)


def _queries(w, x, cfg: DeepseekConfig, rotate):
    """x [t, h] -> (q_nope [t, H, nope], q_rope [t, H, rope] rotated by
    ``rotate``)."""
    q = _project_q(w, x, cfg)
    return q[..., : cfg.qk_nope_head_dim], rotate(q[..., cfg.qk_nope_head_dim:])


def _latent_kv(w, x, cfg: DeepseekConfig):
    """x [t, h] → (c_kv [t, r] normalized, k_rope [t, rope_dim] un-roped)."""
    dkv = mm(x, w["w_dkv"])
    c_kv = rms_norm(dkv[:, : cfg.kv_lora_rank], w["kv_norm"], cfg.rms_norm_eps)
    k_rope = dkv[:, cfg.kv_lora_rank :]
    return c_kv, k_rope


def _up_projections(w, cfg: DeepseekConfig):
    w_uk = w["w_uk"].reshape(cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim)
    w_uv = w["w_uv"].reshape(cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim)
    return w_uk, w_uv


def _absorb(w_uk, q_nope, q_rope, width: int, dtype):
    """The absorbed queries the kernels take, in the model's dtype:
    ``q_lat [..., H, R]`` (q_nope through the K up-projection, accumulated in
    float32) and ``q_rope`` widened with zeros to the rope page's width (an
    activation's pad, a few KB a token; the cache is never padded)."""
    q_lat = jnp.einsum(
        "...hn,rhn->...hr", q_nope, w_uk, preferred_element_type=jnp.float32
    ).astype(dtype)
    pad = [(0, 0)] * (q_rope.ndim - 1) + [(0, width - q_rope.shape[-1])]
    return q_lat, jnp.pad(q_rope.astype(dtype), pad)


def _decompress(w, w_uv, ctx, cfg: DeepseekConfig):
    """Latent context [..., H, R] -> the attention sublayer's output rows
    [rows, hidden], through the V up-projection and ``wo``."""
    out = jnp.einsum(
        "...hr,rhv->...hv", ctx, w_uv.astype(ctx.dtype),
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)
    return mm(out.reshape(-1, cfg.num_heads * cfg.v_head_dim), w["wo"])


def _merge_parts(ctx, lse_resident, out_window, lse_window, w_uv):
    """One softmax over a row's two parts, each normalised by its own sum:
    the resident pages' latent context ``ctx [t, H, R]`` (decompressed here
    through ``w_uv``) and the window's own output ``out_window [t, H x v]``,
    weighted by ``exp(lse_part - lse)`` of their log-sum-exp ``[t, H]``.  The
    two weights sum to one, so the resident part's is the logistic of the
    difference and the result ``window + a x (resident - window)``: a row
    with nothing resident (its lse ``NEG_INF``, ``a`` exactly 0) takes the
    window part unscaled; a pad, both empty, zeros.  All of it on
    ``[t, H x v]`` rows, the layout the window launch writes and ``wo``
    takes; a head's weight is spread over its ``v`` columns by a product
    with a 0/1 matrix.  Returns [t, H x v] float32."""
    t, h, _ = ctx.shape
    v = w_uv.shape[-1]
    a = jax.nn.sigmoid(lse_resident - lse_window)
    spread = jnp.repeat(jnp.eye(h, dtype=jnp.float32), v, axis=1)      # [H, H x v]
    a = jnp.dot(a, spread, precision=jax.lax.Precision.HIGHEST)
    resident = jnp.einsum(
        "thr,rhv->thv", ctx, w_uv.astype(ctx.dtype), preferred_element_type=jnp.float32
    ).reshape(t, h * v)
    window = out_window.astype(jnp.float32)
    return window + a * (resident - window)


def _write_latents(ck_pages, kr_pages, c_kv, k_rope, slots):
    """Rows ``c_kv [t, R]`` / ``k_rope [t, rope]`` into flat slots of the
    whole cache (``at.slots``; out of range = dropped), in place: the pages
    are the layer loop's carry."""
    n, bs, r = ck_pages.shape
    width = kr_pages.shape[-1]
    k_rope = jnp.pad(k_rope, ((0, 0), (0, width - k_rope.shape[-1])))
    with jax.named_scope("kv_write"):
        ck = ck_pages.reshape(n * bs, r).at[slots].set(
            c_kv.astype(ck_pages.dtype), mode="drop")
        kr = kr_pages.reshape(n * bs, width).at[slots].set(
            k_rope.astype(kr_pages.dtype), mode="drop")
    return ck.reshape(ck_pages.shape), kr.reshape(kr_pages.shape)


def _prompt_slots(at, block_ids, count, s: int):
    """Flat slots of a prompt chunk's ``s`` rows (``count`` of them real) in
    the blocks ``block_ids`` of layer ``at``."""
    idx = jnp.arange(s, dtype=jnp.int32)
    slots = block_ids[idx // at.block_size] * at.block_size + idx % at.block_size
    return at.slots(jnp.where(idx < count, slots, at.num_blocks * at.block_size))


def _by_head(x):
    """[t, H, d] -> [H, t, d] float32."""
    return x.astype(jnp.float32).transpose(1, 0, 2)


def _prompt_attention(q_nope, q_rope, k_nope, k_rope, v, count, scale, prefix=None):
    """A prompt chunk's causal attention against itself, DECOMPRESSED (every
    head's own keys ``k_nope`` and values ``v``, the one rotated key), float32,
    a head at a time: 16 heads' scores of an 8,192-token chunk at once are
    4.3 GB beside the weights.  Rows past ``count`` are masked as keys.

    ``prefix`` = ``(q_lat [s, H, R], q_wide [s, H, P], ck [t, R], kr [t, P],
    visible [t], w_uv [R, H, v])``: resident latents the queries attend too,
    absorbed, under the SAME softmax; their context is summed in latent space
    and decompressed once.  Returns [s, H, v] float32."""
    s = q_nope.shape[0]
    pos = jnp.arange(s)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < count)
    k_rope = k_rope.astype(jnp.float32)

    def head(of):
        qn, qr, kn, vh, *more = of
        sc = jnp.where(mask, (qn @ kn.T + qr @ k_rope.T) * scale, NEG_INF)
        if prefix is None:
            return jax.nn.softmax(sc, axis=-1) @ vh
        ql, qw, uv = more
        sp = jnp.where(visible[None, :], (ql @ ck.T + qw @ kr.T) * scale, NEG_INF)
        weights = jax.nn.softmax(jnp.concatenate([sp, sc], axis=-1), axis=-1)
        t = ck.shape[0]
        return (weights[:, :t] @ ck) @ uv + weights[:, t:] @ vh

    heads = (_by_head(q_nope), _by_head(q_rope), _by_head(k_nope), _by_head(v))
    if prefix is not None:
        q_lat, q_wide, ck, kr, visible, w_uv = prefix
        ck, kr = ck.astype(jnp.float32), kr.astype(jnp.float32)
        heads += (_by_head(q_lat), _by_head(q_wide), w_uv.astype(jnp.float32).transpose(1, 0, 2))
    return jax.lax.map(head, heads).transpose(1, 0, 2)


def _gathered_scores(q_lat, q_rope, ck, kr, visible, scale):
    """The XLA fallback's absorbed scores against gathered pages: ``q_*``
    [b, ..., H, *], ``ck`` / ``kr`` [b, t, *], ``visible`` broadcastable to
    the result [b, H, ..., t]."""
    logits = (
        jnp.einsum("b...hr,btr->bh...t", q_lat.astype(jnp.float32), ck.astype(jnp.float32))
        + jnp.einsum("b...hp,btp->bh...t", q_rope.astype(jnp.float32), kr.astype(jnp.float32))
    ) * scale
    return jax.nn.softmax(jnp.where(visible, logits, NEG_INF), axis=-1)


def _dense_mlp(w, x):
    return mm(jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]), w["w_down"])


def _moe_mlp(w, x, cfg: DeepseekConfig, valid=None):
    """The routed experts (all held here, every row of ``valid`` walked) and
    the shared ones; returns the layer's ``MOE_STATS`` beside the output."""
    routed, stats = moe_ffn(
        x, w["w_router"],
        *(layer_bank(w, name) for name in ("w_gate", "w_up", "w_down")),
        top_k=cfg.experts_per_token,
        router_bias=w.get("router_bias"),
        scoring="sigmoid_noaux" if cfg.scoring_func == "sigmoid" else "softmax",
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        norm_topk_prob=cfg.norm_topk_prob,
        valid=valid, impl=cfg.grouped_matmul, with_stats=True,
    )
    out = routed * jnp.asarray(cfg.routed_scaling_factor, routed.dtype)
    if cfg.n_shared_experts:
        out = out + mm(jax.nn.silu(mm(x, w["ws_gate"])) * mm(x, w["ws_up"]), w["ws_down"])
    return out, stats


def _residual(cfg: DeepseekConfig, w, x, sublayer: int):
    """``(h, add)`` around one sublayer (0: attention, 1: the FFN) of the
    layer ``w``: ``h [rows, hidden]`` is what the sublayer's norm takes and
    ``add(y)`` what the block carries on once the sublayer gave ``y``.  One
    residual stream: ``x`` and ``x + y``.  ``hc_mult`` streams (``x [rows,
    hc_mult x hidden]``): the row's own mix of them, and all of them mixed
    among themselves plus ``y`` spread over them (ops/hyper_connections.py)."""
    if cfg.hc_mult == 1:
        return x, lambda y: x + y
    with jax.named_scope("mhc_pre"):
        h_pre, h_post, h_res = hc.coefficients(
            x, w["hc_phi"][sublayer], w["hc_alpha"][sublayer], w["hc_bias"][sublayer],
            cfg.hc_mult, norm_eps=cfg.rms_norm_eps, iters=cfg.hc_sinkhorn_iters,
            eps=cfg.hc_eps, clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
        )
        h = hc.pre_mix(x, h_pre)

    def add(y):
        with jax.named_scope("mhc_post"):
            return hc.post_mix(x, y, h_post, h_res)

    return h, add


def _block(cfg: DeepseekConfig, attend, valid, x, w, ck_pages, kr_pages, at):
    """THE transformer block of the family: latent attention (the forward's
    ``attend``), then the dense MLP or the expert layer, each behind its
    RMSNorm and inside the residual path (``_residual``).  ``valid`` [rows]:
    the rows that are real tokens (the expert layer walks no others)."""
    h, add = _residual(cfg, w, x, 0)
    attn_in = rms_norm(h, w["attn_norm"], cfg.rms_norm_eps)
    attn_out, ck_pages, kr_pages = attend(w, attn_in, ck_pages, kr_pages, at)
    x = add(attn_out)
    h, add = _residual(cfg, w, x, 1)
    mlp_in = rms_norm(h, w["mlp_norm"], cfg.rms_norm_eps)
    if "w_router" not in w:
        with jax.named_scope("mlp"):
            return add(_dense_mlp(w, mlp_in)), ck_pages, kr_pages
    with jax.named_scope("moe"):
        out, stats = _moe_mlp(w, mlp_in, cfg, valid)
    return add(out), ck_pages, kr_pages, stats


def _forward(params, cfg: DeepseekConfig, x, kv_cache, attend, valid=None):
    """Shared trunk: the dense run then the sparse run over the cache's flat
    pages, carried and written in place; final norm.  With residual streams
    the embedding goes in as ``hc_mult`` equal streams, the layer loop
    carries them, and their sum comes out."""
    if cfg.hc_mult > 1:
        x = hc.replicate(x, cfg.hc_mult)
    x, kv_cache = _scan_layer_runs(
        partial(_block, cfg, attend, valid), x, params, kv_cache, cfg.layer_runs()
    )
    if cfg.hc_mult > 1:
        x = hc.collapse(x, cfg.hc_mult)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps), kv_cache


def _logits(params, cfg, x):
    with jax.named_scope("logits"):
        if cfg.tie_word_embeddings:
            return x @ params["embed"].T.astype(x.dtype)
        return mm(x, params["lm_head"])


def deepseek_forward_prefill(
    params, cfg: DeepseekConfig, token_ids, kv_cache, block_ids, seq_len, start_pos,
    cos, sin,
):
    """Single-sequence prefill → (last-token logits [vocab], new cache).
    Dense causal attention over the chunk with K and V decompressed (the
    chunk only; nothing is read from the cache), latents written."""
    s = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)
    scale = jnp.float32(cfg.attn_scale)

    def attend(w, attn_in, ck_pages, kr_pages, at):
        q_nope, q_rope = _queries(
            w, attn_in, cfg, lambda q: apply_rope(q, positions, cos, sin))
        c_kv, k_rope = _latent_kv(w, attn_in, cfg)
        k_rope = apply_rope(k_rope[:, None, :], positions, cos, sin)[:, 0]
        pages = _write_latents(
            ck_pages, kr_pages, c_kv, k_rope, _prompt_slots(at, block_ids, seq_len, s))
        w_uk, w_uv = _up_projections(w, cfg)
        with jax.named_scope("attn"):
            k_nope = jnp.einsum("tr,rhn->thn", c_kv, w_uk)
            v = jnp.einsum("tr,rhv->thv", c_kv, w_uv)
            out = _prompt_attention(
                q_nope, q_rope, k_nope, k_rope, v, seq_len, scale).astype(cfg.dtype)
        return mm(out.reshape(s, -1), w["wo"]), *pages

    x, new_cache = _forward(
        params, cfg, x, kv_cache, attend, valid=jnp.arange(s) < seq_len)
    last = x[jnp.maximum(seq_len - 1, 0)]
    logits = _logits(params, cfg, last[None])[0]
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_prefill_with_prefix(
    params, cfg: DeepseekConfig, token_ids, kv_cache, full_block_ids,
    tail_block_ids, tail_len, start_pos, cos, sin,
):
    """Continued prefill over a reused prefix (same contract as
    llama_forward_prefill_with_prefix): the tail's queries attend the
    resident prefix LATENTS absorbed (scores in latent space, context
    decompressed once) jointly with the in-chunk dense attention under one
    softmax; only the tail's latents are written."""
    s = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)
    scale = jnp.float32(cfg.attn_scale)

    def attend(w, attn_in, ck_pages, kr_pages, at):
        q_nope, q_rope = _queries(
            w, attn_in, cfg, lambda q: apply_rope(q, positions, cos, sin))
        c_kv, k_rope = _latent_kv(w, attn_in, cfg)
        k_rope = apply_rope(k_rope[:, None, :], positions, cos, sin)[:, 0]
        # gather the resident prefix BEFORE writing the tail
        pref = at.blocks(full_block_ids)
        t_pref = pref.shape[0] * at.block_size
        ck_pref = ck_pages[pref].reshape(t_pref, -1)
        kr_pref = kr_pages[pref].reshape(t_pref, -1)
        pages = _write_latents(
            ck_pages, kr_pages, c_kv, k_rope, _prompt_slots(at, tail_block_ids, tail_len, s))
        w_uk, w_uv = _up_projections(w, cfg)
        with jax.named_scope("attn"):
            q_lat, q_wide = _absorb(w_uk, q_nope, q_rope, kr_pref.shape[-1], jnp.float32)
            k_nope = jnp.einsum("tr,rhn->thn", c_kv, w_uk)
            v_chunk = jnp.einsum("tr,rhv->thv", c_kv, w_uv)
            out = _prompt_attention(
                q_nope, q_rope, k_nope, k_rope, v_chunk, tail_len, scale,
                prefix=(q_lat, q_wide, ck_pref, kr_pref, jnp.arange(t_pref) < start_pos, w_uv),
            ).astype(cfg.dtype)
        return mm(out.reshape(s, -1), w["wo"]), *pages

    x, new_cache = _forward(
        params, cfg, x, kv_cache, attend, valid=jnp.arange(s) < tail_len)
    last = x[jnp.maximum(tail_len - 1, 0)]
    logits = _logits(params, cfg, last[None])[0]
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_decode(
    params, cfg: DeepseekConfig, token_ids, kv_cache, block_tables, context_lens,
    slot_ids, cos, sin, *, attention: str = "jax",
):
    """Batched single-token decode → (logits [batch, vocab], new cache),
    absorbed: ``attention="pallas"`` runs the MLA decode kernel on the flat
    pages (ops/pallas/mla_attention.py), anything else the XLA gather
    fallback."""
    b = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(context_lens - 1, 0)
    scale = float(cfg.attn_scale)

    def attend(w, attn_in, ck_pages, kr_pages, at):
        # apply_rope expects a seq axis: insert and drop it
        q_nope, q_rope = _queries(
            w, attn_in, cfg,
            lambda q: apply_rope(q[:, None], positions[:, None], cos, sin)[:, 0])
        c_kv, k_rope = _latent_kv(w, attn_in, cfg)
        k_rope = apply_rope(k_rope[:, None, None, :], positions[:, None], cos, sin)[:, 0, 0]
        ck_pages, kr_pages = _write_latents(
            ck_pages, kr_pages, c_kv, k_rope, at.slots(slot_ids))
        w_uk, w_uv = _up_projections(w, cfg)
        q_lat, q_wide = _absorb(w_uk, q_nope, q_rope, kr_pages.shape[-1], cfg.dtype)
        tables = at.blocks(block_tables)
        with jax.named_scope("attn"):
            if attention.startswith("pallas"):
                from dynamo_tpu.ops.pallas.mla_attention import mla_paged_attention_decode

                ctx = mla_paged_attention_decode(
                    q_lat, q_wide, ck_pages, kr_pages, tables, context_lens,
                    scale=scale, interpret=attention == "pallas_interpret",
                )
            else:
                length = tables.shape[1] * at.block_size
                ck = ck_pages[tables].reshape(b, length, -1)
                kr = kr_pages[tables].reshape(b, length, -1)
                visible = jnp.arange(length)[None, :] < context_lens[:, None]
                weights = _gathered_scores(q_lat, q_wide, ck, kr, visible[:, None, :], scale)
                ctx = jnp.einsum("bht,btr->bhr", weights, ck.astype(jnp.float32))
        return _decompress(w, w_uv, ctx, cfg), ck_pages, kr_pages

    x, new_cache = _forward(params, cfg, x, kv_cache, attend, valid=context_lens > 0)
    logits = _logits(params, cfg, x)
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_unified(
    params,
    cfg: DeepseekConfig,
    token_ids,      # [T] int32 — flat ragged token batch
    kv_cache,
    block_tables,   # [lanes, max_blocks] int32
    context_lens,   # [lanes] int32 incl. each lane's span end
    token_pos,      # [T] int32 absolute position (-1 = pad)
    token_slot,     # [T] int32 flat cache slot (OOB = pad)
    token_lane,     # [T] int32 owning lane (OOB = pad)
    span_lane,      # [T] int32 (pack_spans): block t's span s at t*tb+s
    span_first,     # [T] int32 first page ordinal of the span
    span_count,     # [T] int32 pages in the span (0 = unused)
    kv_steps,       # [T // tb_tokens] int32 KV steps per token block
    sample_rows,    # [lanes] int32 flat index of span's LAST token
    cos,
    sin,
    *,
    attention: str = "jax",     # "jax" | "pallas" | "pallas_interpret"
    tb_tokens: int = 8,
):
    """Ragged unified-batch forward for the MLA family: mixed spans +
    decode tokens in one launch against the latent cache (the llama
    unified contract).  Every token writes its latent and rotated key at its
    cache slot; the expert layers route per token and walk the live rows only.

    Where a key lies decides its form (``attention="pallas"``).  A key that
    is a row of THIS window (its lane's rows at flat index <= the query's:
    the engine packs a span in ascending position) is attended DECOMPRESSED,
    from the window's own ``c_kv``: one flash launch a layer, 640 products a
    (query, key, head) where the absorbed form takes 2,304.  A key on a page
    resident before the window (its lane's positions below the lane's first
    row here, ``last_resident_pos``) is attended ABSORBED by the page walk,
    whose spans (``pack_spans``) the caller built from those same resident
    positions.  The two parts of a row merge under one softmax by their
    log-sum-exp.  A whole prompt has no resident part, a decode row is a
    window of one beside its context, a pad has neither.  The XLA route
    attends absorbed in one piece, through the cache."""
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(token_pos, 0)
    lanes = context_lens.shape[0]
    live = (token_pos >= 0) & (token_lane >= 0) & (token_lane < lanes)
    scale = float(cfg.attn_scale)
    pallas = attention.startswith("pallas")
    if pallas:
        from dynamo_tpu.ops.pallas.mla_attention import (
            last_resident_pos,
            ragged_mla_attention,
            ragged_mla_attention_window,
        )

        resident_pos = last_resident_pos(token_lane, token_pos, lanes, jnp)

    def attend(w, attn_in, ck_pages, kr_pages, at):
        q_nope, q_rope = _queries(
            w, attn_in, cfg, lambda q: apply_rope(q, positions, cos, sin))
        c_kv, k_rope = _latent_kv(w, attn_in, cfg)
        k_rope = apply_rope(k_rope[:, None, :], positions, cos, sin)[:, 0]
        # (as wide as the page stores it, for the cache and the window launch alike)
        k_rope = jnp.pad(k_rope, ((0, 0), (0, kr_pages.shape[-1] - k_rope.shape[-1])))
        ck_pages, kr_pages = _write_latents(
            ck_pages, kr_pages, c_kv, k_rope, at.slots(token_slot))
        w_uk, w_uv = _up_projections(w, cfg)
        q_lat, q_wide = _absorb(w_uk, q_nope, q_rope, kr_pages.shape[-1], cfg.dtype)
        tables = at.blocks(block_tables)
        with jax.named_scope("attn"):
            if not pallas:
                from dynamo_tpu.ops.attention import ragged_mla_paged_attention

                ctx = ragged_mla_paged_attention(
                    q_lat, q_wide, ck_pages, kr_pages, tables, token_lane,
                    token_pos, scale=scale,
                )
                return _decompress(w, w_uv, ctx, cfg), ck_pages, kr_pages
            interpret = attention == "pallas_interpret"
            ctx, lse_resident = ragged_mla_attention(
                q_lat, q_wide, ck_pages, kr_pages, token_lane, resident_pos,
                tables, span_lane, span_first, span_count, kv_steps,
                scale=scale, tb_tokens=tb_tokens, interpret=interpret,
                with_lse=True,
            )
            up = lambda w_u: jnp.einsum(  # noqa: E731 — the rows' own keys / values
                "tr,rhd->thd", c_kv, w_u, preferred_element_type=jnp.float32
            ).astype(cfg.dtype)
            out, lse_window = ragged_mla_attention_window(
                q_nope, q_wide, up(w_uk), k_rope, up(w_uv), token_lane, token_pos,
                lanes=lanes, scale=scale, interpret=interpret,
            )
            out = _merge_parts(ctx, lse_resident, out, lse_window, w_uv).astype(cfg.dtype)
        return mm(out, w["wo"]), ck_pages, kr_pages

    x, new_cache = _forward(params, cfg, x, kv_cache, attend, valid=live)
    rows = x[sample_rows]  # [lanes, h] — junk for hole lanes, caller-gated
    logits = _logits(params, cfg, rows)
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_verify(
    params, cfg: DeepseekConfig, token_ids, kv_cache, block_tables,
    context_lens, slot_ids, cos, sin, *, attention: str = "jax",
):
    """Speculative-verification forward for the MLA family (contract:
    llama_forward_verify): w window queries a lane against the latent cache,
    absorbed.  Window tokens run position-major (see mixtral_forward_verify
    on why dispatch order matters for the expert layers)."""
    b, w_len = token_ids.shape
    x = params["embed"][token_ids.T.reshape(-1)].astype(cfg.dtype)
    positions = jnp.maximum(
        context_lens[:, None] - w_len + jnp.arange(w_len)[None, :], 0
    )
    flat_slots = slot_ids.T.reshape(-1)
    scale = float(cfg.attn_scale)
    H = cfg.num_heads

    def to_bw(t, *tail):
        return position_major_to_batch(t, w_len, b, *tail)

    def attend(w, attn_in, ck_pages, kr_pages, at):
        q = to_bw(_project_q(w, attn_in, cfg), H, cfg.qk_head_dim)     # [b, w, H, d]
        q_nope = q[..., : cfg.qk_nope_head_dim]
        q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions, cos, sin)
        c_kv, k_rope = _latent_kv(w, attn_in, cfg)                     # [w*b, *]
        k_rope = apply_rope(
            to_bw(k_rope, cfg.qk_rope_head_dim)[:, :, None, :], positions, cos, sin
        )[:, :, 0].transpose(1, 0, 2).reshape(w_len * b, -1)
        ck_pages, kr_pages = _write_latents(
            ck_pages, kr_pages, c_kv, k_rope, at.slots(flat_slots))
        w_uk, w_uv = _up_projections(w, cfg)
        q_lat, q_wide = _absorb(w_uk, q_nope, q_rope, kr_pages.shape[-1], cfg.dtype)
        tables = at.blocks(block_tables)
        with jax.named_scope("attn"):
            if attention.startswith("pallas"):
                from dynamo_tpu.ops.pallas.mla_attention import (
                    mla_paged_window_attention_decode,
                )

                ctx = mla_paged_window_attention_decode(
                    q_lat, q_wide, ck_pages, kr_pages, tables, context_lens,
                    scale=scale, interpret=attention == "pallas_interpret",
                )
            else:
                length = tables.shape[1] * at.block_size
                ck = ck_pages[tables].reshape(b, length, -1)
                kr = kr_pages[tables].reshape(b, length, -1)
                q_pos = context_lens[:, None] - w_len + jnp.arange(w_len)[None, :]
                visible = jnp.arange(length)[None, None, :] <= q_pos[:, :, None]
                weights = _gathered_scores(q_lat, q_wide, ck, kr, visible[:, None], scale)
                ctx = jnp.einsum("bhwt,btr->bwhr", weights, ck.astype(jnp.float32))
        out = _decompress(w, w_uv, ctx, cfg).reshape(b, w_len, -1)
        return out.transpose(1, 0, 2).reshape(w_len * b, -1), ck_pages, kr_pages

    x, new_cache = _forward(params, cfg, x, kv_cache, attend)
    logits = _logits(params, cfg, x)
    logits = logits.reshape(w_len, b, -1).transpose(1, 0, 2)
    return logits.astype(jnp.float32), new_cache


# ------------------------------------------------------------------ weights


def load_hf_weights(cfg: DeepseekConfig, model_dir) -> dict:
    """Load HF DeepSeek-V2/V3 safetensors into the dense/moe layer-stacked
    pytree.  MLA projections split and transpose:
    ``kv_b_proj [H*(nope+v), R]`` splits into ``w_uk [R, H*nope]`` and
    ``w_uv [R, H*v]`` (per-head row grouping), the latent down-projection
    ``kv_a_proj_with_mqa`` transposes into ``w_dkv [h, R+P]``."""
    import numpy as np

    from dynamo_tpu.models.hf_io import read_safetensors

    tensors = read_safetensors(model_dir)   # (none there: FileNotFoundError, seeded weights)
    if cfg.hc_mult > 1:
        raise NotImplementedError(
            f"hc_mult {cfg.hc_mult}: no checkpoint with residual streams has been read "
            "here, so the names and layouts of its mixing tensors (a sublayer's phi, "
            "alpha and bias: params['*_layers']['hc_phi' / 'hc_alpha' / 'hc_bias']) are "
            "not known; such a model is served from seeded weights only (init_params)"
        )

    def get(name: str, transpose: bool = False):
        t = tensors[name]
        if transpose:
            t = t.T
        return np.asarray(t)

    H, nope, v_dim, r = (
        cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    )

    def deinterleave(cols: "np.ndarray") -> "np.ndarray":
        """HF DeepSeek stores rope feature dims interleaved (the official
        modeling code de-interleaves activations before rotate-half; vLLM
        loads with is_neox_style=False).  Our apply_rope is split-half
        (NeoX), so bake the permutation into the projection's rope output
        columns once at load time."""
        return np.concatenate([cols[..., 0::2], cols[..., 1::2]], axis=-1)

    def fix_q_rope(mat: "np.ndarray") -> "np.ndarray":
        """mat [in, H*qk_head]: de-interleave each head's rope slice."""
        shaped = mat.reshape(mat.shape[0], H, nope + cfg.qk_rope_head_dim).copy()
        shaped[..., nope:] = deinterleave(shaped[..., nope:])
        return shaped.reshape(mat.shape[0], -1)

    def attn_leaves(i: int) -> dict:
        p = f"model.layers.{i}.self_attn"
        kv_b = get(f"{p}.kv_b_proj.weight")          # [H*(nope+v), R]
        kv_b = kv_b.reshape(H, nope + v_dim, r)
        w_uk = kv_b[:, :nope, :].transpose(2, 0, 1).reshape(r, H * nope)
        w_uv = kv_b[:, nope:, :].transpose(2, 0, 1).reshape(r, H * v_dim)
        w_dkv = get(f"{p}.kv_a_proj_with_mqa.weight", True).copy()
        w_dkv[:, r:] = deinterleave(w_dkv[:, r:])  # rope key columns
        out = {
            "attn_norm": get(f"model.layers.{i}.input_layernorm.weight"),
            "w_dkv": w_dkv,
            "kv_norm": get(f"{p}.kv_a_layernorm.weight"),
            "w_uk": w_uk,
            "w_uv": w_uv,
            "wo": get(f"{p}.o_proj.weight", True),
            "mlp_norm": get(f"model.layers.{i}.post_attention_layernorm.weight"),
        }
        if cfg.q_lora_rank:
            out["w_dq"] = get(f"{p}.q_a_proj.weight", True)
            out["q_norm"] = get(f"{p}.q_a_layernorm.weight")
            out["w_uq"] = fix_q_rope(get(f"{p}.q_b_proj.weight", True))
        else:
            out["wq"] = fix_q_rope(get(f"{p}.q_proj.weight", True))
        return out

    def stack(dicts: list[dict]) -> dict:
        return {
            # e_score_correction_bias must stay fp32: bf16 rounding flips
            # near-tied expert selections vs the reference
            k: jnp.asarray(
                np.stack([d[k] for d in dicts]),
                jnp.float32 if k == "router_bias" else cfg.dtype,
            )
            for k in dicts[0]
        }

    dense, moe = [], []
    for i in range(cfg.num_layers):
        leaves = attn_leaves(i)
        mlp = f"model.layers.{i}.mlp"
        if i < cfg.first_k_dense:
            leaves.update(
                w_gate=get(f"{mlp}.gate_proj.weight", True),
                w_up=get(f"{mlp}.up_proj.weight", True),
                w_down=get(f"{mlp}.down_proj.weight", True),
            )
            dense.append(leaves)
        else:
            if cfg.scoring_func == "sigmoid":
                leaves["router_bias"] = get(f"{mlp}.gate.e_score_correction_bias")
            leaves.update(
                w_router=get(f"{mlp}.gate.weight", True),
                w_gate=np.stack([
                    get(f"{mlp}.experts.{e}.gate_proj.weight", True)
                    for e in range(cfg.num_experts)
                ]),
                w_up=np.stack([
                    get(f"{mlp}.experts.{e}.up_proj.weight", True)
                    for e in range(cfg.num_experts)
                ]),
                w_down=np.stack([
                    get(f"{mlp}.experts.{e}.down_proj.weight", True)
                    for e in range(cfg.num_experts)
                ]),
            )
            if cfg.n_shared_experts:
                leaves.update(
                    ws_gate=get(f"{mlp}.shared_experts.gate_proj.weight", True),
                    ws_up=get(f"{mlp}.shared_experts.up_proj.weight", True),
                    ws_down=get(f"{mlp}.shared_experts.down_proj.weight", True),
                )
            moe.append(leaves)

    params: dict = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), cfg.dtype),
        "final_norm": jnp.asarray(get("model.norm.weight"), cfg.dtype),
    }
    if dense:
        params["dense_layers"] = stack(dense)
    if moe:
        params["moe_layers"] = stack(moe)
    if not cfg.tie_word_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = jnp.asarray(get("lm_head.weight", True), cfg.dtype)
    return params
