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

Cache layout reuses the engine's {"k", "v"} pytree so paged bookkeeping,
extract/inject and disagg KV shipping work unchanged:
    k: [layers, num_blocks, block_size, 1, kv_lora_rank]   (latent)
    v: [layers, num_blocks, block_size, 1, qk_rope_head_dim] (rope key)

Routing: V2-style renormalized softmax top-k, or V3/R1 aux-free sigmoid
routing (e_score_correction_bias steers selection only, group-limited
top-k) behind ``scoring_func="sigmoid"``.  Long context: YaRN rope scaling
via the HF ``rope_scaling`` dict, including the mscale attention-temperature
correction (``attn_scale``).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.attention import NEG_INF, write_decode_kv, write_prefill_kv
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.attention import position_major_to_batch
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
    dtype: Any = jnp.bfloat16

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


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _attn_params(cfg: DeepseekConfig, keys, n: int) -> dict:
    h = cfg.hidden_size
    hd_q = cfg.num_heads * cfg.qk_head_dim

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    params = {
        "attn_norm": jnp.ones((n, h), cfg.dtype),
        "w_dkv": norm_init(keys[0], (n, h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), h),
        "kv_norm": jnp.ones((n, cfg.kv_lora_rank), cfg.dtype),
        "w_uk": norm_init(
            keys[1], (n, cfg.kv_lora_rank, cfg.num_heads * cfg.qk_nope_head_dim),
            cfg.kv_lora_rank,
        ),
        "w_uv": norm_init(
            keys[2], (n, cfg.kv_lora_rank, cfg.num_heads * cfg.v_head_dim),
            cfg.kv_lora_rank,
        ),
        "wo": norm_init(keys[3], (n, cfg.num_heads * cfg.v_head_dim, h),
                        cfg.num_heads * cfg.v_head_dim),
    }
    if cfg.q_lora_rank:
        params["w_dq"] = norm_init(keys[4], (n, h, cfg.q_lora_rank), h)
        params["q_norm"] = jnp.ones((n, cfg.q_lora_rank), cfg.dtype)
        params["w_uq"] = norm_init(keys[5], (n, cfg.q_lora_rank, hd_q), cfg.q_lora_rank)
    else:
        params["wq"] = norm_init(keys[4], (n, h, hd_q), h)
    return params


def init_params(cfg: DeepseekConfig, rng: jax.Array) -> dict:
    h = cfg.hidden_size
    kd, km = cfg.first_k_dense, cfg.num_moe_layers
    keys = jax.random.split(rng, 24)

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    params: dict = {
        "embed": norm_init(keys[0], (cfg.vocab_size, h), 1.0),
        "final_norm": jnp.ones((h,), cfg.dtype),
    }
    if kd:
        i = cfg.intermediate_size
        dense = _attn_params(cfg, keys[1:7], kd)
        dense.update(
            mlp_norm=jnp.ones((kd, h), cfg.dtype),
            w_gate=norm_init(keys[7], (kd, h, i), h),
            w_up=norm_init(keys[8], (kd, h, i), h),
            w_down=norm_init(keys[9], (kd, i, h), i),
        )
        params["dense_layers"] = dense
    if km:
        mi, e = cfg.moe_intermediate_size, cfg.num_experts
        si = cfg.n_shared_experts * mi
        moe = _attn_params(cfg, keys[10:16], km)
        moe.update(
            mlp_norm=jnp.ones((km, h), cfg.dtype),
            w_router=norm_init(keys[16], (km, h, e), h),
            **(
                {"router_bias": jnp.zeros((km, e), jnp.float32)}
                if cfg.scoring_func == "sigmoid" else {}
            ),
            w_gate=norm_init(keys[17], (km, e, h, mi), h),
            w_up=norm_init(keys[18], (km, e, h, mi), h),
            w_down=norm_init(keys[19], (km, e, mi, h), mi),
        )
        if si:
            moe.update(
                ws_gate=norm_init(keys[20], (km, h, si), h),
                ws_up=norm_init(keys[21], (km, h, si), h),
                ws_down=norm_init(keys[22], (km, si, h), si),
            )
        params["moe_layers"] = moe
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm_init(keys[23], (h, cfg.vocab_size), h)
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
# KV cache: latent + rope-key, tiny per token
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: DeepseekConfig, num_blocks: int, block_size: int, dtype=None):
    dtype = dtype or cfg.dtype
    return {
        "k": jnp.zeros((cfg.num_layers, num_blocks, block_size, 1, cfg.kv_lora_rank), dtype),
        "v": jnp.zeros((cfg.num_layers, num_blocks, block_size, 1, cfg.qk_rope_head_dim), dtype),
    }


def kv_cache_specs(cfg: DeepseekConfig) -> dict:
    # the latent is shared across heads — replicate across tp (it is ~4x
    # smaller than a GQA cache even unsharded)
    return {"k": P(None, None, None, None, None), "v": P(None, None, None, None, None)}


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


def _project_q(w, x, cfg: DeepseekConfig):
    """x [t, h] → q [t, heads, qk_head_dim] (optionally through the q-lora
    bottleneck)."""
    t = x.shape[0]
    if cfg.q_lora_rank:
        q = mm(rms_norm(mm(x, w["w_dq"]), w["q_norm"], cfg.rms_norm_eps), w["w_uq"])
    else:
        q = mm(x, w["wq"])
    return q.reshape(t, cfg.num_heads, cfg.qk_head_dim)


def _latent_kv(w, x, cfg: DeepseekConfig):
    """x [t, h] → (c_kv [t, r] normalized, k_rope [t, rope_dim] un-roped)."""
    dkv = mm(x, w["w_dkv"])
    c_kv = rms_norm(dkv[:, : cfg.kv_lora_rank], w["kv_norm"], cfg.rms_norm_eps)
    k_rope = dkv[:, cfg.kv_lora_rank :]
    return c_kv, k_rope


def _mla_prefill_attn(w, x, cfg: DeepseekConfig, positions, seq_len, k_layer, v_layer,
                      block_ids, cos, sin):
    """Dense causal MLA attention for one prefill chunk; writes latents to
    the paged cache.  Returns (attn_out [s, h], (k_layer, v_layer))."""
    s = x.shape[0]
    H = cfg.num_heads
    q = _project_q(w, x, cfg)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cos, sin)

    c_kv, k_rope = _latent_kv(w, x, cfg)
    k_rope = apply_rope(k_rope[:, None, :], positions, cos, sin)[:, 0]

    k_layer, v_layer = write_prefill_kv(
        k_layer, v_layer, c_kv[:, None, :], k_rope[:, None, :], block_ids, seq_len
    )

    # decompress K/V for the in-chunk dense attention (prefill is
    # compute-bound; this keeps the big matmuls on the MXU)
    w_uk = w["w_uk"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    w_uv = w["w_uv"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    k_nope = jnp.einsum("tr,rhn->thn", c_kv, w_uk)
    v = jnp.einsum("tr,rhv->thv", c_kv, w_uv)

    scale = jnp.float32(cfg.attn_scale)
    logits = (
        jnp.einsum("qhn,khn->hqk", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
        + jnp.einsum("qhp,kp->hqk", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    ) * scale
    pos = jnp.arange(s)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < seq_len)  # [q, k]
    logits = jnp.where(mask[None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hqk,khv->qhv", weights, v.astype(jnp.float32)).astype(cfg.dtype)
    return mm(out.reshape(s, -1), w["wo"]), (k_layer, v_layer)


def _mla_prefill_attn_with_prefix(
    w, x, cfg: DeepseekConfig, positions, tail_len, start_pos, k_layer, v_layer,
    full_block_ids, tail_block_ids, cos, sin,
):
    """Continued MLA prefill: the tail's queries attend to the resident
    prefix LATENTS (absorbed form — scores in latent space, context
    decompressed once) jointly with the in-chunk dense attention under one
    softmax; only the tail's latents are written.  Enables prefix-cache
    reuse and chunked prefill for the MLA family."""
    s = x.shape[0]
    H = cfg.num_heads
    q = _project_q(w, x, cfg)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cos, sin)

    c_kv, k_rope = _latent_kv(w, x, cfg)
    k_rope = apply_rope(k_rope[:, None, :], positions, cos, sin)[:, 0]

    # gather the resident prefix BEFORE writing the tail
    block_size = k_layer.shape[1]
    t_pref = full_block_ids.shape[0] * block_size
    ck_pref = k_layer[full_block_ids].reshape(t_pref, cfg.kv_lora_rank)
    kr_pref = v_layer[full_block_ids].reshape(t_pref, cfg.qk_rope_head_dim)

    k_layer, v_layer = write_prefill_kv(
        k_layer, v_layer, c_kv[:, None, :], k_rope[:, None, :], tail_block_ids, tail_len
    )

    w_uk = w["w_uk"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    w_uv = w["w_uv"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    scale = jnp.float32(cfg.attn_scale)

    # prefix scores, absorbed: q_lat·ck + q_rope·kr (identical math to
    # decompressing the prefix keys, without materializing them per head)
    q_lat = jnp.einsum(
        "qhn,rhn->qhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)
    )
    sp = (
        jnp.einsum("qhr,tr->hqt", q_lat, ck_pref.astype(jnp.float32))
        + jnp.einsum("qhp,tp->hqt", q_rope.astype(jnp.float32), kr_pref.astype(jnp.float32))
    ) * scale
    pref_valid = jnp.arange(t_pref)[None, :] < start_pos  # [1, Tp]
    sp = jnp.where(pref_valid[None], sp, NEG_INF)

    # in-chunk dense scores (decompressed, as in _mla_prefill_attn)
    k_nope = jnp.einsum("tr,rhn->thn", c_kv, w_uk)
    sc = (
        jnp.einsum("qhn,khn->hqk", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
        + jnp.einsum("qhp,kp->hqk", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    ) * scale
    pos = jnp.arange(s)
    chunk_mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < tail_len)
    sc = jnp.where(chunk_mask[None], sc, NEG_INF)

    # one softmax across prefix + chunk keys
    logits = jnp.concatenate([sp, sc], axis=-1)  # [H, s, Tp + s]
    weights = jax.nn.softmax(logits, axis=-1)
    wp, wc = weights[..., :t_pref], weights[..., t_pref:]

    # prefix context in latent space, decompressed once; chunk context dense
    ctx_lat = jnp.einsum("hqt,tr->qhr", wp, ck_pref.astype(jnp.float32))
    out_pref = jnp.einsum("qhr,rhv->qhv", ctx_lat, w_uv.astype(jnp.float32))
    v_chunk = jnp.einsum("tr,rhv->thv", c_kv, w_uv)
    out_chunk = jnp.einsum("hqk,khv->qhv", wc, v_chunk.astype(jnp.float32))
    out = (out_pref + out_chunk).astype(cfg.dtype)
    return mm(out.reshape(s, -1), w["wo"]), (k_layer, v_layer)


def _mla_decode_attn(w, x, cfg: DeepseekConfig, positions, k_layer, v_layer,
                     block_tables, context_lens, slot_ids, cos, sin,
                     attention: str = "jax"):
    """Absorbed-form batched decode attention against the latent cache.

    ``attention="pallas"`` runs the MLA paged-attention kernel
    (ops/pallas/mla_attention.py): page latents stream VMEM-ward via the
    block table with online softmax — no [B, maxb*bs, R] gather
    materialized in HBM.  The XLA gather path is the portable fallback.
    """
    b = x.shape[0]
    H = cfg.num_heads
    q = _project_q(w, x, cfg)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope[:, None], positions[:, None], cos, sin)[:, 0]

    c_kv_new, k_rope_new = _latent_kv(w, x, cfg)
    k_rope_new = apply_rope(k_rope_new[:, None, None, :], positions[:, None], cos, sin)[:, 0]
    k_layer, v_layer = write_decode_kv(
        k_layer, v_layer, c_kv_new[:, None, :], k_rope_new, slot_ids
    )

    # absorb q through the k up-projection: scores live in latent space
    w_uk = w["w_uk"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    w_uv = w["w_uv"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))

    num_blocks, block_size = k_layer.shape[0], k_layer.shape[1]
    scale = float(cfg.attn_scale)

    if attention in ("pallas", "pallas_interpret"):
        from dynamo_tpu.ops.pallas.mla_attention import mla_paged_attention_decode

        ctx = mla_paged_attention_decode(
            q_lat, q_rope,
            k_layer.reshape(num_blocks, block_size, cfg.kv_lora_rank),
            v_layer.reshape(num_blocks, block_size, cfg.qk_rope_head_dim),
            block_tables, context_lens,
            scale=scale, interpret=attention == "pallas_interpret",
        )
    else:
        max_blocks = block_tables.shape[1]
        length = max_blocks * block_size
        ck = k_layer[block_tables].reshape(b, length, cfg.kv_lora_rank)
        kr = v_layer[block_tables].reshape(b, length, cfg.qk_rope_head_dim)
        logits = (
            jnp.einsum("bhr,btr->bht", q_lat, ck.astype(jnp.float32))
            + jnp.einsum("bhp,btp->bht", q_rope.astype(jnp.float32), kr.astype(jnp.float32))
        ) * scale
        valid = jnp.arange(length)[None, :] < context_lens[:, None]
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
        weights = jax.nn.softmax(logits, axis=-1)
        # context in latent space
        ctx = jnp.einsum("bht,btr->bhr", weights, ck.astype(jnp.float32))
    # decompress through the v up-projection
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(jnp.float32)).astype(cfg.dtype)
    return mm(out.reshape(b, -1), w["wo"]), (k_layer, v_layer)


def _mla_unified_attn(w, x, cfg: DeepseekConfig, positions, token_pos,
                      token_lane, token_slot, k_layer, v_layer, block_tables,
                      span_lane, span_first, span_count, kv_steps, cos, sin,
                      attention: str = "jax", tb_tokens: int = 8):
    """Absorbed-form ragged unified-batch MLA attention: the flat token
    axis carries chunked-prefill spans + decode tokens, every token writes
    its latent before anyone reads, scores stay in latent space per token.
    ``attention="pallas"`` runs the packed-lane ragged MLA kernel; the XLA
    twin (ops/attention.ragged_mla_paged_attention) is the fallback."""
    t = x.shape[0]
    H = cfg.num_heads
    q = _project_q(w, x, cfg)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cos, sin)

    c_kv, k_rope = _latent_kv(w, x, cfg)
    k_rope = apply_rope(k_rope[:, None, :], positions, cos, sin)
    k_layer, v_layer = write_decode_kv(
        k_layer, v_layer, c_kv[:, None, :], k_rope, token_slot
    )

    w_uk = w["w_uk"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    w_uv = w["w_uv"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    q_lat = jnp.einsum(
        "thn,rhn->thr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)
    )

    num_blocks, block_size = k_layer.shape[0], k_layer.shape[1]
    scale = float(cfg.attn_scale)
    ck3 = k_layer.reshape(num_blocks, block_size, cfg.kv_lora_rank)
    kr3 = v_layer.reshape(num_blocks, block_size, cfg.qk_rope_head_dim)

    if attention in ("pallas", "pallas_interpret"):
        from dynamo_tpu.ops.pallas import ragged_mla_attention

        ctx = ragged_mla_attention(
            q_lat, q_rope, ck3, kr3, token_lane, token_pos,
            block_tables, span_lane, span_first, span_count, kv_steps,
            scale=scale, tb_tokens=tb_tokens,
            interpret=attention == "pallas_interpret",
        )
    else:
        from dynamo_tpu.ops.attention import ragged_mla_paged_attention

        ctx = ragged_mla_paged_attention(
            q_lat, q_rope, ck3, kr3, block_tables, token_lane, token_pos,
            scale=scale,
        )
    out = jnp.einsum("thr,rhv->thv", ctx, w_uv.astype(jnp.float32)).astype(cfg.dtype)
    return mm(out.reshape(t, -1), w["wo"]), (k_layer, v_layer)


def _mla_window_attn(w, x, cfg: DeepseekConfig, positions, k_layer, v_layer,
                     block_tables, context_lens, flat_slots, cos, sin,
                     b: int, w_len: int, attention: str = "jax"):
    """Multi-query absorbed-form attention for speculative verification:
    w window queries per lane against the latent cache.
    ``attention="pallas"`` runs the MLA window kernel (W queries folded
    into the head axis, latent pages streamed once for all W positions);
    the XLA gather path is the portable fallback.
    ``x`` is position-major flat [w*b, h] (see mixtral_forward_verify on
    why dispatch order matters for the MoE layers)."""
    H = cfg.num_heads

    def to_bw(t, *tail):
        return position_major_to_batch(t, w_len, b, *tail)

    q = _project_q(w, x, cfg)                    # [w*b, H, qk_head_dim]
    q = to_bw(q, H, cfg.qk_head_dim)             # [b, w, H, d]
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cos, sin)  # [b, w, H, p]

    c_kv_new, k_rope_new = _latent_kv(w, x, cfg)  # [w*b, r], [w*b, p]
    k_rope_bw = to_bw(k_rope_new, cfg.qk_rope_head_dim)[:, :, None, :]  # [b, w, 1, p]
    k_rope_bw = apply_rope(k_rope_bw, positions, cos, sin)
    k_layer, v_layer = write_decode_kv(
        k_layer, v_layer,
        c_kv_new[:, None, :],
        k_rope_bw.transpose(1, 0, 2, 3).reshape(w_len * b, 1, -1),
        flat_slots,
    )

    w_uk = w["w_uk"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    w_uv = w["w_uv"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    q_lat = jnp.einsum(
        "bwhn,rhn->bwhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)
    )

    num_blocks, block_size = k_layer.shape[0], k_layer.shape[1]
    if attention in ("pallas", "pallas_interpret"):
        from dynamo_tpu.ops.pallas.mla_attention import (
            mla_paged_window_attention_decode,
        )

        ctx = mla_paged_window_attention_decode(
            q_lat, q_rope,
            k_layer.reshape(num_blocks, block_size, cfg.kv_lora_rank),
            v_layer.reshape(num_blocks, block_size, cfg.qk_rope_head_dim),
            block_tables, context_lens,
            scale=float(cfg.attn_scale),
            interpret=attention == "pallas_interpret",
        )
    else:
        max_blocks = block_tables.shape[1]
        length = max_blocks * block_size
        ck = k_layer[block_tables].reshape(b, length, cfg.kv_lora_rank)
        kr = v_layer[block_tables].reshape(b, length, cfg.qk_rope_head_dim)
        logits = (
            jnp.einsum("bwhr,btr->bhwt", q_lat, ck.astype(jnp.float32))
            + jnp.einsum("bwhp,btp->bhwt", q_rope.astype(jnp.float32), kr.astype(jnp.float32))
        ) * float(cfg.attn_scale)
        q_pos = context_lens[:, None] - w_len + jnp.arange(w_len)[None, :]   # [b, w]
        kv_pos = jnp.arange(length)[None, None, :]                            # [1, 1, t]
        mask = kv_pos <= q_pos[:, :, None]                                    # [b, w, t]
        logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
        weights = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bhwt,btr->bwhr", weights, ck.astype(jnp.float32))
    out = jnp.einsum("bwhr,rhv->bwhv", ctx, w_uv.astype(jnp.float32)).astype(cfg.dtype)
    flat = out.transpose(1, 0, 2, 3).reshape(w_len * b, -1)
    return mm(flat, w["wo"]), (k_layer, v_layer)


def _dense_mlp(w, x):
    return mm(jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]), w["w_down"])


def _moe_mlp(w, x, cfg: DeepseekConfig):
    routed = moe_ffn(
        x, w["w_router"], w["w_gate"], w["w_up"], w["w_down"],
        top_k=cfg.experts_per_token,
        router_bias=w.get("router_bias"),
        scoring="sigmoid_noaux" if cfg.scoring_func == "sigmoid" else "softmax",
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        norm_topk_prob=cfg.norm_topk_prob,
    )
    out = routed * jnp.asarray(cfg.routed_scaling_factor, routed.dtype)
    if cfg.n_shared_experts:
        out = out + mm(jax.nn.silu(mm(x, w["ws_gate"])) * mm(x, w["ws_up"]), w["ws_down"])
    return out


def _run_stack(params_key, mlp_fn, x, cache_k, cache_v, attn_fn, cfg):
    """Scan one homogeneous layer stack, threading its cache slice."""

    def layer(x, layer_in):
        w, k_layer, v_layer = layer_in
        attn_in = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
        attn_out, (k_layer, v_layer) = attn_fn(w, attn_in, k_layer, v_layer)
        x = x + attn_out
        mlp_in = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
        x = x + mlp_fn(w, mlp_in)
        return x, (k_layer, v_layer)

    return jax.lax.scan(layer, x, (params_key, cache_k, cache_v))


def _forward(params, cfg: DeepseekConfig, x, kv_cache, attn_fn):
    """Shared trunk: dense stack then MoE stack, cache split on the layer
    axis and re-concatenated."""
    kd = cfg.first_k_dense
    k_cache, v_cache = kv_cache["k"], kv_cache["v"]
    new_k_parts, new_v_parts = [], []
    if kd:
        x, (nk, nv) = _run_stack(
            params["dense_layers"], lambda w, t: _dense_mlp(w, t),
            x, k_cache[:kd], v_cache[:kd], attn_fn, cfg,
        )
        new_k_parts.append(nk)
        new_v_parts.append(nv)
    if cfg.num_moe_layers:
        x, (nk, nv) = _run_stack(
            params["moe_layers"], lambda w, t: _moe_mlp(w, t, cfg),
            x, k_cache[kd:], v_cache[kd:], attn_fn, cfg,
        )
        new_k_parts.append(nk)
        new_v_parts.append(nv)
    new_cache = {
        "k": jnp.concatenate(new_k_parts) if len(new_k_parts) > 1 else new_k_parts[0],
        "v": jnp.concatenate(new_v_parts) if len(new_v_parts) > 1 else new_v_parts[0],
    }
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_cache


def _logits(params, cfg, x):
    if cfg.tie_word_embeddings:
        return x @ params["embed"].T.astype(x.dtype)
    return mm(x, params["lm_head"])


def deepseek_forward_prefill(
    params, cfg: DeepseekConfig, token_ids, kv_cache, block_ids, seq_len, start_pos,
    cos, sin,
):
    """Single-sequence prefill → (last-token logits [vocab], new cache)."""
    s = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)

    def attn(w, attn_in, k_layer, v_layer):
        return _mla_prefill_attn(
            w, attn_in, cfg, positions, seq_len, k_layer, v_layer, block_ids, cos, sin
        )

    x, new_cache = _forward(params, cfg, x, kv_cache, attn)
    last = x[jnp.maximum(seq_len - 1, 0)]
    logits = _logits(params, cfg, last[None])[0]
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_prefill_with_prefix(
    params, cfg: DeepseekConfig, token_ids, kv_cache, full_block_ids,
    tail_block_ids, tail_len, start_pos, cos, sin,
):
    """Continued prefill over a reused prefix for the MLA family (same
    contract as llama_forward_prefill_with_prefix)."""
    s = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)

    def attn(w, attn_in, k_layer, v_layer):
        return _mla_prefill_attn_with_prefix(
            w, attn_in, cfg, positions, tail_len, start_pos, k_layer, v_layer,
            full_block_ids, tail_block_ids, cos, sin,
        )

    x, new_cache = _forward(params, cfg, x, kv_cache, attn)
    last = x[jnp.maximum(tail_len - 1, 0)]
    logits = _logits(params, cfg, last[None])[0]
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_decode(
    params, cfg: DeepseekConfig, token_ids, kv_cache, block_tables, context_lens,
    slot_ids, cos, sin, *, attention: str = "jax",
):
    """Batched single-token decode → (logits [batch, vocab], new cache).
    MLA decode runs the absorbed latent path; ``attention="pallas"``
    dispatches the MLA paged-attention kernel, anything else the XLA
    gather fallback."""
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(context_lens - 1, 0)

    def attn(w, attn_in, k_layer, v_layer):
        return _mla_decode_attn(
            w, attn_in, cfg, positions, k_layer, v_layer,
            block_tables, context_lens, slot_ids, cos, sin,
            attention=attention,
        )

    x, new_cache = _forward(params, cfg, x, kv_cache, attn)
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
    unified contract).  Every token writes its compressed latent + rope
    key at its cache slot before attention reads, so span tokens see
    their own in-window predecessors through the cache; the MoE stack
    routes per token exactly as the sparse-expert families' FFN does in
    the llama one."""
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(token_pos, 0)

    def attn(w, attn_in, k_layer, v_layer):
        return _mla_unified_attn(
            w, attn_in, cfg, positions, token_pos, token_lane, token_slot,
            k_layer, v_layer, block_tables, span_lane, span_first, span_count,
            kv_steps, cos, sin, attention=attention, tb_tokens=tb_tokens,
        )

    x, new_cache = _forward(params, cfg, x, kv_cache, attn)
    rows = x[sample_rows]  # [lanes, h] — junk for hole lanes, caller-gated
    logits = _logits(params, cfg, rows)
    return logits.astype(jnp.float32), new_cache


def deepseek_forward_verify(
    params, cfg: DeepseekConfig, token_ids, kv_cache, block_tables,
    context_lens, slot_ids, cos, sin, *, attention: str = "jax",
):
    """Speculative-verification forward for the MLA family (contract:
    llama_forward_verify).  Window tokens run position-major (see
    mixtral_forward_verify)."""
    b, w_len = token_ids.shape
    x = params["embed"][token_ids.T.reshape(-1)].astype(cfg.dtype)
    positions = jnp.maximum(
        context_lens[:, None] - w_len + jnp.arange(w_len)[None, :], 0
    )
    flat_slots = slot_ids.T.reshape(-1)

    def attn(w, attn_in, k_layer, v_layer):
        return _mla_window_attn(
            w, attn_in, cfg, positions, k_layer, v_layer, block_tables,
            context_lens, flat_slots, cos, sin, b, w_len, attention=attention,
        )

    x, new_cache = _forward(params, cfg, x, kv_cache, attn)
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

    tensors = read_safetensors(model_dir)

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
