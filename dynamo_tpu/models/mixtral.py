"""Mixtral-class sparse-MoE model (Mixtral 8x7B geometry and kin).

Same attention trunk as the llama family; the dense MLP is replaced by a
top-2-of-E MoE (dynamo_tpu/ops/moe.py).  Expert parallelism is sharding
annotation only: expert-stacked weights carry ``P(None, "ep", ...)`` and
GSPMD emits the dispatch/combine all-to-alls over ICI.

(The reference serves wide-EP MoE through SGLang+DeepEP —
examples/sglang/README.md:105; here the MoE engine is native.)
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.ops.attention import (
    dense_causal_attention,
    gather_prefix_kv,
    paged_decode_attention,
    position_major_to_batch,
    prefill_attention_with_prefix,
    ragged_paged_attention,
    window_attention,
    write_decode_kv,
    write_prefill_kv,
)
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.quant import mm
from dynamo_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 2.0
    # expert FFN width; 0 = same as intermediate_size (Mixtral proper).
    # Qwen3-MoE configs carry a distinct moe_intermediate_size.
    moe_intermediate_size: int = 0
    # renormalize top-k router weights (Mixtral yes; some Qwen3-MoE
    # variants disable it)
    norm_topk_prob: bool = True

    def __post_init__(self):
        # inherited field from LlamaConfig that NO mixtral-family forward
        # honors (prefill/decode/verify all run full attention) — refuse
        # rather than silently ignoring the window; from_hf_config parses
        # the HF window fields specifically so this fires on checkpoints
        if self.sliding_window is not None:
            raise NotImplementedError(
                "mixtral-family attention has no sliding-window mask"
            )

    @property
    def expert_intermediate_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @classmethod
    def mixtral_8x7b(cls) -> "MixtralConfig":
        return cls(
            vocab_size=32_000, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
            max_position_embeddings=32768, rope_theta=1e6,
            num_experts=8, experts_per_token=2,
        )

    @classmethod
    def tiny_moe(cls, vocab_size: int = 512) -> "MixtralConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=2048, rope_theta=10000.0,
            tie_word_embeddings=True, dtype=jnp.float32,
            num_experts=4, experts_per_token=2, capacity_factor=4.0,
        )

    @classmethod
    def from_hf_config(cls, config: dict | str | Path) -> "MixtralConfig":
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
            rope_theta=config.get("rope_theta", 1e6),
            num_experts=config.get("num_local_experts", 0)
            or config.get("num_experts", 8),
            experts_per_token=config.get("num_experts_per_tok", 2),
            moe_intermediate_size=config.get("moe_intermediate_size", 0) or 0,
            norm_topk_prob=config.get("norm_topk_prob", True),
            tie_word_embeddings=config.get("tie_word_embeddings", False),
            rope_scaling=config.get("rope_scaling"),
            qk_norm=config.get(
                "qk_norm", config.get("model_type") == "qwen3_moe"
            ),
            # parsed with HF's use_sliding_window/max_window_layers
            # semantics; a genuinely-windowed MoE checkpoint then hits the
            # __post_init__ refusal instead of silently running full
            # attention
            sliding_window=cls._resolve_sliding_window(config),
        )


def init_params(cfg: MixtralConfig, rng: jax.Array) -> dict:
    keys = jax.random.split(rng, 12)
    h, i, l_, e = (
        cfg.hidden_size, cfg.expert_intermediate_size, cfg.num_layers, cfg.num_experts
    )
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
            "w_router": norm_init(keys[5], (l_, h, e), h),
            "w_gate": norm_init(keys[6], (l_, e, h, i), h),
            "w_up": norm_init(keys[7], (l_, e, h, i), h),
            "w_down": norm_init(keys[8], (l_, e, i, h), i),
        },
    }
    if cfg.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((l_, cfg.head_dim), cfg.dtype)
        params["layers"]["k_norm"] = jnp.ones((l_, cfg.head_dim), cfg.dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm_init(keys[9], (h, cfg.vocab_size), h)
    return params


def param_specs(cfg: MixtralConfig) -> dict:
    """Experts sharded over 'ep'; within-expert FFN dims over 'tp'; attention
    head-sharded over 'tp' as in the llama family."""
    specs = {
        "embed": P(None, None),
        "final_norm": P(None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            "w_router": P(None, None, None),
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
        },
    }
    if cfg.qk_norm:
        specs["layers"]["q_norm"] = P(None, None)
        specs["layers"]["k_norm"] = P(None, None)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def _block(cfg: MixtralConfig, w, x, attn_fn, *, capacity_scale: float = 1.0):
    # capacity_scale: callers that split the batch before routing (the
    # pp-pipelined decode routes per MICROBATCH) scale the factor back up
    # so per-expert capacity matches what full-batch routing would allocate
    attn_in = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    x = x + attn_fn(attn_in)
    mlp_in = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    moe_out = moe_ffn(
        mlp_in, w["w_router"], w["w_gate"], w["w_up"], w["w_down"],
        top_k=cfg.experts_per_token,
        capacity_factor=cfg.capacity_factor * capacity_scale,
        norm_topk_prob=cfg.norm_topk_prob,
    )
    return x + moe_out


def _prefill_trunk(params, cfg: MixtralConfig, token_ids, kv_cache,
                   positions, cos, sin, attend, last_idx):
    """Shared prefill scaffold: embed → layer scan (qkv+rope handled here,
    the caller supplies only the attention math via ``attend``) → final
    norm → last-token logits.  Keeps the plain and continued-prefill paths
    from drifting apart."""
    s = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)

    def layer(x, layer_in):
        w, k_layer, v_layer = layer_in
        state = {}

        def attn(attn_in):
            q = mm(attn_in, w["wq"]).reshape(s, cfg.num_heads, cfg.head_dim)
            k = mm(attn_in, w["wk"]).reshape(s, cfg.num_kv_heads, cfg.head_dim)
            v = mm(attn_in, w["wv"]).reshape(s, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:  # Qwen3-MoE: per-head RMSNorm pre-rope
                q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
            attn_out, state["kv"] = attend(q, k, v, k_layer, v_layer)
            return mm(attn_out.reshape(s, -1), w["wo"])

        x = _block(cfg, w, x, attn)
        return x, state["kv"]

    x, (new_k, new_v) = jax.lax.scan(layer, x, (params["layers"], kv_cache["k"], kv_cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = x[jnp.maximum(last_idx - 1, 0)]
    logits = (
        last[None] @ params["embed"].T.astype(x.dtype)
        if cfg.tie_word_embeddings
        else mm(last[None], params["lm_head"])
    )[0]
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def mixtral_forward_prefill(
    params, cfg: MixtralConfig, token_ids, kv_cache, block_ids, seq_len, start_pos, cos, sin
):
    positions = start_pos + jnp.arange(token_ids.shape[0], dtype=jnp.int32)

    def attend(q, k, v, k_layer, v_layer):
        kv = write_prefill_kv(k_layer, v_layer, k, v, block_ids, seq_len)
        out = dense_causal_attention(q[None], k[None], v[None], seq_len[None])[0]
        return out, kv

    return _prefill_trunk(
        params, cfg, token_ids, kv_cache, positions, cos, sin, attend, seq_len
    )


def mixtral_forward_prefill_with_prefix(
    params, cfg: MixtralConfig, token_ids, kv_cache, full_block_ids,
    tail_block_ids, tail_len, start_pos, cos, sin
):
    """Continued prefill over a reused prefix for the MoE family: tail
    queries attend to the resident prefix KV plus themselves, MoE FFN on the
    tail activations only (same contract as
    llama_forward_prefill_with_prefix)."""
    positions = start_pos + jnp.arange(token_ids.shape[0], dtype=jnp.int32)

    def attend(q, k, v, k_layer, v_layer):
        k_prefix, v_prefix = gather_prefix_kv(k_layer, v_layer, full_block_ids)
        kv = write_prefill_kv(k_layer, v_layer, k, v, tail_block_ids, tail_len)
        out = prefill_attention_with_prefix(
            q, k, v, k_prefix, v_prefix, start_pos, tail_len
        )
        return out, kv

    return _prefill_trunk(
        params, cfg, token_ids, kv_cache, positions, cos, sin, attend, tail_len
    )


def mixtral_forward_decode(
    params, cfg: MixtralConfig, token_ids, kv_cache, block_tables, context_lens, slot_ids,
    cos, sin, *, attention: str = "jax",
):
    b = token_ids.shape[0]

    def paged_attn(q, k_layer, v_layer):
        if attention.startswith("pallas"):
            from dynamo_tpu.ops.pallas import paged_attention_decode

            return paged_attention_decode(
                q, k_layer, v_layer, block_tables, context_lens,
                interpret=attention == "pallas_interpret",
            )
        return paged_decode_attention(q, k_layer, v_layer, block_tables, context_lens)

    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(context_lens - 1, 0)

    def layer(x, layer_in):
        w, k_layer, v_layer = layer_in
        state = {}

        def attn(attn_in):
            q = mm(attn_in, w["wq"]).reshape(b, cfg.num_heads, cfg.head_dim)
            k = mm(attn_in, w["wk"]).reshape(b, cfg.num_kv_heads, cfg.head_dim)
            v = mm(attn_in, w["wv"]).reshape(b, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:  # Qwen3-MoE: per-head RMSNorm pre-rope
                q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q[:, None], positions[:, None], cos, sin)[:, 0]
            k = apply_rope(k[:, None], positions[:, None], cos, sin)[:, 0]
            state["kv"] = write_decode_kv(k_layer, v_layer, k, v, slot_ids)
            attn_out = paged_attn(q, state["kv"][0], state["kv"][1])
            return mm(attn_out.reshape(b, -1), w["wo"])

        x = _block(cfg, w, x, attn)
        return x, state["kv"]

    x, (new_k, new_v) = jax.lax.scan(layer, x, (params["layers"], kv_cache["k"], kv_cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = (
        x @ params["embed"].T.astype(x.dtype)
        if cfg.tie_word_embeddings
        else mm(x, params["lm_head"])
    )
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def mixtral_forward_unified(
    params,
    cfg: MixtralConfig,
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
    page_total,     # [T // tb_tokens] int32 live pages per token block
    sample_rows,    # [lanes] int32 flat index of span's LAST token
    cos,
    sin,
    *,
    attention: str = "jax",     # "jax" | "pallas" | "pallas_interpret"
    tb_tokens: int = 8,
):
    """Ragged unified-batch forward for the sparse-MoE family: the llama
    unified contract (mixed chunked-prefill spans + decode tokens, one
    launch, per-token absolute positions) with the dense MLP swapped for
    the top-k MoE FFN.  Expert routing is already per-token (ops/moe.py),
    so it composes with the ragged layout unchanged — each token routes on
    its own activations regardless of which lane owns it, and in the
    no-drop regime capacity_factor is sized for, per-token expert outputs
    are independent of batch composition (the split-vs-unified byte-parity
    contract).  Pad rows route too and are discarded at the sample gather."""
    t = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(token_pos, 0)

    def layer(x, layer_in):
        w, k_layer, v_layer = layer_in
        state = {}

        def attn(attn_in):
            q = mm(attn_in, w["wq"]).reshape(t, cfg.num_heads, cfg.head_dim)
            k = mm(attn_in, w["wk"]).reshape(t, cfg.num_kv_heads, cfg.head_dim)
            v = mm(attn_in, w["wv"]).reshape(t, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:  # Qwen3-MoE: per-head RMSNorm pre-rope
                q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
            # every token writes before anyone reads: span tokens see their
            # own in-window predecessors through the cache
            state["kv"] = write_decode_kv(k_layer, v_layer, k, v, token_slot)
            if attention.startswith("pallas"):
                from dynamo_tpu.ops.pallas import (
                    ragged_paged_attention as ragged_kernel,
                )

                attn_out = ragged_kernel(
                    q, state["kv"][0], state["kv"][1], token_lane, token_pos,
                    block_tables, span_lane, span_first, span_count,
                    page_total,
                    tb_tokens=tb_tokens,
                    interpret=attention == "pallas_interpret",
                )
            else:
                attn_out = ragged_paged_attention(
                    q, state["kv"][0], state["kv"][1], block_tables,
                    context_lens, token_lane, token_pos,
                )
            return mm(attn_out.reshape(t, -1), w["wo"])

        x = _block(cfg, w, x, attn)
        return x, state["kv"]

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], kv_cache["k"], kv_cache["v"])
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    rows = x[sample_rows]  # [lanes, h] — junk for hole lanes, caller-gated
    logits = (
        rows @ params["embed"].T.astype(rows.dtype)
        if cfg.tie_word_embeddings
        else mm(rows, params["lm_head"])
    )
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def mixtral_forward_decode_pp(
    params, cfg: MixtralConfig, token_ids, kv_cache, block_tables,
    context_lens, slot_ids, cos, sin, *, pp_mesh, microbatches: int | None = None,
):
    """Batched MoE decode with the layer stack pipelined over the ``pp``
    mesh axis (parallel/pipeline.py), composing with expert parallelism:
    the pp axis is manual inside the pipeline runner's partial-manual
    shard_map while the expert-stacked weights keep their ``P(..., "ep",
    ...)`` shardings — GSPMD inserts the expert all-to-alls INSIDE each
    stage exactly as it does for tp in the llama path
    (llama_forward_decode_pp).  BASELINE.json's Mixtral-on-v5p config
    implies this composition.

    MoE drop semantics vs the non-pp decode: routing runs per MICROBATCH,
    with capacity_factor scaled by the microbatch count so each expert's
    per-call capacity equals what full-batch routing would allocate.
    Tokens therefore only compete for slots within their own microbatch —
    outputs match the plain decode exactly whenever no drops occur (the
    served regime capacity_factor is sized for), and under extreme routing
    skew the pp path drops no earlier than full-batch routing would."""
    b = token_ids.shape[0]
    x = params["embed"][token_ids].astype(cfg.dtype)
    positions = jnp.maximum(context_lens - 1, 0)
    m_count = microbatches or pp_mesh.shape["pp"]

    def body(x_mb, aux_mb, w, layer_cache):
        k_layer, v_layer = layer_cache
        pos_mb, slots_mb, tables_mb, lens_mb = aux_mb
        bmb = x_mb.shape[0]
        state = {}

        def attn(attn_in):
            q = mm(attn_in, w["wq"]).reshape(bmb, cfg.num_heads, cfg.head_dim)
            k = mm(attn_in, w["wk"]).reshape(bmb, cfg.num_kv_heads, cfg.head_dim)
            v = mm(attn_in, w["wv"]).reshape(bmb, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:
                q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q[:, None], pos_mb[:, None], cos, sin)[:, 0]
            k = apply_rope(k[:, None], pos_mb[:, None], cos, sin)[:, 0]
            state["kv"] = write_decode_kv(k_layer, v_layer, k, v, slots_mb)
            attn_out = paged_decode_attention(
                q, state["kv"][0], state["kv"][1], tables_mb, lens_mb
            )
            return mm(attn_out.reshape(bmb, -1), w["wo"])

        x_mb = _block(cfg, w, x_mb, attn, capacity_scale=float(m_count))
        return x_mb, state["kv"]

    from dynamo_tpu.parallel.pipeline import pipeline_layer_stack

    x, (new_k, new_v) = pipeline_layer_stack(
        body, x, (positions, slot_ids, block_tables, context_lens),
        params["layers"], (kv_cache["k"], kv_cache["v"]), pp_mesh,
        microbatches=microbatches,
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = (
        x @ params["embed"].T.astype(x.dtype)
        if cfg.tie_word_embeddings
        else mm(x, params["lm_head"])
    )
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def mixtral_forward_verify(
    params, cfg: MixtralConfig, token_ids, kv_cache, block_tables,
    context_lens, slot_ids, cos, sin, *, attention: str = "jax",
):
    """Speculative-verification forward for the MoE family: the [b, w]
    window runs through the same attention scaffold as decode (multi-query
    paged window attention) and the MoE FFN sees the window's b*w tokens.
    Contract matches llama_forward_verify.

    Token order is POSITION-major (all lanes' position-0 tokens first):
    expert-capacity slots assign in dispatch order (ops/moe.py), so the
    always-emitted position-0 tokens never lose a slot to a later draft
    position.  MoE parity with plain decode is therefore near-exact but
    not guaranteed under extreme routing skew — capacity grows w-fold with
    the window, yet which tokens drop can differ from the non-speculative
    schedule (a capacity-dropping property, not an acceptance-logic one)."""
    b, w_len = token_ids.shape
    # [b, w] → position-major flat [w*b]
    x = params["embed"][token_ids.T.reshape(-1)].astype(cfg.dtype)
    positions = jnp.maximum(
        context_lens[:, None] - w_len + jnp.arange(w_len)[None, :], 0
    )  # [b, w]
    flat_slots = slot_ids.T.reshape(-1)

    def attend_pages(q, k_layer, v_layer):
        return window_attention(
            attention, q, k_layer, v_layer, block_tables, context_lens
        )

    def to_bw(t, *tail):
        return position_major_to_batch(t, w_len, b, *tail)

    def layer(x, layer_in):
        w, k_layer, v_layer = layer_in
        state = {}

        def attn(attn_in):
            q = to_bw(mm(attn_in, w["wq"]), cfg.num_heads, cfg.head_dim)
            k = to_bw(mm(attn_in, w["wk"]), cfg.num_kv_heads, cfg.head_dim)
            v = to_bw(mm(attn_in, w["wv"]), cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:
                q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
            state["kv"] = write_decode_kv(
                k_layer, v_layer,
                k.transpose(1, 0, 2, 3).reshape(w_len * b, cfg.num_kv_heads, cfg.head_dim),
                v.transpose(1, 0, 2, 3).reshape(w_len * b, cfg.num_kv_heads, cfg.head_dim),
                flat_slots,
            )
            attn_out = attend_pages(q, state["kv"][0], state["kv"][1])  # [b, w, H, D]
            flat = attn_out.transpose(1, 0, 2, 3).reshape(w_len * b, -1)
            return mm(flat, w["wo"])

        x = _block(cfg, w, x, attn)
        return x, state["kv"]

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], kv_cache["k"], kv_cache["v"])
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = (
        x @ params["embed"].T.astype(x.dtype)
        if cfg.tie_word_embeddings
        else mm(x, params["lm_head"])
    )
    logits = logits.reshape(w_len, b, -1).transpose(1, 0, 2)
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


# ------------------------------------------------------------------ weights


def load_hf_weights(cfg: MixtralConfig, model_dir) -> dict:
    """Load and stack HF Mixtral safetensors into the layer-stacked pytree
    (HF projections are [out, in]; ours [in, out] → transpose; experts stack
    on a leading E axis)."""
    import numpy as np

    from dynamo_tpu.models.hf_io import read_safetensors

    tensors = read_safetensors(model_dir)

    def get(name: str, transpose: bool = False):
        t = tensors[name]
        if transpose:
            t = t.T
        return np.asarray(t)

    names = (
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
        "w_router", "w_gate", "w_up", "w_down",
    ) + (("q_norm", "k_norm") if cfg.qk_norm else ())
    layers: dict[str, list] = {k: [] for k in names}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        layers["attn_norm"].append(get(f"{p}.input_layernorm.weight"))
        layers["wq"].append(get(f"{p}.self_attn.q_proj.weight", True))
        layers["wk"].append(get(f"{p}.self_attn.k_proj.weight", True))
        layers["wv"].append(get(f"{p}.self_attn.v_proj.weight", True))
        layers["wo"].append(get(f"{p}.self_attn.o_proj.weight", True))
        layers["mlp_norm"].append(get(f"{p}.post_attention_layernorm.weight"))
        if cfg.qk_norm:
            layers["q_norm"].append(get(f"{p}.self_attn.q_norm.weight"))
            layers["k_norm"].append(get(f"{p}.self_attn.k_norm.weight"))
        if f"{p}.block_sparse_moe.gate.weight" in tensors:
            # Mixtral naming: w1=gate, w3=up, w2=down
            moe_p, hf_names = f"{p}.block_sparse_moe", ("w1", "w3", "w2")
        else:
            # Qwen3-MoE naming: mlp.experts.{e}.gate/up/down_proj
            moe_p, hf_names = f"{p}.mlp", ("gate_proj", "up_proj", "down_proj")
        layers["w_router"].append(get(f"{moe_p}.gate.weight", True))
        for ours, theirs in zip(("w_gate", "w_up", "w_down"), hf_names):
            layers[ours].append(np.stack([
                get(f"{moe_p}.experts.{e}.{theirs}.weight", True)
                for e in range(cfg.num_experts)
            ]))

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), cfg.dtype),
        "final_norm": jnp.asarray(get("model.norm.weight"), cfg.dtype),
        "layers": {
            k: jnp.asarray(np.stack(v), cfg.dtype) for k, v in layers.items()
        },
    }
    if not cfg.tie_word_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = jnp.asarray(get("lm_head.weight", True), cfg.dtype)
    return params
