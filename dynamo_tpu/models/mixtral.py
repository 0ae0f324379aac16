"""Mixtral-class sparse-MoE model (Mixtral 8x7B geometry and kin).

The llama family's block and step programs (models/llama.py: ``_block``, the
``llama_forward_*``) with ONE thing of its own: the FFN.  ``MixtralConfig``
is a ``LlamaConfig`` whose ``ffn`` is a top-k-of-E MoE (dynamo_tpu/ops/moe.py)
over expert-stacked weights; this file holds that config, its parameters
and loader, and the one forward whose token order the experts dictate
(``mixtral_forward_verify``).  Expert parallelism is sharding annotation
only: expert-stacked weights carry ``P(None, "ep", ...)`` and GSPMD emits
the dispatch/combine all-to-alls over ICI.

(The reference serves wide-EP MoE through SGLang+DeepEP —
examples/sglang/README.md:105; here the MoE engine is native.)
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import (
    LayerKind,
    LayerRun,
    LlamaConfig,
    _block,
    _embed,
    _logits,
    _scan_layers,
    layer_bank,
)
from dynamo_tpu.ops.attention import (
    position_major_to_batch,
    window_attention,
    write_decode_kv,
)
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2
    # expert FFN width; 0 = same as intermediate_size (Mixtral proper).
    # Qwen3-MoE configs carry a distinct moe_intermediate_size.
    moe_intermediate_size: int = 0
    # renormalize top-k router weights (Mixtral yes; some Qwen3-MoE
    # variants disable it)
    norm_topk_prob: bool = True

    # grouped product of the expert layer (ops/moe.py ``grouped_matmul``):
    # "auto" = the Pallas kernel on a TPU; the engine sets "xla" under a mesh
    grouped_matmul: str = "auto"

    # the expert layer skips rows that are no token (llama._valid_rows)
    ffn_wants_valid_rows = True

    @property
    def expert_intermediate_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    def layer_runs(self) -> tuple[LayerRun, ...]:
        """One run of alike layers, through the loop that hands a layer's
        weights as a view of the stack (``llama._scan_layer_runs``): the
        grouped product then reads a layer's expert banks where they lie
        (sliced out for the kernel they would be copied every step)."""
        kind = LayerKind(self.sliding_window, True, "kv", "layers")
        return (LayerRun(kind, 0, self.num_layers, 0),)

    def ffn(self, w: dict, x: jnp.ndarray, valid=None) -> jnp.ndarray:
        """The family's FFN for the shared block: route each token to its
        top-k experts; every assignment is computed (ops/moe.py), rows that
        are not ``valid`` tokens are not."""
        return moe_ffn(
            x, w["w_router"],
            *(layer_bank(w, name) for name in ("w_gate", "w_up", "w_down")),
            top_k=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob,
            valid=valid, impl=self.grouped_matmul,
        )

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
            num_experts=4, experts_per_token=2,
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
            # HF's use_sliding_window/max_window_layers semantics; the shared
            # block honours the window in every forward
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


def mixtral_forward_verify(
    params, cfg: MixtralConfig, token_ids, kv_cache, block_tables,
    context_lens, slot_ids, cos, sin, *, attention: str = "jax",
):
    """Speculative-verification forward, the family's one forward of its own
    (every other step program is the llama forward of that name, with
    ``MixtralConfig.ffn`` in its block: models/registry.py).  Contract
    matches llama_forward_verify; what differs is the ORDER of the window's
    b*w tokens.

    Token order is POSITION-major (all lanes' position-0 tokens first), as
    it was when the expert layer gave out capacity slots in dispatch order;
    the expert layer drops nothing now, so the order no longer decides any
    token's result."""
    b, w_len = token_ids.shape
    # [b, w] → position-major flat [w*b]
    x = _embed(params, cfg, token_ids.T.reshape(-1))
    positions = jnp.maximum(
        context_lens[:, None] - w_len + jnp.arange(w_len)[None, :], 0
    )  # [b, w]
    flat_slots = slot_ids.T.reshape(-1)

    def to_bw(t):  # [w*b, heads, d] → [b, w, heads, d]
        return position_major_to_batch(t, w_len, b, *t.shape[1:])

    def to_flat(t):  # and back
        return t.transpose(1, 0, 2, 3).reshape(w_len * b, *t.shape[2:])

    def attend(q, k, v, k_pages, v_pages, at):
        # rotate and attend as [batch, window]; K and V go to their slots in
        # the order they came in
        q = apply_rope(to_bw(q), positions, cos, sin)
        k = apply_rope(to_bw(k), positions, cos, sin)
        with jax.named_scope("kv_write"):
            pages = write_decode_kv(
                k_pages, v_pages, to_flat(k), v, at.slots(flat_slots)
            )
        with jax.named_scope("attn"):
            attn = window_attention(
                attention, q, *pages, at.blocks(block_tables), context_lens,
                sliding_window=cfg.sliding_window,
            )
        return to_flat(attn), *pages

    layer = partial(_block, cfg, attend)
    x, kv_cache = _scan_layers(layer, x, params["layers"], kv_cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _logits(params, cfg, x).reshape(w_len, b, -1).transpose(1, 0, 2)
    return logits.astype(jnp.float32), kv_cache


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
