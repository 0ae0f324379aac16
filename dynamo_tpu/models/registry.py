"""Model family registry.

Binds a ``model_type`` (HF config.json naming) to the functional pieces the
engine needs: config parsing, param init, sharding specs, prefill/decode
forwards.  Families registered here are served by the same engine,
scheduler, router and disagg machinery.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable


@dataclass(frozen=True)
class ModelFamily:
    name: str
    config_from_hf: Callable[[Any], Any]
    init_params: Callable
    param_specs: Callable
    forward_prefill: Callable
    forward_decode: Callable
    # cache geometry hooks; None = the llama-family GQA paged cache
    # (MLA families override: cache stores compressed latents)
    init_kv_cache: Callable | None = None
    kv_cache_specs: Callable | None = None
    make_rope_tables: Callable | None = None
    # continued prefill over a resident prefix (prefix-cache reuse, chunked
    # prefill); None = the engine disables prefix caching for this family
    forward_prefill_with_prefix: Callable | None = None
    # prefill from precomputed input embeddings (multimodal: vision patches
    # spliced before text); None = no multimodal support for this family
    forward_prefill_embeds: Callable | None = None
    # token-embedding lookup hook: (params, cfg, token_ids) -> [n, hidden].
    # None = raw table lookup.  Families with input-embedding quirks
    # (gemma's sqrt(hidden) scale) set this so generic engine code — the
    # multimodal prefill splices text embeddings itself — stays family-
    # agnostic instead of copying the quirk inline.
    embed: Callable | None = None
    # forward_prefill accepts sp_mesh= (ring-attention sequence parallelism)
    supports_sp: bool = False
    # forward_prefill_with_prefix accepts sp_mesh (ring attention over the
    # tail + merged resident prefix) — what lets prefix caching and
    # chunked prefill compose with a sequence-parallel mesh
    prefix_prefill_accepts_sp: bool = False
    # pipelined decode over the pp mesh axis (parallel/pipeline.py)
    forward_decode_pp: Callable | None = None
    # HF safetensors loader: (cfg, model_dir) -> params pytree
    load_weights: Callable | None = None
    # forward_decode accepts tp_mesh= (shard_map'd pallas attention)
    decode_accepts_tp_mesh: bool = False
    # multi-position verification forward (speculative decoding); None =
    # the engine rejects speculative config for this family
    forward_verify: Callable | None = None
    # ragged unified-batch forward (one launch mixing chunked-prefill spans
    # and decode tokens, ops/pallas/ragged_attention.py); None = the engine
    # keeps the split prefill/decode step for this family
    forward_unified: Callable | None = None
    # ``forward_unified`` attends the keys of the window's own rows itself
    # (from its activations) and walks only the pages RESIDENT before the
    # window: the engine then hands ``pack_spans`` each row's last resident
    # position of its lane in place of the row's own
    # (ops/pallas/mla_attention.py ``last_resident_pos``)
    unified_attends_window: bool = False
    # param-tree leaf names eligible for weight-only int8 (ops/quant.py);
    # empty = the family's forwards don't route matmuls through quant.mm
    quant_leaves: tuple[str, ...] = ()
    # a family with window layers keeps their keys and values in a second
    # pool whose blocks are released behind the window: (cfg, lanes,
    # max_len, block_size) -> blocks of that pool (None = one pool; the
    # engine then hands ``init_kv_cache`` a ``window_blocks`` keyword,
    # serves no prefix cache and no KV transfer for the family)
    window_pool_blocks: Callable | None = None
    # the cache holds leaves a LANE beside its pages (a recurrent state):
    # the engine hands ``init_kv_cache`` a ``lanes`` keyword and
    # ``forward_prefill`` the prompt's ``lane``
    lane_state: bool = False

    def cache_init(self, cfg, num_blocks: int, block_size: int, dtype=None, **pools):
        if self.init_kv_cache is not None:
            return self.init_kv_cache(cfg, num_blocks, block_size, dtype, **pools)
        from dynamo_tpu.models import llama

        return llama.init_kv_cache(cfg, num_blocks, block_size, dtype)

    def cache_specs(self, cfg):
        """Pytree of PartitionSpecs matching the cache pytree."""
        if self.kv_cache_specs is not None:
            return self.kv_cache_specs(cfg)
        from dynamo_tpu.models import llama

        spec = llama.kv_cache_spec()
        return {"k": spec, "v": spec}

    def rope_tables(self, cfg):
        if self.make_rope_tables is not None:
            return self.make_rope_tables(cfg)
        from dynamo_tpu.models import llama

        return llama.make_rope_tables(cfg)


# attention projections + FFN/expert banks shared by the llama-like and
# MoE families ([L, E, in, out] expert banks quantize per (layer, expert,
# out-channel) — the scale rule is axis-position based, not rank based);
# small routers and norms stay full-precision
_PROJ_QUANT_LEAVES = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
)


def _llama_like_family(
    name: str, config_tweak=None, *, config_from_hf=None, load_weights=None,
) -> ModelFamily:
    """One ModelFamily construction for every llama-geometry variant.

    ``config_tweak(dict)`` mutates the HF config before parsing (biases,
    qk-norm flags); ``config_from_hf``/``load_weights`` replace the whole
    parse/load step for families with checkpoint quirks (gemma's baked
    (1+w) norms, phi3's fused tensors) so each stays a one-line
    declaration."""
    from dynamo_tpu.models import llama

    def default_config_from_hf(config):
        import json

        if not isinstance(config, dict):
            config = json.loads(Path(config).read_text())
        config = dict(config)
        if config_tweak is not None:
            config_tweak(config)
        return llama.LlamaConfig.from_hf_config(config)

    return ModelFamily(
        name=name,
        config_from_hf=config_from_hf or default_config_from_hf,
        init_params=llama.init_params,
        param_specs=llama.param_specs,
        forward_prefill=llama.llama_forward_prefill,
        forward_decode=llama.llama_forward_decode,
        forward_prefill_with_prefix=llama.llama_forward_prefill_with_prefix,
        forward_prefill_embeds=llama.llama_forward_prefill_embeds,
        embed=llama._embed,
        supports_sp=True,
        prefix_prefill_accepts_sp=True,
        forward_decode_pp=llama.llama_forward_decode_pp,
        load_weights=load_weights or llama.load_hf_weights,
        decode_accepts_tp_mesh=True,
        quant_leaves=_PROJ_QUANT_LEAVES,
        forward_verify=llama.llama_forward_verify,
        forward_unified=llama.llama_forward_unified,
    )


def _llama_family() -> ModelFamily:
    return _llama_like_family("llama")


def _qwen2_family() -> ModelFamily:
    # Qwen2/2.5 = llama geometry + attention qkv biases
    return _llama_like_family(
        "qwen2", lambda c: c.setdefault("attention_bias", True)
    )


def _qwen3_family() -> ModelFamily:
    # Qwen3 = llama geometry + per-head q/k RMSNorm before rope, no biases
    return _llama_like_family("qwen3", lambda c: c.update(qk_norm=True))


def _phi3_family() -> ModelFamily:
    # Phi-3 = llama math with fused checkpoint tensors (split at load) and
    # an always-on sliding window; longrope variants refused at config
    # parse (models/llama.py phi3_* helpers)
    from dynamo_tpu.models import llama

    return _llama_like_family(
        "phi3",
        config_from_hf=llama.phi3_config_from_hf,
        load_weights=llama.phi3_load_hf_weights,
    )


def _gemma_family() -> ModelFamily:
    # Gemma-1 = llama skeleton + GeGLU, sqrt(hidden) embedding scale, and
    # (1+w) RMSNorm baked at load (models/llama.py gemma_* helpers).
    from dynamo_tpu.models import llama

    return _llama_like_family(
        "gemma",
        config_from_hf=llama.gemma_config_from_hf,
        load_weights=llama.gemma_load_hf_weights,
    )


def _gemma2_family() -> ModelFamily:
    # Gemma-2 = alternating local/global attention (per-layer window array
    # through one lax.scan), attn + final logit soft-capping, sandwich
    # norms, query_pre_attn_scalar (models/gemma2.py)
    from dynamo_tpu.models import gemma2

    return ModelFamily(
        name="gemma2",
        config_from_hf=gemma2.Gemma2Config.from_hf_config,
        init_params=gemma2.init_params,
        param_specs=gemma2.param_specs,
        forward_prefill=gemma2.gemma2_forward_prefill,
        forward_decode=gemma2.gemma2_forward_decode,
        forward_prefill_with_prefix=gemma2.gemma2_forward_prefill_with_prefix,
        make_rope_tables=gemma2.make_rope_tables,
        embed=gemma2._embed,
        load_weights=gemma2.load_hf_weights,
        quant_leaves=_PROJ_QUANT_LEAVES,
        forward_verify=gemma2.gemma2_forward_verify,
    )


def _gemma3_family() -> ModelFamily:
    # Gemma-3 text = Gemma-2 machinery + 5:1 local/global pattern, dual
    # rope bases packed along the feature axis, per-head q/k (1+w) norms,
    # no soft-capping (models/gemma3.py).  Multimodal checkpoints parse
    # their text_config; image inputs are rejected (no embeds prefill).
    from dynamo_tpu.models import gemma3

    return ModelFamily(
        name="gemma3",
        config_from_hf=gemma3.Gemma3Config.from_hf_config,
        init_params=gemma3.init_params,
        param_specs=gemma3.param_specs,
        forward_prefill=gemma3.gemma3_forward_prefill,
        forward_decode=gemma3.gemma3_forward_decode,
        forward_prefill_with_prefix=gemma3.gemma3_forward_prefill_with_prefix,
        forward_prefill_embeds=gemma3.gemma3_forward_prefill_embeds,
        make_rope_tables=gemma3.make_rope_tables,
        embed=gemma3._embed,
        load_weights=gemma3.load_hf_weights,
        quant_leaves=_PROJ_QUANT_LEAVES,
        forward_verify=gemma3.gemma3_forward_verify,
    )


def _sparse_expert_family(name: str) -> ModelFamily:
    """Mixtral-style routed experts on the llama geometry: the llama-like
    families' forwards as they are (``MixtralConfig`` is a ``LlamaConfig``
    that supplies its own FFN to their block), with the family's own
    parameters, loader and verify order.  What the shared forwards could do
    for it but nothing has yet shown they do stays unset: sequence-parallel
    prefill, a tp-sharded decode kernel, prefill from embeddings."""
    from dynamo_tpu.models import llama, mixtral

    return ModelFamily(
        name=name,
        config_from_hf=mixtral.MixtralConfig.from_hf_config,
        init_params=mixtral.init_params,
        param_specs=mixtral.param_specs,
        forward_prefill=llama.llama_forward_prefill,
        forward_decode=llama.llama_forward_decode,
        forward_prefill_with_prefix=llama.llama_forward_prefill_with_prefix,
        forward_decode_pp=llama.llama_forward_decode_pp,
        load_weights=mixtral.load_hf_weights,
        quant_leaves=_PROJ_QUANT_LEAVES,
        forward_verify=mixtral.mixtral_forward_verify,
        forward_unified=llama.llama_forward_unified,
    )


def _mixtral_family() -> ModelFamily:
    return _sparse_expert_family("mixtral")


def _qwen3_moe_family() -> ModelFamily:
    # Qwen3-MoE = Mixtral-style routed experts + per-head q/k RMSNorm
    # (from_hf_config infers qk_norm from model_type, which the registry
    # key guarantees is present on any config routed here)
    return _sparse_expert_family("qwen3_moe")


def _exaone_moe_family() -> ModelFamily:
    # EXAONE-MoE = the llama block with a KIND per layer (window or full
    # attention, rotated or not, dense MLP or routed experts beside a shared
    # one) and a second cache pool for the window layers
    # (models/exaone_moe.py).  Its window layers' prefix is gone once a
    # sequence has passed it, so: no continued prefill (prefix cache,
    # chunked prefill), no verify, no pipelined decode.
    from dynamo_tpu.models import exaone_moe, llama

    return ModelFamily(
        name="exaone_moe",
        config_from_hf=exaone_moe.ExaoneMoeConfig.from_hf_config,
        init_params=exaone_moe.init_params,
        param_specs=exaone_moe.param_specs,
        forward_prefill=llama.llama_forward_prefill,
        forward_decode=llama.llama_forward_decode,
        init_kv_cache=exaone_moe.init_kv_cache,
        kv_cache_specs=exaone_moe.kv_cache_specs,
        quant_leaves=_PROJ_QUANT_LEAVES + ("ws_gate", "ws_up", "ws_down"),
        forward_unified=llama.llama_forward_unified,
        window_pool_blocks=exaone_moe.window_pool_blocks,
    )


def _phi4flash_family() -> ModelFamily:
    # Phi-4-flash = state-space layers (a recurrent state a lane), window and
    # full differential attention, and a cross-decoder of gated memory units
    # and layers that read ONE layer's pages (models/phi4flash.py).  The
    # state at a block boundary is not kept, so: no continued prefill (prefix
    # cache, chunked prefill), no verify, no pipelined decode, no loader.
    from dynamo_tpu.models import phi4flash

    return ModelFamily(
        name="phi4flash",
        config_from_hf=phi4flash.Phi4FlashConfig.from_hf_config,
        init_params=phi4flash.init_params,
        param_specs=phi4flash.param_specs,
        forward_prefill=phi4flash.phi4flash_forward_prefill,
        forward_decode=phi4flash.phi4flash_forward_decode,
        init_kv_cache=phi4flash.init_kv_cache,
        kv_cache_specs=phi4flash.kv_cache_specs,
        make_rope_tables=phi4flash.make_rope_tables,
        forward_unified=phi4flash.phi4flash_forward_unified,
        window_pool_blocks=phi4flash.window_pool_blocks,
        lane_state=True,
    )


def _deepseek_family() -> ModelFamily:
    from dynamo_tpu.models import deepseek

    return ModelFamily(
        name="deepseek",
        config_from_hf=deepseek.DeepseekConfig.from_hf_config,
        init_params=deepseek.init_params,
        param_specs=deepseek.param_specs,
        forward_prefill=deepseek.deepseek_forward_prefill,
        forward_decode=deepseek.deepseek_forward_decode,
        forward_prefill_with_prefix=deepseek.deepseek_forward_prefill_with_prefix,
        load_weights=deepseek.load_hf_weights,
        init_kv_cache=deepseek.init_kv_cache,
        kv_cache_specs=deepseek.kv_cache_specs,
        make_rope_tables=deepseek.make_rope_tables,
        # absorbed-form up-projections (w_uk/w_uv) stay full precision:
        # they are reshaped + consumed inside fp32 einsums
        quant_leaves=(
            "w_dq", "w_uq", "wq", "w_dkv", "wo", "w_gate", "w_up", "w_down",
            "ws_gate", "ws_up", "ws_down", "lm_head",
        ),
        forward_verify=deepseek.deepseek_forward_verify,
        forward_unified=deepseek.deepseek_forward_unified,
        unified_attends_window=True,
    )


_FAMILIES: dict[str, Callable[[], ModelFamily]] = {
    "llama": _llama_family,
    # Mistral = llama geometry + sliding-window attention; the window comes
    # from config.json's sliding_window and threads through the llama
    # forwards (models/llama.py)
    "mistral": _llama_family,
    "qwen2": _qwen2_family,
    "qwen3": _qwen3_family,
    "gemma": _gemma_family,
    "gemma2": _gemma2_family,
    "gemma3": _gemma3_family,
    "gemma3_text": _gemma3_family,
    "phi3": _phi3_family,
    "mixtral": _mixtral_family,
    "qwen3_moe": _qwen3_moe_family,
    # HF model_type keys for the MLA architectures only — classic
    # DeepSeek-MoE ("deepseek") uses conventional attention and would need
    # its own family
    "exaone_moe": _exaone_moe_family,
    "phi4flash": _phi4flash_family,
    "deepseek_v2": _deepseek_family,
    "deepseek_v3": _deepseek_family,
    # the same latent attention and expert layers inside hc_mult residual
    # streams a token (config keys hc_* / mhc_*; ops/hyper_connections.py)
    "xing4_0": _deepseek_family,
}


def known_families() -> list[str]:
    return sorted(_FAMILIES)


def get_family(model_type: str) -> ModelFamily:
    factory = _FAMILIES.get(model_type)
    if factory is None:
        raise ValueError(
            f"unknown model family {model_type!r}; known: {sorted(_FAMILIES)}"
        )
    return factory()


def register_family(name: str, factory: Callable[[], ModelFamily]) -> None:
    _FAMILIES[name] = factory
