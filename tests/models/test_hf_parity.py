"""Numerical parity vs HuggingFace transformers — the external oracle.

The other model tests compare against same-repo dense references, which
share this repo's op implementations: a systematic convention error (rope
rotate-half layout, norm placement, qkv bias handling, MoE router
normalization) would pass them all.  These tests round-trip REAL HF
models: build a tiny HF model (random weights), ``save_pretrained`` →
load through OUR ``from_hf_config`` + ``load_hf_weights`` → compare
last-token logits for several prompts.  That validates the full
checkpoint-ingestion chain, exactly what serving a real checkpoint runs.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402


def _hf_logits(model, token_ids: list[int]) -> np.ndarray:
    with torch.no_grad():
        out = model(torch.tensor([token_ids], dtype=torch.long))
    return out.logits[0, -1].float().numpy()


def _our_llama_logits(model_dir, token_ids: list[int]) -> np.ndarray:
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        llama_forward_prefill,
        load_hf_weights,
        make_rope_tables,
    )

    cfg = LlamaConfig.from_hf_config(f"{model_dir}/config.json")
    cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = load_hf_weights(cfg, model_dir)
    cos, sin = make_rope_tables(cfg)
    cache = init_kv_cache(cfg, 16, 4)
    blocks = jnp.arange(8, dtype=jnp.int32)
    logits, _ = llama_forward_prefill(
        params, cfg, jnp.asarray(token_ids, jnp.int32), cache, blocks,
        jnp.int32(len(token_ids)), jnp.int32(0), cos, sin,
    )
    return np.asarray(logits)


def _our_mixtral_logits(model_dir, token_ids: list[int]) -> np.ndarray:
    from dynamo_tpu.models import mixtral as mx
    from dynamo_tpu.models.llama import init_kv_cache, make_rope_tables
    from dynamo_tpu.models.registry import get_family

    cfg = mx.MixtralConfig.from_hf_config(f"{model_dir}/config.json")
    cfg = mx.MixtralConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = mx.load_hf_weights(cfg, model_dir)
    cos, sin = make_rope_tables(cfg)
    cache = init_kv_cache(cfg, 16, 4)
    blocks = jnp.arange(8, dtype=jnp.int32)
    logits, _ = get_family("mixtral").forward_prefill(
        params, cfg, jnp.asarray(token_ids, jnp.int32), cache, blocks,
        jnp.int32(len(token_ids)), jnp.int32(0), cos, sin,
    )
    return np.asarray(logits)


PROMPTS = [
    [3, 17, 99, 250, 7, 42],
    [5, 5, 5, 200, 201, 202, 203, 204],
    list(range(10, 30)),
]


def _check(ours_fn, model, model_dir, atol=2e-4, rtol=2e-4):
    for prompt in PROMPTS:
        ours = ours_fn(str(model_dir), prompt)
        theirs = _hf_logits(model, prompt)
        np.testing.assert_allclose(ours, theirs, atol=atol, rtol=rtol)


@pytest.mark.slow
def test_llama_matches_hf(tmp_path):
    config = transformers.LlamaConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False, torch_dtype="float32",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _check(_our_llama_logits, model, tmp_path)


@pytest.mark.slow
def test_llama_rope_scaling_llama3_matches_hf(tmp_path):
    """The llama3 rope-scaling schedule (low/high-freq factor ramp) against
    HF's implementation of the same config."""
    config = transformers.LlamaConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=True, torch_dtype="float32",
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _check(_our_llama_logits, model, tmp_path)


@pytest.mark.slow
def test_qwen2_matches_hf(tmp_path):
    """Qwen2 = llama geometry + qkv biases; HF ties use_sliding_window
    default false so full attention."""
    config = transformers.Qwen2Config(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False, torch_dtype="float32",
    )
    torch.manual_seed(2)
    model = transformers.Qwen2ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    def ours(model_dir, prompt):
        from dynamo_tpu.models.registry import get_family

        fam = get_family("qwen2")
        cfg = fam.config_from_hf(f"{model_dir}/config.json")
        cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
        params = fam.load_weights(cfg, model_dir)
        from dynamo_tpu.models.llama import (
            init_kv_cache,
            llama_forward_prefill,
            make_rope_tables,
        )

        cos, sin = make_rope_tables(cfg)
        cache = init_kv_cache(cfg, 16, 4)
        blocks = jnp.arange(8, dtype=jnp.int32)
        logits, _ = llama_forward_prefill(
            params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
            jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
        )
        return np.asarray(logits)

    _check(ours, model, tmp_path)


@pytest.mark.slow
def test_qwen3_matches_hf(tmp_path):
    """Qwen3 adds per-head q/k RMSNorm before rope."""
    if not hasattr(transformers, "Qwen3ForCausalLM"):
        pytest.skip("transformers too old for Qwen3")
    config = transformers.Qwen3Config(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=True, torch_dtype="float32",
    )
    torch.manual_seed(3)
    model = transformers.Qwen3ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    def ours(model_dir, prompt):
        from dynamo_tpu.models.registry import get_family

        fam = get_family("qwen3")
        cfg = fam.config_from_hf(f"{model_dir}/config.json")
        cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
        params = fam.load_weights(cfg, model_dir)
        from dynamo_tpu.models.llama import (
            init_kv_cache,
            llama_forward_prefill,
            make_rope_tables,
        )

        cos, sin = make_rope_tables(cfg)
        cache = init_kv_cache(cfg, 16, 4)
        blocks = jnp.arange(8, dtype=jnp.int32)
        logits, _ = llama_forward_prefill(
            params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
            jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
        )
        return np.asarray(logits)

    _check(ours, model, tmp_path)


@pytest.mark.slow
def test_mixtral_matches_hf(tmp_path):
    """MoE family vs HF Mixtral: exact top-k, every assignment computed, on
    both sides."""
    config = transformers.MixtralConfig(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False, torch_dtype="float32",
        num_local_experts=4, num_experts_per_tok=2,
    )
    torch.manual_seed(4)
    model = transformers.MixtralForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _check(_our_mixtral_logits, model, tmp_path, atol=5e-4, rtol=5e-4)


@pytest.mark.slow
def test_gemma_matches_hf(tmp_path):
    """Gemma-1: GeGLU MLP, sqrt(hidden) input-embedding scale, (1+w)
    RMSNorm (baked at load), tied unembedding, head_dim != hidden/heads."""
    config = transformers.GemmaConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=256, rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh", torch_dtype="float32",
    )
    torch.manual_seed(5)
    model = transformers.GemmaForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    def ours(model_dir, prompt):
        from dynamo_tpu.models.llama import (
            init_kv_cache,
            llama_forward_prefill,
            make_rope_tables,
        )
        from dynamo_tpu.models.registry import get_family

        fam = get_family("gemma")
        cfg = fam.config_from_hf(f"{model_dir}/config.json")
        cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
        assert cfg.mlp_activation == "gelu_tanh"
        assert cfg.embed_scale == pytest.approx(8.0)  # sqrt(64)
        params = fam.load_weights(cfg, model_dir)
        cos, sin = make_rope_tables(cfg)
        cache = init_kv_cache(cfg, 16, 4)
        blocks = jnp.arange(8, dtype=jnp.int32)
        logits, _ = llama_forward_prefill(
            params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
            jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
        )
        return np.asarray(logits)

    _check(ours, model, tmp_path)


@pytest.mark.slow
def test_phi3_matches_hf(tmp_path):
    """Phi-3: fused qkv_proj/gate_up_proj split at load, and the always-on
    sliding window — the SMALL window here makes HF's window mask part of
    the oracle, so an off-by-one in our window convention fails loudly."""
    config = transformers.Phi3Config(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rope_theta=10000.0,
        sliding_window=8, tie_word_embeddings=False, torch_dtype="float32",
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
        attn_implementation="eager",
    )
    torch.manual_seed(6)
    model = transformers.Phi3ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    def ours(model_dir, prompt):
        from dynamo_tpu.models.llama import (
            init_kv_cache,
            llama_forward_prefill,
            make_rope_tables,
        )
        from dynamo_tpu.models.registry import get_family

        fam = get_family("phi3")
        cfg = fam.config_from_hf(f"{model_dir}/config.json")
        cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
        assert cfg.sliding_window == 8
        params = fam.load_weights(cfg, model_dir)
        cos, sin = make_rope_tables(cfg)
        cache = init_kv_cache(cfg, 16, 4)
        blocks = jnp.arange(8, dtype=jnp.int32)
        logits, _ = llama_forward_prefill(
            params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
            jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
        )
        return np.asarray(logits)

    _check(ours, model, tmp_path)


def test_phi3_longrope_refused():
    from dynamo_tpu.models.registry import get_family

    with pytest.raises(NotImplementedError, match="longrope"):
        get_family("phi3").config_from_hf({
            "model_type": "phi3", "vocab_size": 128, "hidden_size": 32,
            "intermediate_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4,
            "rope_scaling": {"rope_type": "longrope", "short_factor": [1.0],
                             "long_factor": [1.0]},
        })


@pytest.mark.slow
def test_llama_decode_path_matches_hf_at_every_position(tmp_path):
    """The serving hot path against the oracle: prefill a short prompt,
    then DECODE token by token (write_decode_kv + paged_decode_attention),
    comparing logits with HF's full-context logits at every position.
    Pins the paged cache writes, slot arithmetic, and decode attention —
    none of which the last-token prefill checks exercise."""
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        llama_forward_decode,
        llama_forward_prefill,
        load_hf_weights,
        make_rope_tables,
    )

    config = transformers.LlamaConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=True, torch_dtype="float32",
    )
    torch.manual_seed(7)
    model = transformers.LlamaForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11, 85, 301, 12, 13]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()  # [len, vocab]

    cfg = LlamaConfig.from_hf_config(f"{tmp_path}/config.json")
    cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = load_hf_weights(cfg, tmp_path)
    cos, sin = make_rope_tables(cfg)
    block_size = 4
    cache = init_kv_cache(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prefill_len = 4
    logits, cache = llama_forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=2e-4, rtol=2e-4
    )

    # decode the rest one token at a time; position p's logits must match
    # HF's logits at p (the slot arithmetic crosses block boundaries here)
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = llama_forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=3e-4, rtol=3e-4,
            err_msg=f"decode position {p}",
        )


@pytest.mark.slow
def test_mixtral_decode_path_matches_hf(tmp_path):
    """MoE decode against the oracle: per-token expert routing in the
    decode path (the family's forward_decode) vs HF's full-context forward."""
    from dynamo_tpu.models import mixtral as mx
    from dynamo_tpu.models.llama import init_kv_cache, make_rope_tables
    from dynamo_tpu.models.registry import get_family

    config = transformers.MixtralConfig(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False, torch_dtype="float32",
        num_local_experts=4, num_experts_per_tok=2,
    )
    torch.manual_seed(8)
    model = transformers.MixtralForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11, 85, 301]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()

    cfg = mx.MixtralConfig.from_hf_config(f"{tmp_path}/config.json")
    cfg = mx.MixtralConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = mx.load_hf_weights(cfg, tmp_path)
    cos, sin = make_rope_tables(cfg)
    block_size = 4
    cache = init_kv_cache(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    fam = get_family("mixtral")
    prefill_len = 4
    logits, cache = fam.forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=5e-4, rtol=5e-4
    )
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = fam.forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=5e-4, rtol=5e-4,
            err_msg=f"moe decode position {p}",
        )


@pytest.mark.slow
def test_phi3_windowed_decode_matches_hf(tmp_path):
    """Sliding-window DECODE against the oracle: positions past the window
    must drop old context exactly as HF's eager window mask does (the
    prefill parity test covers the window only within one forward)."""
    from dynamo_tpu.models.llama import (
        init_kv_cache,
        llama_forward_decode,
        llama_forward_prefill,
        make_rope_tables,
    )
    from dynamo_tpu.models.registry import get_family

    config = transformers.Phi3Config(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rope_theta=10000.0,
        sliding_window=6, tie_word_embeddings=False, torch_dtype="float32",
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
        attn_implementation="eager",
    )
    torch.manual_seed(9)
    model = transformers.Phi3ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11, 85, 301, 12, 13, 44, 45]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()

    fam = get_family("phi3")
    cfg = fam.config_from_hf(f"{tmp_path}/config.json")
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    assert cfg.sliding_window == 6
    params = fam.load_weights(cfg, tmp_path)
    cos, sin = make_rope_tables(cfg)
    block_size = 4
    cache = init_kv_cache(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prefill_len = 4
    logits, cache = llama_forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=3e-4, rtol=3e-4
    )
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):  # crosses the window at p>=6
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = llama_forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=3e-4, rtol=3e-4,
            err_msg=f"windowed decode position {p}",
        )


@pytest.mark.slow
def test_deepseek_v2_mla_matches_hf(tmp_path):
    """MLA against the oracle — the most intricate model code in the repo
    (compressed-latent KV cache, q/kv low-rank projections, decoupled rope,
    absorbed-form decode, dense+MoE layer mix with shared experts) vs HF
    DeepseekV2, both prefill and the per-position decode path."""
    if not hasattr(transformers, "DeepseekV2ForCausalLM"):
        pytest.skip("transformers too old for DeepseekV2")
    from dynamo_tpu.models import deepseek as ds

    config = transformers.DeepseekV2Config(
        vocab_size=320, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=48,
        n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
        first_k_dense_replace=1, moe_layer_freq=1,
        # norm_topk_prob FALSE, faithful to real V2 checkpoints: the HF V2
        # port never applies the normalization (its greedy branch goes
        # straight to routed_scaling_factor), while this repo honors the
        # flag — with True the two legitimately diverge
        routed_scaling_factor=1.0, norm_topk_prob=False,
        scoring_func="softmax", topk_method="greedy", n_group=1, topk_group=1,
        max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=True, torch_dtype="float32",
        attn_implementation="eager", aux_loss_alpha=0.0, seq_aux=False,
    )
    torch.manual_seed(10)
    model = transformers.DeepseekV2ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11, 85, 301]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()

    cfg = ds.DeepseekConfig.from_hf_config(f"{tmp_path}/config.json")
    cfg = ds.DeepseekConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = ds.load_hf_weights(cfg, tmp_path)
    cos, sin = ds.make_rope_tables(cfg)
    block_size = 4
    cache = ds.init_kv_cache(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prefill_len = 4
    logits, cache = ds.deepseek_forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=5e-4, rtol=5e-4
    )
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = ds.deepseek_forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=5e-4, rtol=5e-4,
            err_msg=f"mla decode position {p}",
        )


@pytest.mark.slow
def test_qwen3_moe_matches_hf(tmp_path):
    """Qwen3-MoE: per-head q/k RMSNorm + routed experts (norm_topk_prob
    honored by BOTH sides here, unlike the V2 port), prefill and decode."""
    if not hasattr(transformers, "Qwen3MoeForCausalLM"):
        pytest.skip("transformers too old for Qwen3Moe")
    from dynamo_tpu.models import mixtral as mx
    from dynamo_tpu.models.llama import init_kv_cache, make_rope_tables
    from dynamo_tpu.models.registry import get_family

    config = transformers.Qwen3MoeConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False, torch_dtype="float32",
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=48,
        decoder_sparse_step=1, norm_topk_prob=True, mlp_only_layers=[],
    )
    torch.manual_seed(11)
    model = transformers.Qwen3MoeForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()

    fam = get_family("qwen3_moe")
    cfg = fam.config_from_hf(f"{tmp_path}/config.json")
    assert cfg.qk_norm
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    params = fam.load_weights(cfg, tmp_path)
    cos, sin = make_rope_tables(cfg)
    block_size = 4
    cache = init_kv_cache(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prefill_len = 4
    logits, cache = fam.forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=5e-4, rtol=5e-4
    )
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = fam.forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=5e-4, rtol=5e-4,
            err_msg=f"qwen3-moe decode position {p}",
        )


@pytest.mark.slow
def test_gemma2_matches_hf(tmp_path):
    """Gemma-2: ALTERNATING sliding/full attention layers (per-layer window
    array through one scan), attn + final logit soft-capping, sandwich
    norms, query_pre_attn_scalar, GeGLU, sqrt(hidden) embed scale, (1+w)
    RMSNorm baked at load.  The 20-token prompt exceeds the 8-token window
    so the sliding layers genuinely drop context."""
    config = transformers.Gemma2Config(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        sliding_window=8, query_pre_attn_scalar=16.0,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        hidden_activation="gelu_pytorch_tanh", torch_dtype="float32",
        attn_implementation="eager",
    )
    torch.manual_seed(11)
    model = transformers.Gemma2ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    def ours(model_dir, prompt):
        from dynamo_tpu.models import gemma2
        from dynamo_tpu.models.registry import get_family

        fam = get_family("gemma2")
        cfg = fam.config_from_hf(f"{model_dir}/config.json")
        cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
        assert cfg.sliding_window == 8
        assert cfg.query_pre_attn_scalar == 16.0
        params = fam.load_weights(cfg, model_dir)
        cos, sin = fam.rope_tables(cfg)
        cache = fam.cache_init(cfg, 16, 4)
        blocks = jnp.arange(8, dtype=jnp.int32)
        logits, _ = gemma2.gemma2_forward_prefill(
            params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
            jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
        )
        return np.asarray(logits)

    _check(ours, model, tmp_path)


@pytest.mark.slow
def test_gemma2_windowed_decode_matches_hf(tmp_path):
    """Gemma-2 DECODE across the sliding boundary: the even (windowed)
    layers must drop old context per-position while the odd (full) layers
    keep it — the per-layer traced-window mask in paged_decode_attention."""
    from dynamo_tpu.models import gemma2
    from dynamo_tpu.models.registry import get_family

    config = transformers.Gemma2Config(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        sliding_window=6, query_pre_attn_scalar=16.0,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        hidden_activation="gelu_pytorch_tanh", torch_dtype="float32",
        attn_implementation="eager",
    )
    torch.manual_seed(12)
    model = transformers.Gemma2ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11, 85, 301, 12, 13, 44, 45]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()

    fam = get_family("gemma2")
    cfg = fam.config_from_hf(f"{tmp_path}/config.json")
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    params = fam.load_weights(cfg, tmp_path)
    cos, sin = fam.rope_tables(cfg)
    block_size = 4
    cache = fam.cache_init(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prefill_len = 4
    logits, cache = gemma2.gemma2_forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=3e-4, rtol=3e-4
    )
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):  # crosses window 6 at p >= 6
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = gemma2.gemma2_forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=3e-4, rtol=3e-4,
            err_msg=f"gemma2 windowed decode position {p}",
        )


@pytest.mark.slow
def test_gemma3_matches_hf(tmp_path):
    """Gemma-3 text: 5:1 local/global attention pattern, DUAL rope bases
    (local 10k / global 1M, packed along the feature axis and selected by
    a traced per-layer flag), per-head q/k (1+w) RMSNorm, no soft-capping.
    7 layers puts one global layer (idx 5) among six local ones; the
    20-token prompt exceeds the 8-token window."""
    config = transformers.Gemma3TextConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=7, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256,
        rope_theta=1_000_000.0, rope_local_base_freq=10000.0,
        sliding_window=8, query_pre_attn_scalar=16.0,
        hidden_activation="gelu_pytorch_tanh", torch_dtype="float32",
        attn_implementation="eager",
    )
    torch.manual_seed(13)
    model = transformers.Gemma3ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    def ours(model_dir, prompt):
        from dynamo_tpu.models import gemma3
        from dynamo_tpu.models.registry import get_family

        fam = get_family("gemma3_text")
        cfg = fam.config_from_hf(f"{model_dir}/config.json")
        cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
        assert cfg.global_layers == (False,) * 5 + (True,) + (False,)
        params = fam.load_weights(cfg, model_dir)
        cos, sin = fam.rope_tables(cfg)
        cache = fam.cache_init(cfg, 16, 4)
        blocks = jnp.arange(8, dtype=jnp.int32)
        logits, _ = gemma3.gemma3_forward_prefill(
            params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
            jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
        )
        return np.asarray(logits)

    _check(ours, model, tmp_path)


@pytest.mark.slow
def test_gemma3_windowed_decode_matches_hf(tmp_path):
    """Gemma-3 DECODE across the sliding boundary with the dual-base rope:
    local layers drop context per-position, the global layer keeps it."""
    from dynamo_tpu.models import gemma3
    from dynamo_tpu.models.registry import get_family

    config = transformers.Gemma3TextConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=7, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256,
        rope_theta=1_000_000.0, rope_local_base_freq=10000.0,
        sliding_window=6, query_pre_attn_scalar=16.0,
        hidden_activation="gelu_pytorch_tanh", torch_dtype="float32",
        attn_implementation="eager",
    )
    torch.manual_seed(14)
    model = transformers.Gemma3ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    tokens = [3, 17, 99, 250, 7, 42, 200, 11, 85, 301, 12, 13, 44, 45]
    with torch.no_grad():
        hf_all = model(
            torch.tensor([tokens], dtype=torch.long)
        ).logits[0].float().numpy()

    fam = get_family("gemma3")
    cfg = fam.config_from_hf(f"{tmp_path}/config.json")
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    params = fam.load_weights(cfg, tmp_path)
    cos, sin = fam.rope_tables(cfg)
    block_size = 4
    cache = fam.cache_init(cfg, 16, block_size)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prefill_len = 4
    logits, cache = gemma3.gemma3_forward_prefill(
        params, cfg, jnp.asarray(tokens[:prefill_len], jnp.int32), cache,
        blocks, jnp.int32(prefill_len), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), hf_all[prefill_len - 1], atol=3e-4, rtol=3e-4
    )
    tables = blocks[None, :]
    for p in range(prefill_len, len(tokens)):
        slot = jnp.asarray([blocks[p // block_size] * block_size + p % block_size])
        logits, cache = gemma3.gemma3_forward_decode(
            params, cfg, jnp.asarray([tokens[p]], jnp.int32), cache,
            tables, jnp.asarray([p + 1], jnp.int32), slot, cos, sin,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], hf_all[p], atol=3e-4, rtol=3e-4,
            err_msg=f"gemma3 windowed decode position {p}",
        )


@pytest.mark.slow
def test_gemma3_multimodal_checkpoint_text_half(tmp_path):
    """A multimodal Gemma-3 checkpoint (Gemma3ForConditionalGeneration:
    nested text_config, weights under model.language_model.*) loads its
    text half through the same family — config unwrap + tensor remap —
    and matches the HF text model's logits."""
    text_cfg = dict(
        vocab_size=320, hidden_size=64, intermediate_size=128,
        num_hidden_layers=7, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256,
        rope_theta=1_000_000.0, rope_local_base_freq=10000.0,
        sliding_window=8, query_pre_attn_scalar=16.0,
        hidden_activation="gelu_pytorch_tanh",
    )
    config = transformers.Gemma3Config(
        text_config=text_cfg,
        vision_config={
            "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 1, "num_attention_heads": 2,
            "image_size": 28, "patch_size": 14,
        },
        torch_dtype="float32",
    )
    torch.manual_seed(15)
    model = transformers.Gemma3ForConditionalGeneration(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    from dynamo_tpu.models import gemma3
    from dynamo_tpu.models.registry import get_family

    fam = get_family("gemma3")
    cfg = fam.config_from_hf(f"{tmp_path}/config.json")  # unwraps text_config
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    assert cfg.num_layers == 7 and cfg.sliding_window == 8
    params = fam.load_weights(cfg, tmp_path)  # remaps language_model.*
    cos, sin = fam.rope_tables(cfg)
    cache = fam.cache_init(cfg, 16, 4)
    blocks = jnp.arange(8, dtype=jnp.int32)

    prompt = [3, 17, 99, 250, 7, 42]
    logits, _ = gemma3.gemma3_forward_prefill(
        params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
        jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
    )
    with torch.no_grad():
        hf = model.language_model(
            torch.tensor([prompt], dtype=torch.long)
        ).last_hidden_state
        hf_logits = (
            hf @ model.model.language_model.embed_tokens.weight.T
        )[0, -1].float().numpy()
    np.testing.assert_allclose(
        np.asarray(logits), hf_logits, atol=3e-4, rtol=3e-4
    )
