"""HF safetensors loaders for the MoE/MLA families: export our tiny params
in the HF layout, load them back through the family loader, and require the
forward pass to match the original exactly (mapping + transposes + expert
stacking + kv_b split are all load-bearing)."""

import jax
import jax.numpy as jnp
import numpy as np
from safetensors.numpy import save_file

from dynamo_tpu.models import deepseek, mixtral
from dynamo_tpu.models.registry import get_family


def test_mixtral_hf_roundtrip(tmp_path):
    cfg = mixtral.MixtralConfig.tiny_moe()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    L = params["layers"]

    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"], np.float32),
        "model.norm.weight": np.asarray(params["final_norm"], np.float32),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        tensors[f"{p}.input_layernorm.weight"] = np.asarray(L["attn_norm"][i], np.float32)
        tensors[f"{p}.self_attn.q_proj.weight"] = np.ascontiguousarray(np.asarray(L["wq"][i], np.float32).T)
        tensors[f"{p}.self_attn.k_proj.weight"] = np.ascontiguousarray(np.asarray(L["wk"][i], np.float32).T)
        tensors[f"{p}.self_attn.v_proj.weight"] = np.ascontiguousarray(np.asarray(L["wv"][i], np.float32).T)
        tensors[f"{p}.self_attn.o_proj.weight"] = np.ascontiguousarray(np.asarray(L["wo"][i], np.float32).T)
        tensors[f"{p}.post_attention_layernorm.weight"] = np.asarray(L["mlp_norm"][i], np.float32)
        tensors[f"{p}.block_sparse_moe.gate.weight"] = np.ascontiguousarray(np.asarray(L["w_router"][i], np.float32).T)
        for e in range(cfg.num_experts):
            tensors[f"{p}.block_sparse_moe.experts.{e}.w1.weight"] = np.ascontiguousarray(np.asarray(L["w_gate"][i, e], np.float32).T)
            tensors[f"{p}.block_sparse_moe.experts.{e}.w3.weight"] = np.ascontiguousarray(np.asarray(L["w_up"][i, e], np.float32).T)
            tensors[f"{p}.block_sparse_moe.experts.{e}.w2.weight"] = np.ascontiguousarray(np.asarray(L["w_down"][i, e], np.float32).T)
    save_file(tensors, str(tmp_path / "model.safetensors"))

    loaded = mixtral.load_hf_weights(cfg, tmp_path)
    for (path_a, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(loaded)[0],
        jax.tree_util.tree_flatten_with_path(params)[0],
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6), path_a

    # forward equality through real compute
    from dynamo_tpu.models.llama import init_kv_cache, make_rope_tables

    cos, sin = make_rope_tables(cfg)
    tokens = jnp.arange(3, 11, dtype=jnp.int32)
    blocks = jnp.asarray([0, 1], jnp.int32)
    ref, _ = get_family("mixtral").forward_prefill(
        params, cfg, tokens, init_kv_cache(cfg, 8, 4), blocks,
        jnp.int32(8), jnp.int32(0), cos, sin,
    )
    out, _ = get_family("mixtral").forward_prefill(
        loaded, cfg, tokens, init_kv_cache(cfg, 8, 4), blocks,
        jnp.int32(8), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_deepseek_hf_roundtrip(tmp_path):
    cfg = deepseek.DeepseekConfig.tiny_mla()
    params = deepseek.init_params(cfg, jax.random.PRNGKey(1))
    H, nope, vd, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"], np.float32),
        "model.norm.weight": np.asarray(params["final_norm"], np.float32),
    }

    P_rope = cfg.qk_rope_head_dim

    def interleave(cols):
        """Inverse of the loader's de-interleave: write HF's interleaved
        rope column order."""
        out = np.empty_like(cols)
        half = cols.shape[-1] // 2
        out[..., 0::2] = cols[..., :half]
        out[..., 1::2] = cols[..., half:]
        return out

    def export_attn(src, j, i):
        p = f"model.layers.{i}.self_attn"
        tensors[f"model.layers.{i}.input_layernorm.weight"] = np.asarray(src["attn_norm"][j], np.float32)
        tensors[f"model.layers.{i}.post_attention_layernorm.weight"] = np.asarray(src["mlp_norm"][j], np.float32)
        w_dkv = np.asarray(src["w_dkv"][j], np.float32).copy()
        w_dkv[:, r:] = interleave(w_dkv[:, r:])
        tensors[f"{p}.kv_a_proj_with_mqa.weight"] = np.ascontiguousarray(w_dkv.T)
        tensors[f"{p}.kv_a_layernorm.weight"] = np.asarray(src["kv_norm"][j], np.float32)
        # inverse of the kv_b split: w_uk [r, H*nope], w_uv [r, H*v] → [H*(nope+v), r]
        w_uk = np.asarray(src["w_uk"][j], np.float32).reshape(r, H, nope).transpose(1, 2, 0)
        w_uv = np.asarray(src["w_uv"][j], np.float32).reshape(r, H, vd).transpose(1, 2, 0)
        kv_b = np.ascontiguousarray(np.concatenate([w_uk, w_uv], axis=1).reshape(H * (nope + vd), r))
        tensors[f"{p}.kv_b_proj.weight"] = kv_b
        tensors[f"{p}.o_proj.weight"] = np.ascontiguousarray(np.asarray(src["wo"][j], np.float32).T)
        if cfg.q_lora_rank:
            tensors[f"{p}.q_a_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_dq"][j], np.float32).T)
            tensors[f"{p}.q_a_layernorm.weight"] = np.asarray(src["q_norm"][j], np.float32)
            w_uq = np.asarray(src["w_uq"][j], np.float32).copy()
            w_uq = w_uq.reshape(w_uq.shape[0], H, nope + P_rope)
            w_uq[..., nope:] = interleave(w_uq[..., nope:])
            w_uq = w_uq.reshape(w_uq.shape[0], -1)
            tensors[f"{p}.q_b_proj.weight"] = np.ascontiguousarray(w_uq.T)
        else:
            wq = np.asarray(src["wq"][j], np.float32).copy()
            wq = wq.reshape(wq.shape[0], H, nope + P_rope)
            wq[..., nope:] = interleave(wq[..., nope:])
            wq = wq.reshape(wq.shape[0], -1)
            tensors[f"{p}.q_proj.weight"] = np.ascontiguousarray(wq.T)

    for i in range(cfg.first_k_dense):
        src = params["dense_layers"]
        export_attn(src, i, i)
        mlp = f"model.layers.{i}.mlp"
        tensors[f"{mlp}.gate_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_gate"][i], np.float32).T)
        tensors[f"{mlp}.up_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_up"][i], np.float32).T)
        tensors[f"{mlp}.down_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_down"][i], np.float32).T)
    for j in range(cfg.num_moe_layers):
        i = cfg.first_k_dense + j
        src = params["moe_layers"]
        export_attn(src, j, i)
        mlp = f"model.layers.{i}.mlp"
        tensors[f"{mlp}.gate.weight"] = np.ascontiguousarray(np.asarray(src["w_router"][j], np.float32).T)
        for e in range(cfg.num_experts):
            tensors[f"{mlp}.experts.{e}.gate_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_gate"][j, e], np.float32).T)
            tensors[f"{mlp}.experts.{e}.up_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_up"][j, e], np.float32).T)
            tensors[f"{mlp}.experts.{e}.down_proj.weight"] = np.ascontiguousarray(np.asarray(src["w_down"][j, e], np.float32).T)
        if cfg.n_shared_experts:
            tensors[f"{mlp}.shared_experts.gate_proj.weight"] = np.ascontiguousarray(np.asarray(src["ws_gate"][j], np.float32).T)
            tensors[f"{mlp}.shared_experts.up_proj.weight"] = np.ascontiguousarray(np.asarray(src["ws_up"][j], np.float32).T)
            tensors[f"{mlp}.shared_experts.down_proj.weight"] = np.ascontiguousarray(np.asarray(src["ws_down"][j], np.float32).T)
    save_file(tensors, str(tmp_path / "model.safetensors"))

    loaded = deepseek.load_hf_weights(cfg, tmp_path)
    cos, sin = deepseek.make_rope_tables(cfg)
    tokens = jnp.arange(3, 11, dtype=jnp.int32)
    blocks = jnp.asarray([0, 1], jnp.int32)
    ref, _ = deepseek.deepseek_forward_prefill(
        params, cfg, tokens, deepseek.init_kv_cache(cfg, 8, 4), blocks,
        jnp.int32(8), jnp.int32(0), cos, sin,
    )
    out, _ = deepseek.deepseek_forward_prefill(
        loaded, cfg, tokens, deepseek.init_kv_cache(cfg, 8, 4), blocks,
        jnp.int32(8), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
