"""Mixtral MoE: router/dispatch correctness vs per-token dense expert
reference, prefill/decode consistency, ep+tp sharded equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import init_kv_cache, kv_cache_spec, make_rope_tables
from dynamo_tpu.models.mixtral import MixtralConfig, init_params, param_specs
from dynamo_tpu.models.registry import get_family
from dynamo_tpu.ops.moe import moe_experts, moe_ffn, moe_router
from dynamo_tpu.parallel import MeshConfig, make_mesh, shard_pytree

CFG = MixtralConfig.tiny_moe()
# the family's step programs, as the engine takes them
mixtral_forward_prefill = get_family("mixtral").forward_prefill
mixtral_forward_decode = get_family("mixtral").forward_decode
BLOCK_SIZE = 4
NUM_BLOCKS = 32


def test_moe_matches_per_token_dense():
    """The sorted grouped product must equal computing each token through
    its own top-k experts directly."""
    rng = jax.random.PRNGKey(0)
    t, h, i, e, k = 6, 16, 24, 4, 2
    keys = jax.random.split(rng, 5)
    x = jax.random.normal(keys[0], (t, h), jnp.float32)
    w_router = jax.random.normal(keys[1], (h, e), jnp.float32)
    w_gate = jax.random.normal(keys[2], (e, h, i), jnp.float32) / 4
    w_up = jax.random.normal(keys[3], (e, h, i), jnp.float32) / 4
    w_down = jax.random.normal(keys[4], (e, i, h), jnp.float32) / 4

    out = moe_ffn(x, w_router, w_gate, w_up, w_down, top_k=k)

    ids, probs = moe_router(x, w_router, k)
    expected = np.zeros((t, h), np.float32)
    for ti in range(t):
        for kk in range(k):
            eid = int(ids[ti, kk])
            hidden = jax.nn.silu(x[ti] @ w_gate[eid]) * (x[ti] @ w_up[eid])
            expected[ti] += float(probs[ti, kk]) * np.asarray(hidden @ w_down[eid])
    np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("told", [None, 2], ids=["router_unknown", "every_expert_held"])
def test_no_token_dropped_when_all_route_to_one_expert(told):
    """Every token routed to ONE expert still gets that expert's result
    (the capacity path zeroed all but the first ``capacity`` of them), told
    the router's width (both experts held: the gather sums the rows) or not."""
    rng = jax.random.PRNGKey(1)
    t, h, i = 8, 8, 8
    x = jax.random.normal(rng, (t, h), jnp.float32)
    ids = jnp.zeros((t, 1), jnp.int32)
    probs = jnp.ones((t, 1), jnp.float32)
    w = jnp.stack([jnp.eye(h, i), 2 * jnp.eye(h, i)])
    out, stats = moe_experts(
        x, ids, probs, w, w, jnp.stack([jnp.eye(i, h)] * 2), experts_routed=told)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jax.nn.silu(x) * x), rtol=1e-5, atol=1e-5
    )
    # routed, held, experts touched, busiest expert's rows, layers, rows walked,
    # rows multiplied (one row tile of 128), rows gathered
    assert stats.tolist() == [t, t, 1, t, 1, t, 128, t if told else 0]


def test_mixtral_prefill_decode_consistency():
    """Decoding token t+1 after prefill(1..t) must match prefill(1..t+1)."""
    params = init_params(CFG, jax.random.PRNGKey(2))
    cos, sin = make_rope_tables(CFG)
    tokens = list(range(3, 12))
    block_ids = jnp.asarray([0, 1, 2], jnp.int32)

    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_a, cache = mixtral_forward_prefill(
        params, CFG, jnp.asarray(tokens, jnp.int32), cache, block_ids,
        jnp.int32(len(tokens)), jnp.int32(0), cos, sin,
    )
    nxt = int(jnp.argmax(logits_a))

    # path A: decode the next token against the cache
    context = len(tokens) + 1
    slot = jnp.asarray([(context - 1) // BLOCK_SIZE * BLOCK_SIZE + (context - 1) % BLOCK_SIZE], jnp.int32)
    tables = jnp.pad(block_ids, (0, 1))[None, :]
    logits_dec, _ = mixtral_forward_decode(
        params, CFG, jnp.asarray([nxt], jnp.int32), cache, tables,
        jnp.asarray([context], jnp.int32), slot, cos, sin,
    )

    # path B: fresh prefill over tokens + [nxt]
    cache2 = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_b, _ = mixtral_forward_prefill(
        params, CFG, jnp.asarray(tokens + [nxt], jnp.int32), cache2, block_ids,
        jnp.int32(context), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(np.asarray(logits_dec[0]), np.asarray(logits_b), rtol=2e-3, atol=2e-3)


def test_mixtral_ep_sharded_matches_single():
    params = init_params(CFG, jax.random.PRNGKey(3))
    cos, sin = make_rope_tables(CFG)
    tokens = jnp.asarray(list(range(3, 11)), jnp.int32)
    block_ids = jnp.asarray([0, 1], jnp.int32)

    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_single, _ = mixtral_forward_prefill(
        params, CFG, tokens, cache, block_ids, jnp.int32(8), jnp.int32(0), cos, sin
    )

    mesh = make_mesh(MeshConfig(ep=2, tp=2), devices=jax.devices()[:4])
    sharded_params = shard_pytree(params, param_specs(CFG), mesh)
    cache_specs = {"k": kv_cache_spec(), "v": kv_cache_spec()}
    sharded_cache = shard_pytree(init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE), cache_specs, mesh)
    out_shardings = (
        NamedSharding(mesh, P()),
        {"k": NamedSharding(mesh, kv_cache_spec()), "v": NamedSharding(mesh, kv_cache_spec())},
    )

    run = jax.jit(
        lambda p, c, ids: mixtral_forward_prefill(
            p, CFG, ids, c, block_ids, jnp.int32(8), jnp.int32(0), cos, sin
        ),
        out_shardings=out_shardings,
    )
    logits_ep, _ = run(sharded_params, sharded_cache, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_ep), np.asarray(logits_single), rtol=2e-3, atol=2e-3
    )


def test_qwen3_moe_qk_norm_prefill_decode_consistency():
    """Qwen3-MoE geometry (MoE + per-head qk-norm): decode at position t
    must match prefill logits at the same position."""
    import dataclasses

    import numpy as np

    cfg = dataclasses.replace(CFG, qk_norm=True)
    params = init_params(cfg, jax.random.PRNGKey(5))
    params["layers"]["q_norm"] = (
        1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(6),
                                      params["layers"]["q_norm"].shape)
    ).astype(cfg.dtype)
    params["layers"]["k_norm"] = (
        1.0 - 0.2 * jax.random.normal(jax.random.PRNGKey(7),
                                      params["layers"]["k_norm"].shape)
    ).astype(cfg.dtype)
    cos, sin = make_rope_tables(cfg)
    prompt = list(range(3, 11))
    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    blocks = jnp.asarray([0, 1, 2], jnp.int32)
    logits, cache = mixtral_forward_prefill(
        params, cfg, jnp.asarray(prompt, jnp.int32), cache, blocks,
        jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
    )
    nxt = int(jnp.argmax(logits))
    full = prompt + [nxt]
    cache2 = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    ref, _ = mixtral_forward_prefill(
        params, cfg, jnp.asarray(full, jnp.int32), cache2, blocks,
        jnp.int32(len(full)), jnp.int32(0), cos, sin,
    )
    tables = blocks[None, :]
    dec, _ = mixtral_forward_decode(
        params, cfg, jnp.asarray([nxt], jnp.int32), cache, tables,
        jnp.asarray([len(full)], jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(dec[0]), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_qwen3_moe_registry_and_loader(tmp_path):
    """qwen3_moe family: config flags flow, and the loader reads the
    Qwen3-MoE expert naming (mlp.experts.{e}.gate_proj) + q/k norms."""
    import dataclasses

    import numpy as np
    from safetensors.numpy import save_file

    from dynamo_tpu.models.registry import get_family

    fam = get_family("qwen3_moe")
    cfg = fam.config_from_hf(
        {
            "model_type": "qwen3_moe",
            "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
            "moe_intermediate_size": 48,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "num_experts": 4, "num_experts_per_tok": 2,
            "tie_word_embeddings": True, "norm_topk_prob": False,
        }
    )
    assert cfg.qk_norm and cfg.num_experts == 4
    assert cfg.tie_word_embeddings            # must not drop HF fields
    assert cfg.expert_intermediate_size == 48
    assert not cfg.norm_topk_prob

    cfg = dataclasses.replace(CFG, qk_norm=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    L = params["layers"]
    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"], np.float32),
        "model.norm.weight": np.asarray(params["final_norm"], np.float32),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        tensors[f"{p}.input_layernorm.weight"] = np.asarray(L["attn_norm"][i], np.float32)
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            tensors[f"{p}.self_attn.{theirs}.weight"] = np.ascontiguousarray(
                np.asarray(L[ours][i], np.float32).T
            )
        tensors[f"{p}.self_attn.q_norm.weight"] = np.asarray(L["q_norm"][i], np.float32)
        tensors[f"{p}.self_attn.k_norm.weight"] = np.asarray(L["k_norm"][i], np.float32)
        tensors[f"{p}.post_attention_layernorm.weight"] = np.asarray(L["mlp_norm"][i], np.float32)
        tensors[f"{p}.mlp.gate.weight"] = np.ascontiguousarray(
            np.asarray(L["w_router"][i], np.float32).T
        )
        for e in range(cfg.num_experts):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                tensors[f"{p}.mlp.experts.{e}.{theirs}.weight"] = np.ascontiguousarray(
                    np.asarray(L[ours][i, e], np.float32).T
                )
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = fam.load_weights(cfg, tmp_path)
    for k in L:
        np.testing.assert_allclose(
            np.asarray(loaded["layers"][k]), np.asarray(L[k]), atol=1e-6,
            err_msg=k,
        )
