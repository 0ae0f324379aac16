"""Pipeline parallelism: the pp-staged decode must match the single-device
layer scan exactly (same layer body, microbatched over ppermute handoffs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import (
    LlamaConfig,
    init_kv_cache,
    init_params,
    llama_forward_decode,
    llama_forward_decode_pp,
    make_rope_tables,
)
from dynamo_tpu.parallel import MeshConfig, make_mesh

# 4 layers so the stack splits across up to 4 stages
CFG = LlamaConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=4,
    num_heads=4, num_kv_heads=2, head_dim=16, max_position_embeddings=2048,
    rope_theta=10000.0, tie_word_embeddings=True, dtype=jnp.float32,
)


def setup(batch=8, num_blocks=16, block_size=4):
    params = init_params(CFG, jax.random.PRNGKey(0))
    cos, sin = make_rope_tables(CFG)
    cache = init_kv_cache(CFG, num_blocks, block_size)
    # pre-populate the cache with context so attention is non-trivial
    key = jax.random.PRNGKey(1)
    cache = {
        k: jax.random.normal(jax.random.fold_in(key, i), v.shape, v.dtype)
        for i, (k, v) in enumerate(cache.items())
    }
    maxb = 4
    tables = jnp.asarray(
        [[i * maxb + j for j in range(maxb)] for i in range(batch)], jnp.int32
    ) % num_blocks
    lens = jnp.asarray([3 + i for i in range(batch)], jnp.int32)
    slots = (tables[jnp.arange(batch), (lens - 1) // block_size] * block_size
             + (lens - 1) % block_size)
    tokens = jnp.asarray(np.arange(batch) % 5 + 2, jnp.int32)
    return params, cache, tokens, tables, lens, slots, cos, sin


@pytest.mark.parametrize("pp,microbatches", [(4, 4), (2, 4), (4, 2)])
def test_pp_decode_matches_single_device(pp, microbatches):
    mesh = make_mesh(MeshConfig(pp=pp), devices=jax.devices()[:pp])
    params, cache, tokens, tables, lens, slots, cos, sin = setup()

    ref_logits, ref_cache = llama_forward_decode(
        params, CFG, tokens, {k: v.copy() for k, v in cache.items()},
        tables, lens, slots, cos, sin,
    )
    pp_logits, pp_cache = llama_forward_decode_pp(
        params, CFG, tokens, cache, tables, lens, slots, cos, sin,
        pp_mesh=mesh, microbatches=microbatches,
    )
    np.testing.assert_allclose(
        np.asarray(pp_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    for k in ref_cache:
        np.testing.assert_allclose(
            np.asarray(pp_cache[k]), np.asarray(ref_cache[k]), rtol=1e-6, atol=1e-6
        )


def test_pp_requires_divisible_batch():
    mesh = make_mesh(MeshConfig(pp=4), devices=jax.devices()[:4])
    params, cache, tokens, tables, lens, slots, cos, sin = setup(batch=8)
    with pytest.raises(ValueError, match="not divisible"):
        llama_forward_decode_pp(
            params, CFG, tokens, cache, tables, lens, slots, cos, sin,
            pp_mesh=mesh, microbatches=3,
        )


def test_engine_rejects_indivisible_pp_config():
    from dynamo_tpu.engine import EngineConfig, JaxLlmEngine

    with pytest.raises(ValueError, match="divisible by the pp axis"):
        JaxLlmEngine(
            EngineConfig(
                model=CFG, num_blocks=16, block_size=4, max_batch_size=6,
                mesh=MeshConfig(pp=4), max_model_len=64,
            )
        )


def _mixtral_setup(batch=8, num_blocks=16, block_size=4):
    from dynamo_tpu.models import mixtral as mx

    # routing per microbatch equals routing the whole batch: the expert
    # layer computes every assignment, whatever slice of the batch it sees
    cfg = mx.MixtralConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, max_position_embeddings=2048,
        rope_theta=10000.0, tie_word_embeddings=True, dtype=jnp.float32,
        num_experts=4, experts_per_token=2,
    )
    params = mx.init_params(cfg, jax.random.PRNGKey(2))
    cos, sin = make_rope_tables(cfg)
    cache = init_kv_cache(cfg, num_blocks, block_size)
    key = jax.random.PRNGKey(1)
    cache = {
        k: jax.random.normal(jax.random.fold_in(key, i), v.shape, v.dtype)
        for i, (k, v) in enumerate(cache.items())
    }
    maxb = 4
    tables = jnp.asarray(
        [[i * maxb + j for j in range(maxb)] for i in range(batch)], jnp.int32
    ) % num_blocks
    lens = jnp.asarray([3 + i for i in range(batch)], jnp.int32)
    slots = (tables[jnp.arange(batch), (lens - 1) // block_size] * block_size
             + (lens - 1) % block_size)
    tokens = jnp.asarray(np.arange(batch) % 5 + 2, jnp.int32)
    return cfg, params, cache, tokens, tables, lens, slots, cos, sin


def test_pp_ep_mixtral_decode_matches_single_device():
    """pp×ep composition (BASELINE.json's Mixtral-on-v5p shape): stages
    over the manual pp axis, expert weights sharded over the automatic ep
    axis inside each stage, vs the plain single-device MoE decode."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.models import mixtral as mx
    from dynamo_tpu.models.llama import kv_cache_spec
    from dynamo_tpu.models.registry import get_family

    cfg, params, cache, tokens, tables, lens, slots, cos, sin = _mixtral_setup()
    ref_logits, ref_cache = get_family("mixtral").forward_decode(
        params, cfg, tokens, {k: v.copy() for k, v in cache.items()},
        tables, lens, slots, cos, sin,
    )

    mesh = make_mesh(MeshConfig(pp=2, ep=2), devices=jax.devices()[:4])
    params_m = jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), NamedSharding(mesh, s)),
        params, mx.param_specs(cfg),
    )
    cache_m = jax.tree.map(
        lambda x: jax.device_put(np.asarray(x), NamedSharding(mesh, kv_cache_spec())),
        cache,
    )
    pp_logits, pp_cache = get_family("mixtral").forward_decode_pp(
        params_m, cfg, tokens, cache_m, tables, lens, slots, cos, sin,
        pp_mesh=mesh, microbatches=2,
    )
    np.testing.assert_allclose(
        np.asarray(pp_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    for k in ref_cache:
        np.testing.assert_allclose(
            np.asarray(pp_cache[k]), np.asarray(ref_cache[k]), rtol=1e-6, atol=1e-6
        )


def test_engine_accepts_pp_ep_moe_and_rejects_pp_ep_dense():
    from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
    from dynamo_tpu.models import mixtral as mx

    mcfg = mx.MixtralConfig.tiny_moe()
    engine = JaxLlmEngine(
        EngineConfig(
            model=mcfg, model_family="mixtral", num_blocks=16, block_size=4,
            max_batch_size=4, mesh=MeshConfig(pp=2, ep=2), max_model_len=64,
        )
    )
    assert engine.mesh is not None  # init accepted the composition

    with pytest.raises(ValueError, match="composes with tp"):
        JaxLlmEngine(
            EngineConfig(
                model=CFG, num_blocks=16, block_size=4, max_batch_size=4,
                mesh=MeshConfig(pp=2, ep=2), max_model_len=64,
            )
        )
