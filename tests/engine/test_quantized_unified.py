"""Quantized hot path through the unified batch step.

The matrix ISSUE'd for real-TPU serving: int8 weight-only quantization
and fp8 KV cache must both flow through ``forward_unified`` for every
family that ships one (llama geometry, mixtral, qwen3_moe, deepseek_v2)
WITHOUT tripping the engine's auto-disable — and split-vs-unified parity
must survive quantization.

Parity contract, empirically pinned:

- **int8 weights**: byte-identical greedy AND seeded streams.  Both
  engines share the SAME quantized params and a full-precision cache, so
  quantization cancels out of the split/unified comparison exactly.
- **fp8 KV, greedy**: byte-identical streams.  Argmax absorbs the
  read-path difference (split prefill attends full-precision in-prompt
  activations; unified reads every token back through the quantized
  cache).
- **fp8 KV, seeded high-temperature**: byte-identity is FORBIDDEN by
  construction (the paths genuinely compute different floats, and
  temperature amplifies the gap into different samples), so the pin is
  tolerance at the forward level — unified kernel vs the XLA twin on one
  fp8 cache agree tightly, and each engine path reproduces itself
  deterministically.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.engine.test_jax_engine import request, sampled_request
from tests.engine.test_unified_batch import run_family_matrix, run_matrix

FAMILIES = "llama", "mixtral", "qwen3_moe", "deepseek_v2"


def _family_params(*families):
    # llama is the fast-tier representative; the other families pin the
    # identical contract in the slow tier (per-family engine spin-up is
    # the dominant cost, ~10s each on the CPU harness)
    return [
        f if f == "llama" else pytest.param(f, marks=pytest.mark.slow)
        for f in families
    ]


def family_cfg(family):
    if family in ("mixtral", "qwen3_moe"):
        from dataclasses import replace

        from dynamo_tpu.models.mixtral import MixtralConfig

        cfg = MixtralConfig.tiny_moe()
        return replace(cfg, qk_norm=True) if family == "qwen3_moe" else cfg
    if family == "deepseek_v2":
        from dynamo_tpu.models.deepseek import DeepseekConfig

        return DeepseekConfig.tiny_mla()
    return None  # llama drives through run_matrix's shared tiny engine


async def _family_parity(family, reqs, **engine_kw):
    if family == "llama":
        split, unified, stats, _ = await run_matrix(
            reqs, overlap=True, **engine_kw
        )
    else:
        split, unified, stats = await run_family_matrix(
            family, family_cfg(family), reqs, overlap=True, **engine_kw
        )
    return split, unified, stats


@pytest.mark.parametrize("family", _family_params(*FAMILIES))
async def test_int8_unified_parity(family):
    """int8 weight-only: byte-identical greedy streams split-vs-unified
    (both paths run the SAME quantized weights), unified windows actually
    served, zero fallbacks."""
    prompts = [list(range(3 + i, 13 + i)) for i in range(3)]
    reqs = [request(p, max_tokens=6, ignore_eos=True) for p in prompts]
    split, unified, stats = await _family_parity(
        family, reqs, quantize="int8", prefill_chunk_tokens=8,
    )
    assert unified == split
    assert stats["decode_windows_unified_total"] > 0
    assert not stats["unified_fallbacks"]


@pytest.mark.parametrize("family", _family_params("llama", "deepseek_v2"))
async def test_int8_seeded_parity(family):
    """Seeded high-temperature sampling with penalties stays byte-identical
    under int8 — quantization is identical on both paths, so the sampled
    trajectories cannot diverge."""
    prompt = list(range(3, 20))
    req = sampled_request(
        prompt, max_tokens=8, temperature=8.0, seed=1234,
        frequency_penalty=2.0,
    )
    split, unified, stats = await _family_parity(
        family, [req], quantize="int8", prefill_chunk_tokens=8,
    )
    assert unified == split
    assert stats["decode_windows_unified_total"] > 0


@pytest.mark.parametrize("family", _family_params(*FAMILIES))
async def test_fp8_kv_unified_greedy_parity(family):
    """fp8 KV cache flows through the unified step (no auto-disable, no
    `unsupported_kv_dtype` fallback) and greedy streams stay byte-identical
    split-vs-unified for every family."""
    prompts = [list(range(3 + i, 13 + i)) for i in range(3)]
    reqs = [request(p, max_tokens=6, ignore_eos=True) for p in prompts]
    split, unified, stats = await _family_parity(
        family, reqs, kv_cache_dtype="fp8", prefill_chunk_tokens=8,
    )
    assert unified == split
    assert stats["decode_windows_unified_total"] > 0
    assert not stats["unified_fallbacks"]


@pytest.mark.slow
async def test_fp8_seeded_deterministic_not_byte_pinned():
    """The fp8 seeded case: split and unified compute genuinely different
    floats (full-precision in-prompt attention vs quantized cache reads),
    so byte-parity is not a valid contract — what IS pinned: each path
    reproduces itself exactly, and the unified path still serves ragged
    windows under seeded sampling."""
    prompt = list(range(3, 20))
    req = sampled_request(
        prompt, max_tokens=8, temperature=8.0, seed=1234,
        frequency_penalty=2.0,
    )
    runs = []
    for _ in range(2):
        _, unified, stats, _ = await run_matrix(
            [req], overlap=True, kv_cache_dtype="fp8",
            prefill_chunk_tokens=8,
        )
        runs.append(unified)
        assert stats["decode_windows_unified_total"] > 0
    assert runs[0] == runs[1]  # deterministic per path


@pytest.mark.slow
async def test_int8_weights_plus_fp8_kv_combined():
    """The full quantized serving stack (int8 weights + fp8 KV — the TPU
    analog of the reference's FP8 serving) through unified: streams land,
    unified windows serve, nothing falls back."""
    prompts = [list(range(3 + i, 13 + i)) for i in range(2)]
    reqs = [request(p, max_tokens=5, ignore_eos=True) for p in prompts]
    split, unified, stats = await _family_parity(
        "llama", reqs, quantize="int8", kv_cache_dtype="fp8",
        prefill_chunk_tokens=8,
    )
    assert unified == split
    assert stats["decode_windows_unified_total"] > 0
    assert not stats["unified_fallbacks"]


def test_fp8_unified_forward_kernel_vs_twin():
    """Interpret-mode pin for the fp8 KV READ inside the ragged kernel at
    the model level: llama_forward_unified with attention=pallas_interpret
    vs the XLA twin over one shared fp8 cache — same quantized inputs, so
    the tolerance is numerical noise, not quantization error."""
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_params,
        llama_forward_unified,
        make_rope_tables,
    )
    from dynamo_tpu.ops.pallas import pack_spans

    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    bs, lanes, maxb, tb = 4, 4, 4, 4
    cache = init_kv_cache(cfg, num_blocks=32, block_size=bs,
                          dtype=jnp.float8_e4m3fn)
    assert cache["k"].dtype == jnp.float8_e4m3fn
    tables = jnp.arange(lanes * maxb, dtype=jnp.int32).reshape(lanes, maxb)
    cos, sin = make_rope_tables(cfg)

    # ragged window: a 6-token chunk on lane 0 + three decode tokens
    spans = [(0, 0, 6), (1, 3, 1), (2, 5, 1), (3, 2, 1)]
    total = sum(n for _, _, n in spans)
    t_pad = -(-total // tb) * tb
    token_lane = np.full(t_pad, lanes, np.int32)
    token_pos = np.full(t_pad, -1, np.int32)
    ctx = np.zeros(lanes, np.int32)
    cur = 0
    for lane, start, n in spans:
        token_lane[cur:cur + n] = lane
        token_pos[cur:cur + n] = np.arange(start, start + n)
        ctx[lane] = start + n
        cur += n
    slot = np.where(
        token_pos >= 0,
        np.asarray(tables)[np.clip(token_lane, 0, lanes - 1)][
            np.arange(t_pad), np.clip(token_pos, 0, None) // bs
        ] * bs + np.clip(token_pos, 0, None) % bs,
        10**6,
    ).astype(np.int32)
    meta = pack_spans(token_lane, token_pos, lanes=lanes, tb_tokens=tb,
                      block_size=bs)
    tokens = jnp.asarray(np.arange(3, 3 + t_pad) % cfg.vocab_size, jnp.int32)
    args = (
        params, cfg, tokens, cache, tables, jnp.asarray(ctx),
        jnp.asarray(token_pos), jnp.asarray(slot), jnp.asarray(token_lane),
        *(jnp.asarray(a) for a in meta),
        jnp.asarray([5, 6, 7, 8], jnp.int32), cos, sin,
    )
    ref_logits, ref_cache = llama_forward_unified(
        *args, attention="jax", tb_tokens=tb
    )
    out_logits, out_cache = llama_forward_unified(
        *args, attention="pallas_interpret", tb_tokens=tb
    )
    assert ref_cache["k"].dtype == jnp.float8_e4m3fn
    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    # both paths wrote the same fp8 bytes back
    np.testing.assert_array_equal(
        np.asarray(out_cache["k"].astype(jnp.float32)),
        np.asarray(ref_cache["k"].astype(jnp.float32)),
    )
