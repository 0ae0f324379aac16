"""The exaone_moe family through the engine: layer kinds, the window pool.

Served answers (tokens AND returned log-probabilities) are held against the
benchmark's plain reference of the same share (benchmark/reference/
exaone_moe.py) over sequences several windows long, on the split and on the
unified step, with the XLA attention and the Pallas kernels in interpret
mode; the window pool's blocks are counted after every step."""

import asyncio
import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.llm.protocols.common import Annotated, LLMEngineOutput
from dynamo_tpu.models.exaone_moe import ExaoneMoeConfig, init_params
from dynamo_tpu.runtime.engine import Context
from tests.engine.test_jax_engine import request

REF = modules.load(
    Path(__file__).resolve().parents[2] / "benchmark" / "reference" / "exaone_moe.py"
)
WINDOW, BLOCK = 8, 4
HF = {
    "model_type": "exaone_moe", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "sliding_window": WINDOW,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "num_experts": 2, "expert_parallel_size": 4, "expert_parallel_rank": 1,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
}
SEED = 11


@pytest.fixture(scope="module")
def served():
    """The config as the server parses it, and ONE set of weights: the
    recipe's bfloat16 values, served and referred to in float32 so that the
    comparison is of the mathematics."""
    cfg = dataclasses.replace(
        ExaoneMoeConfig.from_hf_config(HF), dtype=jnp.bfloat16
    )
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32), init_params(cfg, jax.random.PRNGKey(SEED))
    )
    return dataclasses.replace(cfg, dtype=jnp.float32), params, REF.init_weights(HF, SEED)


def make_engine(served, **overrides) -> JaxLlmEngine:
    cfg, params, _ = served
    defaults = dict(
        model=cfg, model_family="exaone_moe", num_blocks=64, block_size=BLOCK,
        max_batch_size=4, prefill_buckets=(16, 32, 64), max_model_len=96,
    )
    defaults.update(overrides)
    engine = JaxLlmEngine(EngineConfig(**defaults), params=params)
    engine.start()
    return engine


async def collect(engine, req_wire):
    stream = await engine.generate(Context(req_wire))
    tokens, lps = [], []
    async for item in stream:
        data = Annotated.from_wire(item, LLMEngineOutput.from_wire).data
        if data is not None:
            tokens.extend(data.token_ids)
            lps.extend(data.logprobs or [])
    return tokens, lps


async def idle_stats(engine) -> dict:
    """The engine's stats once the last window has retired (a finished
    lane's blocks go back when the window after its last one does)."""
    for _ in range(100):
        stats = engine.stats()
        if not stats["kv_active_blocks"] and not stats["num_requests_running"]:
            break
        await asyncio.sleep(0.02)
    return stats


def reference_logprobs(served, prompt, tokens):
    """The reference's full forward over prompt + served tokens: its
    log-probability of each served token, and its own first choice there."""
    _, _, weights = served
    with jax.default_matmul_precision("highest"):
        rows = range(len(prompt) - 1, len(prompt) + len(tokens) - 1)
        logits = np.asarray(REF.forward(weights, HF, prompt + tokens, rows=list(rows)))
    lsm = logits - np.asarray(jax.nn.logsumexp(logits, axis=-1))[:, None]
    return lsm[np.arange(len(tokens)), tokens], logits.argmax(-1).tolist()


PROMPTS = [
    [int(t) for t in np.random.default_rng(i).integers(2, 500, size=n)]
    for i, n in enumerate((41, 27, 9))
]


@pytest.mark.parametrize("unified", [False, True], ids=["split", "unified"])
@pytest.mark.parametrize("attention", ["jax", "pallas_interpret"])
async def test_prefill_then_decode_through_both_pools_equals_reference(
    served, unified, attention
):
    """Sequences five windows long, three at a time (admission beside
    running decodes on the unified step): every served token is the
    reference's first choice and its log-probability the reference's."""
    engine = make_engine(served, unified_batch=unified, attention_impl=attention)
    try:
        results = []
        tasks = []
        for prompt in PROMPTS:
            tasks.append(asyncio.ensure_future(
                collect(engine, request(prompt, max_tokens=14, ignore_eos=True))
            ))
            await asyncio.sleep(0.05)
        results = await asyncio.gather(*tasks)
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    for prompt, (tokens, lps) in zip(PROMPTS, results):
        assert len(tokens) == 14
        want_lp, want_first = reference_logprobs(served, prompt, tokens)
        assert tokens == want_first
        np.testing.assert_allclose(lps, want_lp, atol=2e-4)
    if unified:
        assert stats["decode_windows_unified_total"] > 0
    assert stats["prefix_hits_total"] == 0
    # 2 of 8 experts are held: a quarter of the assignments, give or take
    routed, held = stats["moe_assignments_routed_total"], stats["moe_assignments_held_total"]
    assert routed > 0 and 0.1 < held / routed < 0.45
    assert stats["moe_rows_gathered_total"] == 0        # a share held: the walk scatter-adds
    assert stats["moe_expert_layers_total"] % 7 == 0
    assert stats["moe_gmm_flops_total"] == 2 * 3 * 64 * 32 * held
    if attention != "jax":      # the kernels' work is counted where they run
        assert 0 < stats["window_pages_visited_total"] < stats["window_pages_full_total"]
    assert stats["window_blocks_released_total"] > 0
    assert stats["window_pool_blocks_in_use"] == 0 and stats["kv_active_blocks"] == 0


async def test_window_pool_holds_a_window_whatever_the_context(served):
    """After any step a sequence holds at most ceil((window + step tokens) /
    block) + 1 window-pool blocks: the whole prompt for the step that
    computes it, then the window and the block being filled."""
    engine = make_engine(served, unified_batch=True)
    pool = engine.allocator.window_pool
    seen: list[int] = []
    release = engine.allocator.release_behind_window

    def counting(seq_id, next_pos):
        release(seq_id, next_pos)
        seen.append(pool.held(seq_id))

    engine.allocator.release_behind_window = counting
    try:
        prompt = PROMPTS[0]
        tokens, _ = await collect(engine, request(prompt, max_tokens=30, ignore_eos=True))
        await idle_stats(engine)
    finally:
        engine.stop()
    assert len(tokens) == 30 and len(seen) >= 30
    # one token a step after the prompt's own
    assert max(seen) <= math.ceil((WINDOW + 1) / BLOCK) + 1
    assert pool.used_blocks == 0 and pool.released_behind_total >= (41 + 30 - WINDOW) // BLOCK - 1


async def test_preemption_returns_both_pools_whole(served):
    """Too few full-pool blocks for three growing sequences: the youngest is
    preempted and recomputed, every answer is still the reference's, and
    both pools come back whole."""
    engine = make_engine(served, num_blocks=30, unified_batch=True, max_model_len=64)
    try:
        results = await asyncio.gather(*[
            collect(engine, request(p, max_tokens=20, ignore_eos=True)) for p in PROMPTS
        ])
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    assert stats["num_preemptions_total"] > 0
    for prompt, (tokens, _) in zip(PROMPTS, results):
        assert tokens == reference_logprobs(served, prompt, tokens)[1]
    assert engine.allocator.used_blocks == 0
    assert engine.allocator.window_pool.used_blocks == 0


def test_refuses_what_a_window_pool_cannot_serve(served):
    cfg, params, _ = served
    with pytest.raises(ValueError, match="window pool"):
        JaxLlmEngine(EngineConfig(
            model=cfg, model_family="exaone_moe", num_blocks=16, block_size=BLOCK,
            max_batch_size=2, max_model_len=32, decode_steps=2,
        ), params=params)
    engine = JaxLlmEngine(EngineConfig(
        model=cfg, model_family="exaone_moe", num_blocks=16, block_size=BLOCK,
        max_batch_size=2, max_model_len=32,
    ), params=params)
    assert not engine.prefix_caching
    with pytest.raises(NotImplementedError, match="window pool"):
        engine.reserve_blocks(8)
