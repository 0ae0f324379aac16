"""The phi4flash family through the engine: a recurrent state a lane beside
the two pools, pages one layer writes and seven read.

Served answers (tokens AND returned log-probabilities) are held against the
benchmark's plain reference (benchmark/reference/sambay.py) over sequences
several windows long, on the split and on the unified step, with the XLA
attention and the Pallas kernels in interpret mode; a lane given up and
taken again, with overlap on, and a preempted sequence give what a fresh
engine gives; the counters are held against a hand count."""

import asyncio
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.llm.protocols.common import Annotated, LLMEngineOutput
from dynamo_tpu.models.phi4flash import Phi4FlashConfig, init_params
from dynamo_tpu.observability.perf import model_cost
from dynamo_tpu.runtime.engine import Context
from tests.engine.test_jax_engine import request

REF = modules.load(
    Path(__file__).resolve().parents[2] / "benchmark" / "reference" / "sambay.py"
)
WINDOW, BLOCK = 8, 4
HF = {
    "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 8, "num_key_value_heads": 4,
    "vocab_size": 512, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
    "sliding_window": WINDOW, "max_position_embeddings": 256,
    "tie_word_embeddings": True, "mamba_d_state": 4,
}
SEED = 11


@pytest.fixture(scope="module")
def served():
    """The config as the server parses it, and ONE set of weights: the
    recipe's bfloat16 values, served and referred to in float32 so that the
    comparison is of the mathematics."""
    cfg = Phi4FlashConfig.from_hf_config(HF)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32), init_params(cfg, jax.random.PRNGKey(SEED))
    )
    return dataclasses.replace(cfg, dtype=jnp.float32), params, REF.init_weights(HF, SEED)


def make_engine(served, **overrides) -> JaxLlmEngine:
    cfg, params, _ = served
    defaults = dict(
        model=cfg, model_family="phi4flash", num_blocks=64, block_size=BLOCK,
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
    for _ in range(100):
        stats = engine.stats()
        if not stats["kv_active_blocks"] and not stats["num_requests_running"]:
            break
        await asyncio.sleep(0.02)
    return stats


def reference_logprobs(served, prompt, tokens):
    _, _, weights = served
    with jax.default_matmul_precision("highest"):
        rows = range(len(prompt) - 1, len(prompt) + len(tokens) - 1)
        logits = np.asarray(REF.forward(weights, HF, prompt + tokens, rows=list(rows)))
    lsm = logits - np.asarray(jax.nn.logsumexp(logits, axis=-1))[:, None]
    return lsm[np.arange(len(tokens)), tokens], logits.argmax(-1).tolist()


def assert_reference(served, prompt, tokens, lps, n):
    assert len(tokens) == n
    want_lp, want_first = reference_logprobs(served, prompt, tokens)
    assert tokens == want_first
    np.testing.assert_allclose(lps, want_lp, atol=2e-3)


PROMPTS = [
    [int(t) for t in np.random.default_rng(i).integers(2, 500, size=n)]
    for i, n in enumerate((41, 27, 9))
]


def test_the_seeded_draw_is_the_reference_s(served):
    """Program and reference draw the same weights from the seed, leaf for
    leaf (the program's stacked, the reference's flat)."""
    cfg, _, weights = served
    params = init_params(dataclasses.replace(cfg, dtype=jnp.bfloat16), jax.random.PRNGKey(SEED))
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32), np.asarray(weights["embed"], np.float32))
    checked = 0
    for name, leaf in weights.items():
        if name == "embed":
            continue
        head, leaf_name = name.split(".")
        group, layer = head.rstrip("0123456789"), int(head[len(head.rstrip("0123456789")):])
        # (a float32 rule is one jitted draw there, op by op here: an ulp)
        np.testing.assert_allclose(
            np.asarray(params[group][leaf_name][layer], np.float32),
            np.asarray(leaf, np.float32), rtol=1e-6, err_msg=name)
        checked += 1
    assert checked == 3 * 9 + 3 * 9 + 5 + 9     # leaves drawn: ssm, attn, gmu, cross


@pytest.mark.parametrize("unified", [False, True], ids=["split", "unified"])
@pytest.mark.parametrize("attention", ["jax", "pallas_interpret"])
async def test_prefill_then_decode_through_state_and_pools_equals_reference(
    served, unified, attention
):
    """Sequences five windows long, three at a time (admission beside
    running decodes on the unified step: prompt spans and decode lanes in one
    launch): every served token is the reference's first choice and its
    log-probability the reference's."""
    engine = make_engine(served, unified_batch=unified, attention_impl=attention)
    try:
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
        assert_reference(served, prompt, tokens, lps, 14)
    assert stats["num_preemptions_total"] == 0
    if unified:
        assert stats["decode_windows_unified_total"] > 0


async def test_a_lane_taken_again_under_overlap_starts_from_zeros(served):
    """One lane, overlap on: the second sequence takes the lane the first
    gave up (whose last steps in flight may still have written it) and gets
    the logits it gets on a fresh engine, which are the reference's."""
    engine = make_engine(served, max_batch_size=1, decode_overlap=True)
    try:
        first = await collect(engine, request(PROMPTS[1], max_tokens=9, ignore_eos=True))
        second = await collect(engine, request(PROMPTS[2], max_tokens=12, ignore_eos=True))
        dirty = float(jnp.abs(engine.cache["ssm"]).max())
    finally:
        engine.stop()
    assert dirty > 0
    assert_reference(served, PROMPTS[1], *first, 9)
    assert_reference(served, PROMPTS[2], *second, 12)


async def test_preemption_and_recompute_give_the_same_answer(served):
    """A full pool too small for three sequences: one is preempted and
    recomputed from position 0, its state from zeros; every answer is still
    the reference's."""
    engine = make_engine(served, num_blocks=24, max_batch_size=3)
    try:
        results = await asyncio.gather(*(
            collect(engine, request(p, max_tokens=20, ignore_eos=True)) for p in PROMPTS
        ))
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    assert stats["num_preemptions_total"] > 0
    for prompt, (tokens, lps) in zip(PROMPTS, results):
        assert_reference(served, prompt, tokens, lps, 20)


async def test_the_counters_hold_a_hand_count(served):
    """One prompt of 27 tokens and 5 more tokens, alone on the engine, on
    the unified step with the Pallas kernels' bookkeeping: one prompt window
    of 27 rows, then 4 decode rows (the fifth token's row is never run)."""
    cfg = served[0]
    engine = make_engine(served, attention_impl="pallas_interpret", decode_overlap=False)
    try:
        await collect(engine, request(PROMPTS[1], max_tokens=5, ignore_eos=True))
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    cost = model_cost(cfg)
    n, decodes = 27, 4
    assert (cost.ssm_layers, cost.cross_layers) == (3, 1)
    state = 4 * 4 * 128 + 4 * 3 * 128           # a layer's, a lane: float32 state and taps
    assert cost.ssm_state_bytes_per_lane == 3 * state
    assert stats["ssm_rows_total"] == stats["gmu_rows_total"] == n + decodes
    assert stats["ssm_state_bytes_total"] == (
        decodes * 2 * 3 * state + n * 3 * 4 * (4 * 128 + 2 * 4) + 3 * state
    )
    assert stats["ssm_flops_total"] == (n + decodes) * 3 * (7 * 4 * 128 + 2 * 4 * 128)
    # attention: 2 window layers (window 8), layer 5 and ONE cross layer walk
    # pages of 4 tokens: 8 query heads x 16 wide, as the kernels run them
    page = BLOCK * 2 * 2 * 16 * 4               # keys and values of 2 cache heads, float32
    full_pages = sum(-(-c // BLOCK) for c in range(n + 1, n + 1 + decodes))
    window_pages = sum(
        -(-c // BLOCK) - max(c - WINDOW, 0) // BLOCK for c in range(n + 1, n + 1 + decodes))
    assert stats["decode_kv_read_bytes_total"] == (2 * full_pages + 2 * window_pages) * page
    assert stats["decode_cross_kv_read_bytes_total"] == full_pages * page
    ctx = sum(range(n + 1, n + 1 + decodes))
    assert stats["decode_attn_flops_total"] == 4 * 8 * 16 * (2 * ctx + 2 * decodes * WINDOW)
    # the prompt window: the full layer and the cross layer copy the same
    # pages, the two window layers theirs
    prompt_pages = stats["ragged_live_pages_total"]
    assert stats["ragged_cross_kv_read_bytes_total"] == prompt_pages * page
    assert stats["ragged_kv_read_bytes_total"] == (
        2 * prompt_pages + 2 * (stats["window_pages_visited_total"] - window_pages)
    ) * page


def test_what_the_family_cannot_do_is_refused(served):
    from dynamo_tpu.models.registry import get_family

    family = get_family("phi4flash")
    assert family.forward_prefill_with_prefix is None and family.forward_verify is None
    assert family.forward_decode_pp is None and family.load_weights is None
    engine = make_engine(served, enable_prefix_caching=True, prefill_chunk_tokens=16)
    try:
        assert not engine.prefix_caching and engine.chunk_tokens is None
        with pytest.raises(Exception, match="window pool|single"):
            engine.allocator.single_pool_only("KV extraction for a decode worker")
    finally:
        engine.stop()
    with pytest.raises(ValueError, match="verify|speculative"):
        make_engine(served, speculative="ngram")
    for bad in ({"mb_per_layer": 3}, {"num_hidden_layers": 6}, {"mlp_bias": True}):
        with pytest.raises(NotImplementedError):
            Phi4FlashConfig.from_hf_config({**HF, **bad})
