"""The step programs' token buckets above the last configured one: half-octave
steps up to the context (``engine._token_buckets``), so a prompt over 4,096
tokens pads by at most a third of its rows and not up to the context.

The rule, list for list; then, on a tiny engine of context 8,192 in each of
three families, a 5,000-token prompt beside a running answer: it is booked to
the 6,144 bucket, nothing compiles when it arrives after warm-up, and its
greedy tokens and log-probabilities are those of the same prompt served at
8,192 rows by an engine given the list as it was before the rule; and the two
counters of ``stats()`` that say how well the buckets fit, against a hand
count of one mixed window."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.engine.engine import _token_buckets
from dynamo_tpu.models import deepseek, exaone_moe, llama
from dynamo_tpu.utils.compile_cache import compile_counts
from tests.engine import test_exaone_moe, test_moonlight
from tests.engine.test_exaone_moe import collect, idle_stats
from tests.engine.test_jax_engine import request

DEFAULT = EngineConfig.__dataclass_fields__["prefill_buckets"].default
TO_4096 = [32, 64, 128, 256, 512, 1024, 2048, 4096]


@pytest.mark.parametrize("configured,context,want", [
    (DEFAULT, 2048, TO_4096[:-1]),
    (DEFAULT, 4096, TO_4096),
    (DEFAULT, 8192, TO_4096 + [6144, 8192]),
    (DEFAULT, 10000, TO_4096 + [6144, 8192, 10000]),
    (DEFAULT, 32768, TO_4096 + [6144, 8192, 12288, 16384, 24576, 32768]),
    # a caller's own list: continued from ITS last entry, untouched where it
    # reaches the context, clipped where it passes it
    ((16,), 96, [16, 24, 32, 48, 64, 96]),
    ((16, 32, 64), 64, [16, 32, 64]),
    ((32, 4096, 8192), 8192, [32, 4096, 8192]),
    ((128,), 32, [32]),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, (tuple, list)) else str(v))
def test_buckets_go_on_in_half_octaves_above_the_last_configured(configured, context, want):
    assert _token_buckets(configured, context) == want
    if context <= 4096 and configured is DEFAULT:
        # the list an engine of such a context built before the rule
        old = sorted({min(b, context) for b in configured})
        assert want == old + ([context] if old[-1] < context else [])
    top = max(min(b, context) for b in configured)
    # above the last configured bucket a window pads by at most a third
    assert all(b <= 1.5 * a for a, b in zip(want, want[1:]) if a >= top)


BLOCK = 16
SHORT = [int(t) for t in np.random.default_rng(46).integers(2, 500, size=20)]


# One layer of each kind and one narrow KV head a family: the XLA attention
# the CPU runs gathers a page view a token, and a 6,144-row window over 8,192
# positions costs in proportion to layers x the page's width.
def _llama(context):
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), num_layers=1, num_heads=2, num_kv_heads=1, head_dim=8,
        max_position_embeddings=context)
    return "llama", cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


def _exaone_moe(context):
    hf = {**test_exaone_moe.HF, "max_position_embeddings": context,
          "num_hidden_layers": 2, "layer_types": ["sliding_attention", "full_attention"],
          "mlp_layer_types": ["dense", "sparse"], "num_attention_heads": 2,
          "num_key_value_heads": 1, "head_dim": 8}
    cfg = dataclasses.replace(exaone_moe.ExaoneMoeConfig.from_hf_config(hf), dtype=jnp.float32)
    return "exaone_moe", cfg, exaone_moe.init_params(cfg, jax.random.PRNGKey(3))


def _deepseek_v3(context):
    hf = {**test_moonlight.HF, "max_position_embeddings": context, "num_hidden_layers": 2}
    cfg = dataclasses.replace(deepseek.DeepseekConfig.from_hf_config(hf), dtype=jnp.float32)
    return "deepseek_v3", cfg, deepseek.init_params(cfg, jax.random.PRNGKey(3))


def _engine(family, cfg, params, buckets, context) -> JaxLlmEngine:
    engine = JaxLlmEngine(EngineConfig(
        model=cfg, model_family=family, num_blocks=context // BLOCK + 32, block_size=BLOCK,
        max_batch_size=2, prefill_buckets=buckets, max_model_len=context,
        unified_batch=True, enable_prefix_caching=False,
    ), params=params)
    engine.start()
    return engine


async def _long_beside_a_running_answer(engine, long):
    """The long prompt admitted while a short one decodes: its tokens and
    log-probabilities, and what the counters moved by."""
    before = engine.stats()
    first = asyncio.ensure_future(
        collect(engine, request(SHORT, max_tokens=40, ignore_eos=True)))
    while engine.stats()["decode_tokens_total"] <= before["decode_tokens_total"]:
        await asyncio.sleep(0.01)
    tokens, lps = await collect(engine, request(long, max_tokens=6, ignore_eos=True))
    await first
    after = await idle_stats(engine)
    moved = {key: after[key] - before[key] for key in (
        "prompt_window_live_tokens_total", "prompt_window_bucket_tokens_total",
        "decode_windows_unified_total")}
    return tokens, lps, moved


# The latent family at an eighth of every length (context 1,024, a 625-token
# prompt, the 768 bucket between 512 and 1,024): its page keeps the rotated
# key 128 wide whatever the model, so the XLA attention's view of 64 tokens'
# pages is 268 MB at 8,192 positions and one window takes 20 s on the CPU.
# Its expert layer still walks more than one chunk (768 x 3 rows of 2,048).
@pytest.mark.parametrize("family,scale", [(_llama, 1), (_exaone_moe, 1), (_deepseek_v3, 8)])
async def test_a_5000_token_prompt_runs_the_6144_bucket_and_answers_as_at_8192(family, scale):
    context, last, between = 8192 // scale, 4096 // scale, 6144 // scale
    long = [int(t) for t in np.random.default_rng(45).integers(2, 500, size=5000 // scale)]
    name, cfg, params = family(context)
    engine = _engine(name, cfg, params, (32, last), context)
    try:
        assert engine.buckets == [32, last, between, context]
        assert engine._table_len(len(long) // BLOCK + 1) == between // BLOCK
        await engine.warmup()
        assert {("unified", between), ("prefill", between)} <= set(engine.program_temp_bytes)
        compiled = compile_counts()["compiles_total"]
        tokens, lps, moved = await _long_beside_a_running_answer(engine, long)
        assert compile_counts()["compiles_total"] == compiled
    finally:
        engine.stop()
    # the short prompt's window, then the long prompt beside the one lane
    assert moved["decode_windows_unified_total"] == 2
    assert moved["prompt_window_live_tokens_total"] == len(SHORT) + len(long) + 1
    assert moved["prompt_window_bucket_tokens_total"] == 32 + between

    # the list as it was before the rule: the context closes it
    old = _engine(name, cfg, params, (32, last, context), context)
    try:
        assert old.buckets == [32, last, context]
        want_tokens, want_lps, whole = await _long_beside_a_running_answer(old, long)
    finally:
        old.stop()
    assert whole["prompt_window_bucket_tokens_total"] == 32 + context
    assert len(tokens) == 6 and tokens == want_tokens
    np.testing.assert_allclose(lps, want_lps, atol=1e-5)


async def test_the_fill_counters_equal_a_hand_count_of_one_mixed_window():
    """Zero before a request; a 9-token prompt alone in the 16 bucket; then
    a 27-token prompt beside that lane's decode: 28 live tokens in the 32
    bucket.  A prompt-only step (the split path) books its own bucket."""
    _, cfg, params = _llama(96)
    prompts = test_moonlight.PROMPTS
    for unified, want in ((True, (9 + 27 + 1, 16 + 32)), (False, (9 + 27, 16 + 32))):
        engine = JaxLlmEngine(EngineConfig(
            model=cfg, num_blocks=64, block_size=4, max_batch_size=2,
            prefill_buckets=(16, 32, 64), max_model_len=96, unified_batch=unified,
            enable_prefix_caching=False, decode_overlap=False,
        ), params=params)
        engine.start()
        try:
            zero = engine.stats()
            assert zero["prompt_window_live_tokens_total"] == 0
            assert zero["prompt_window_bucket_tokens_total"] == 0
            first = asyncio.ensure_future(
                collect(engine, request(prompts[2], max_tokens=30, ignore_eos=True)))
            while engine.stats()["decode_tokens_total"] < 1:
                await asyncio.sleep(0.01)
            await collect(engine, request(prompts[1], max_tokens=2, ignore_eos=True))
            await first
            stats = await idle_stats(engine)
        finally:
            engine.stop()
        got = (stats["prompt_window_live_tokens_total"],
               stats["prompt_window_bucket_tokens_total"])
        assert got == want, (unified, got)
