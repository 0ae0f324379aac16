"""The ``xing4_0`` family as ``xing4-29b-l8`` serves it (Xing4.0-29B-A4B: four
residual streams a token, latent attention with a compressed query and YaRN,
64 experts of which 4 a token beside a shared one), at a tiny size on the CPU,
through the engine's normal path.

Served answers (tokens AND returned log-probabilities) are held against the
benchmark's plain reference (benchmark/reference/xing_mhc.py): the unified step
with a prompt span beside running decodes, then decode through the latent
pages, the XLA attention and the Pallas kernels in interpret mode; a prompt in
two windows (the continuation over its own pages); the served dtype.  Then the
residual streams' two counters and the latent family's prices against a hand
count, at the tiny size and at the published widths."""

import asyncio
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.models.deepseek import DeepseekConfig, init_params
from dynamo_tpu.observability.perf import _latent_cost, model_cost
from tests.engine.test_exaone_moe import collect, idle_stats
from tests.engine.test_jax_engine import request
from tests.models.test_xing import HF

ROOT = Path(__file__).resolve().parents[2]
REF = modules.load(ROOT / "benchmark" / "reference" / "xing_mhc.py")
BLOCK = 4
SEED = 13
LAYERS, SPARSE, K, STREAMS, HIDDEN = 5, 3, 2, 4, 256
PROMPTS = [
    [int(t) for t in np.random.default_rng(i).integers(2, 500, size=n)]
    for i, n in enumerate((41, 27, 9))
]


def _served(dtype):
    """The config as the server parses it and ONE set of weights: the
    recipe's values (bfloat16 matrices, float32 mixing leaves), the matrices
    served in ``dtype``."""
    cfg = DeepseekConfig.from_hf_config(HF)
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    params = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, params)
    return dataclasses.replace(cfg, dtype=dtype), params, REF.init_weights(HF, SEED)


@pytest.fixture(scope="module")
def served():
    return _served(jnp.float32)


def make_engine(served, **overrides) -> JaxLlmEngine:
    cfg, params, _ = served
    defaults = dict(
        model=cfg, model_family="xing4_0", num_blocks=64, block_size=BLOCK,
        max_batch_size=4, prefill_buckets=(16, 32, 64), max_model_len=96,
        unified_batch=True, enable_prefix_caching=False,
    )
    defaults.update(overrides)
    engine = JaxLlmEngine(EngineConfig(**defaults), params=params)
    engine.start()
    return engine


def reference_logprobs(served, prompt, tokens):
    weights = served[2]
    rows = list(range(len(prompt) - 1, len(prompt) + len(tokens) - 1))
    logits = np.asarray(REF.forward(weights, HF, prompt + tokens, rows=rows))
    lsm = logits - np.asarray(jax.nn.logsumexp(logits, axis=-1))[:, None]
    return lsm[np.arange(len(tokens)), tokens], logits.argmax(-1).tolist(), logits


async def serve_staggered(engine, prompts, max_tokens=12):
    tasks = []
    for prompt in prompts:
        tasks.append(asyncio.ensure_future(
            collect(engine, request(prompt, max_tokens=max_tokens, ignore_eos=True))))
        await asyncio.sleep(0.05)
    return await asyncio.gather(*tasks)


@pytest.mark.parametrize("attention", ["jax", "pallas_interpret"])
async def test_unified_then_decode_through_the_streams_equals_reference(served, attention):
    """float32: three sequences admitted beside running decodes, then decode
    through the cache: every served token is the reference's first choice and
    its log-probability the reference's (3e-4: the sum orders of absorbed
    against decompressed attention through five layers, as for the family
    without streams)."""
    engine = make_engine(served, attention_impl=attention)
    try:
        results = await serve_staggered(engine, PROMPTS)
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    for prompt, (tokens, lps) in zip(PROMPTS, results):
        assert len(tokens) == 12
        want_lp, want_first, _ = reference_logprobs(served, prompt, tokens)
        assert tokens == want_first
        np.testing.assert_allclose(lps, want_lp, atol=3e-4)
    assert stats["decode_windows_unified_total"] > 0
    assert stats["unified_fallbacks"] == {}
    assert stats["moe_assignments_held_total"] == stats["moe_assignments_routed_total"] > 0
    assert stats["moe_expert_layers_total"] % SPARSE == 0


async def test_bfloat16_serving_stays_within_its_roundings_of_the_reference():
    """bfloat16, as the cell serves it: the streams are rounded to 8 bits of
    mantissa after every sublayer where the reference keeps float32 (the
    coefficients are float32 on both sides).  A served token's
    log-probability lies within 0.12 of the reference's on average (read:
    0.07; float32: 3e-4) and the token within 0.35 of a logit spread of the
    reference's best for all but one in twelve (read: 1 of 36, at 1.2
    spreads, its log-probability off by as much: a near-tied selection among
    the experts fell the other way, and through the streams' order-one
    coefficients that token's state is another; every other token reads
    0-0.15.  The family without streams is held to 0.35 on every token; the
    cell's own limits are from chip readings)."""
    served = _served(jnp.bfloat16)
    engine = make_engine(served)
    try:
        results = await serve_staggered(engine, PROMPTS)
    finally:
        engine.stop()
    errs, gaps = [], []
    for prompt, (tokens, lps) in zip(PROMPTS, results):
        want_lp, _, logits = reference_logprobs(served, prompt, tokens)
        gaps += ((logits.max(-1) - logits[np.arange(len(tokens)), tokens]) / logits.std(-1)).tolist()
        errs += np.abs(np.asarray(lps) - want_lp).tolist()
    assert np.mean(errs) < 0.12
    assert np.mean(np.asarray(gaps) > 0.35) <= 1 / 12


async def test_a_prompt_served_in_two_windows_equals_one_window(served):
    """Chunked prefill: the 41-token prompt in windows of 16 tokens (each a
    continuation over its own latent pages, the streams starting again from
    the embedding for every token) answers as in one window."""
    whole = make_engine(served)
    try:
        one, lps_one = await collect(whole, request(PROMPTS[0], max_tokens=8, ignore_eos=True))
    finally:
        whole.stop()
    chunked = make_engine(served, prefill_chunk_tokens=16)
    try:
        two, lps_two = await collect(chunked, request(PROMPTS[0], max_tokens=8, ignore_eos=True))
        stats = await idle_stats(chunked)
    finally:
        chunked.stop()
    assert stats["decode_windows_unified_total"] >= 3
    assert one == two
    np.testing.assert_allclose(lps_one, lps_two, atol=2e-4)


async def test_the_streams_counters_read_zero_from_the_start_and_equal_a_hand_count(served):
    """``mhc_rows_total`` and ``mhc_stream_bytes_total`` are in ``stats()``
    before a request, at zero; then ONE mixed window (a 27-token prompt
    beside a running decode) and the decode steps around it: every live row
    passes two sublayers in each of five layers, and a perfect mixing moves
    for each the four streams in and out and the sublayer's input and output
    (float32 here: (2 x 4 + 2) x 256 x 4 B)."""
    engine = make_engine(served, max_batch_size=2, decode_overlap=False)
    try:
        zero = engine.stats()
        assert zero["mhc_rows_total"] == zero["mhc_stream_bytes_total"] == 0
        first = asyncio.ensure_future(
            collect(engine, request(PROMPTS[0], max_tokens=6, ignore_eos=True)))
        while engine.stats()["decode_tokens_total"] < 1:
            await asyncio.sleep(0.01)
        await collect(engine, request(PROMPTS[1], max_tokens=2, ignore_eos=True))
        await first
        # both prompts' rows, and one row for every token served after a prompt's first
        rows = 41 + 27 + 5 + 1
        stats = await idle_stats(engine)
        for _ in range(100):    # the last step is booked when its iteration ends
            if stats["prefill_tokens_total"] + stats["decode_tokens_total"] == rows:
                break
            await asyncio.sleep(0.02)
            stats = engine.stats()
    finally:
        engine.stop()
    assert stats["decode_windows_unified_total"] >= 1
    assert stats["prefill_tokens_total"] + stats["decode_tokens_total"] == rows
    assert stats["mhc_rows_total"] == rows * 2 * LAYERS
    per_row = (2 * STREAMS + 2) * HIDDEN * 4
    assert stats["mhc_stream_bytes_total"] == stats["mhc_rows_total"] * per_row
    # the expert layers saw the same rows, k assignments each, all held
    routed = stats["moe_assignments_routed_total"]
    assert 0 < routed <= SPARSE * K * rows and routed % (SPARSE * K) == 0
    assert stats["moe_assignments_held_total"] == routed
    assert stats["moe_gmm_flops_total"] == 2 * 3 * HIDDEN * 48 * routed


async def test_a_model_of_one_stream_keeps_no_such_counter():
    from tests.engine.test_moonlight import _served as moonlight, make_engine as moonlight_engine

    engine = moonlight_engine(moonlight(jnp.float32))
    try:
        stats = engine.stats()
    finally:
        engine.stop()
    assert "mhc_rows_total" not in stats and "mhc_stream_bytes_total" not in stats


def test_the_prices_at_the_published_widths_equal_a_hand_count():
    """``observability/perf.py`` at 32 heads on one latent, a compressed
    query, 4 of 64 experts and four streams of 3,584, by itself: a (query,
    key) pair 2 x 32 x (512 + 64) + 2 x 32 x 512 operations a layer, the page
    row 512 + 128 stored values, attention's matrices 28,409,856 (its two
    inner norms are no product), the MLP averaged over 2 dense and 6 sparse
    layers, ``phi`` 2 x 14,336 x 24 a layer, and 71,680 B a row and sublayer
    for a perfect mixing in bfloat16."""
    hf = json.loads((ROOT / "benchmark" / "configs" / "xing4-29b-l8.json").read_text())
    cfg = DeepseekConfig.from_hf_config(hf)
    latent = _latent_cost(cfg)
    assert latent["attn_flops_per_ctx_token"] == 2 * 32 * 576 + 2 * 32 * 512 == 69_632
    assert latent["page_row"] == 640
    assert latent["attn_params"] == (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
                                     + 4096 * 3584) == 28_409_856
    expert, dense = 3 * 3584 * 1024, 3 * 3584 * 9216
    always = 3584 * 64 + expert
    assert latent["mlp_params"] == ((2 * dense + 6 * (always + 64 * expert)) // 8,
                                    (2 * dense + 6 * (always + 4 * expert)) // 8)
    cost = model_cost(cfg)
    assert cost.mhc_sublayers == 16 and cost.mhc_stream_bytes_per_row == 71_680 == 2 * 28_672 + 2 * 7_168
    phi = 2 * 14_336 * 24
    assert cost.linear_flops_per_token == 2 * (
        131_072 * 3584 + 8 * (28_409_856 + phi) + 2 * dense + 6 * (always + 4 * expert))
    # every parameter but the norms and the selection bias, the mixing leaves at 4 B
    shapes = modules.load(ROOT / "benchmark" / "xing_mhc_shapes.py")
    unpriced = 8 * (2 * 3584 + 768 + 512) + 3584 + 6 * 64
    assert cost.param_count == shapes.total_params(hf) - unpriced
    assert cost.weight_bytes == 2 * cost.param_count + 2 * 8 * (phi + 2 * 27)
    assert cost.kv_bytes_per_token == shapes.kv_bytes_per_token(hf) == 10_240
    # the family without streams is priced as it was
    plain = model_cost(dataclasses.replace(cfg, hc_mult=1))
    assert plain.mhc_sublayers == plain.mhc_stream_bytes_per_row == 0
    assert plain.param_count == cost.param_count - 8 * (phi + 2 * 27)
    assert plain.weight_bytes == 2 * plain.param_count
