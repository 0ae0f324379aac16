"""The deepseek_v3 family as ``moonlight-16b-l9`` serves it (Moonlight-16B-A3B:
direct q, sigmoid routing with a selection bias, 2 shared experts, a leading
dense layer), at a tiny size on the CPU.

Served answers (tokens AND returned log-probabilities) are held against the
benchmark's plain reference (benchmark/reference/deepseek_mla.py: latent
attention DECOMPRESSED, every product at ``highest``) through the engine's
normal path: the unified step with a prompt span beside running decodes, then
decode through the latent pages, the XLA attention and the Pallas kernels in
interpret mode.  The program attends absorbed; that the two agree is the
test.  Then: a prompt served in two windows, a bias that changes the
selection but not the weights, the counters against a hand count of one
mixed window, and the lowered step programs' treatment of the cache."""

import asyncio
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from benchmark import modules
from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.engine.engine import KERNEL_WORK_KEYS, MOE_STAT_KEYS
from dynamo_tpu.models import deepseek
from dynamo_tpu.models.deepseek import DeepseekConfig, init_params
from dynamo_tpu.ops.pallas.ragged_attention import kv_step_pages
from tests.engine.test_exaone_moe import collect, idle_stats
from tests.engine.test_jax_engine import request

REF = modules.load(
    Path(__file__).resolve().parents[2] / "benchmark" / "reference" / "deepseek_mla.py"
)
BLOCK = 4
HF = {
    "model_type": "deepseek_v3", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 50000,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
}
SEED = 13
LAYERS, SPARSE, HEADS, K = 3, 2, 4, 3
PROMPTS = [
    [int(t) for t in np.random.default_rng(i).integers(2, 500, size=n)]
    for i, n in enumerate((41, 27, 9))
]


def _served(dtype, hf=HF, bias_scale=1.0):
    """The config as the server parses it and ONE set of weights: the
    recipe's bfloat16 values, served in ``dtype``."""
    cfg = dataclasses.replace(DeepseekConfig.from_hf_config(hf), dtype=jnp.bfloat16)
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    weights = REF.init_weights(hf, SEED)
    if bias_scale != 1.0:
        params["moe_layers"]["router_bias"] = params["moe_layers"]["router_bias"] * bias_scale
        weights = {k: v * bias_scale if k.endswith("router_bias") else v for k, v in weights.items()}
    params = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, params)
    return dataclasses.replace(cfg, dtype=dtype), params, weights, hf


@pytest.fixture(scope="module")
def served():
    return _served(jnp.float32)


def make_engine(served, **overrides) -> JaxLlmEngine:
    cfg, params, _, _ = served
    defaults = dict(
        model=cfg, model_family="deepseek_v3", num_blocks=64, block_size=BLOCK,
        max_batch_size=4, prefill_buckets=(16, 32, 64), max_model_len=96,
        unified_batch=True, enable_prefix_caching=False,
    )
    defaults.update(overrides)
    engine = JaxLlmEngine(EngineConfig(**defaults), params=params)
    engine.start()
    return engine


def reference_logprobs(served, prompt, tokens):
    """The reference's full forward over prompt + served tokens: its
    log-probability of each served token, and its own first choice there."""
    _, _, weights, hf = served
    rows = list(range(len(prompt) - 1, len(prompt) + len(tokens) - 1))
    logits = np.asarray(REF.forward(weights, hf, prompt + tokens, rows=rows))
    lsm = logits - np.asarray(jax.nn.logsumexp(logits, axis=-1))[:, None]
    return lsm[np.arange(len(tokens)), tokens], logits.argmax(-1).tolist(), logits


async def serve_staggered(engine, prompts, max_tokens=14):
    tasks = []
    for prompt in prompts:
        tasks.append(asyncio.ensure_future(
            collect(engine, request(prompt, max_tokens=max_tokens, ignore_eos=True))))
        await asyncio.sleep(0.05)
    return await asyncio.gather(*tasks)


def test_the_recipe_is_the_references(served):
    """The reference draws what the program draws, leaf for leaf, and takes
    nothing the program made."""
    _, params, weights, _ = served
    for group, name in (("dense_layers", "dense"), ("moe_layers", "sparse")):
        for leaf, stack in params[group].items():
            if leaf.endswith("norm"):
                assert bool(jnp.all(stack == 1))
                continue
            for layer in range(stack.shape[0]):
                mine = weights[f"{name}{layer}.{leaf}"].astype(jnp.float32)
                assert bool(jnp.all(stack[layer].astype(jnp.float32) == mine)), (group, leaf)
    assert bool(jnp.all(params["embed"] == weights["embed"].astype(jnp.float32)))
    assert bool(jnp.all(params["lm_head"] == weights["lm_head"].astype(jnp.float32)))
    assert params["moe_layers"]["router_bias"].dtype == jnp.float32
    assert 0 < float(jnp.abs(params["moe_layers"]["router_bias"]).max()) < 0.1


@pytest.mark.parametrize("attention", ["jax", "pallas_interpret"])
async def test_unified_then_decode_through_latent_pages_equals_reference(served, attention):
    """(i) float32: three sequences admitted beside running decodes, then
    decode through the cache: every served token is the decompressed
    reference's first choice and its log-probability the reference's."""
    engine = make_engine(served, attention_impl=attention)
    try:
        results = await serve_staggered(engine, PROMPTS)
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    for prompt, (tokens, lps) in zip(PROMPTS, results):
        assert len(tokens) == 14
        want_lp, want_first, _ = reference_logprobs(served, prompt, tokens)
        assert tokens == want_first
        np.testing.assert_allclose(lps, want_lp, atol=3e-4)
    assert stats["decode_windows_unified_total"] > 0
    assert stats["unified_fallbacks"] == {}
    assert stats["moe_assignments_held_total"] == stats["moe_assignments_routed_total"] > 0
    assert stats["moe_expert_layers_total"] % SPARSE == 0


@pytest.mark.parametrize("attention", ["jax", "pallas_interpret"])
async def test_bfloat16_serving_stays_within_its_roundings_of_the_reference(attention):
    """(i) bfloat16, as the cell serves it: activations, absorbed queries,
    probabilities and the latent pages are all rounded to 8 bits of mantissa
    where the reference keeps float32, through 3 layers; near-ties of the
    random logits then fall either way, so the served token is held to lie
    within 0.35 of a logit spread under the reference's best (the float32
    serving reads 0) and its log-probability within 0.12 of the reference's
    on average (float32: 3e-4; the cell's own limits are set from chip
    readings, PERF.md section 2)."""
    served = _served(jnp.bfloat16)
    engine = make_engine(served, attention_impl=attention)
    try:
        results = await serve_staggered(engine, PROMPTS)
    finally:
        engine.stop()
    errs = []
    for prompt, (tokens, lps) in zip(PROMPTS, results):
        want_lp, _, logits = reference_logprobs(served, prompt, tokens)
        gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]) / logits.std(-1)
        assert gap.max() < 0.35
        errs += np.abs(np.asarray(lps) - want_lp).tolist()
    assert np.mean(errs) < 0.12


async def test_a_prompt_served_in_two_windows_equals_one_window(served):
    """(ii) chunked prefill: the 41-token prompt in windows of 16 tokens
    (continuation over its own latent pages) answers as in one window."""
    whole = make_engine(served)
    try:
        one, lps_one = await collect(whole, request(PROMPTS[0], max_tokens=10, ignore_eos=True))
    finally:
        whole.stop()
    chunked = make_engine(served, prefill_chunk_tokens=16)
    try:
        two, lps_two = await collect(chunked, request(PROMPTS[0], max_tokens=10, ignore_eos=True))
        stats = await idle_stats(chunked)
    finally:
        chunked.stop()
    assert chunked.chunk_tokens == 16
    # 41 tokens in windows of 16: three prompt windows where the whole made one
    assert stats["decode_windows_unified_total"] >= 3
    assert one == two
    np.testing.assert_allclose(lps_one, lps_two, atol=2e-4)


def test_absorbed_equals_decompressed_under_a_bias_that_picks_other_experts():
    """(iii) ``routed_scaling_factor`` 2.446 and a selection bias 200 times
    the recipe's: the bias moves the selection (other experts than without
    it) and never the weights (the reference's sum, which takes the scores
    unbiased), and the absorbed step programs still agree with the
    decompressed reference."""
    served = _served(jnp.float32, bias_scale=200.0)
    cfg, params, weights, hf = served
    plain = _served(jnp.float32)
    ids = PROMPTS[0]

    def chosen(w):
        x = REF.hidden({k: v for k, v in w.items()}, dict(hf, num_hidden_layers=1), ids)
        c = REF.dims(hf)
        cos = jnp.ones((len(ids), c["rope"] // 2))
        _, _, picked, g = REF._route(x, {k[len("sparse0."):]: v for k, v in w.items()
                                         if k.startswith("sparse0.")}, cos, 0 * cos, c)
        return np.sort(np.asarray(picked), -1), np.asarray(g)

    picked, g = chosen(weights)
    picked_plain, _ = chosen(plain[2])
    assert (picked != picked_plain).any()
    np.testing.assert_allclose(g.sum(-1), 2.446, rtol=1e-5)

    cos, sin = deepseek.make_rope_tables(cfg)
    cache = deepseek.init_kv_cache(cfg, 16, BLOCK)
    blocks = jnp.arange(11, dtype=jnp.int32)
    logits, _ = deepseek.deepseek_forward_prefill(
        params, cfg, jnp.asarray(ids, jnp.int32), cache, blocks, jnp.int32(len(ids)),
        jnp.int32(0), cos, sin)
    want = np.asarray(REF.forward(weights, hf, ids, rows=[len(ids) - 1]))[0]
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-3 * want.std())


async def test_counters_read_zero_from_the_start_and_equal_a_hand_count(served):
    """(iv) every ``moe_*`` and attention counter is in ``stats()`` before a
    request, at zero; then ONE mixed window (a 27-token prompt beside a
    running decode at context 42) and the decode steps around it, counted by
    hand from the latent kernels' own arithmetic."""
    # (no step dispatched ahead of its tokens: every launch is a served one)
    engine = make_engine(
        served, attention_impl="pallas_interpret", max_batch_size=2, decode_overlap=False)
    try:
        zero = engine.stats()
        for key in (*KERNEL_WORK_KEYS, *MOE_STAT_KEYS, "moe_gmm_flops_total", "moe_gmm_bytes_total"):
            assert zero[key] == 0, key
        first = asyncio.ensure_future(
            collect(engine, request(PROMPTS[0], max_tokens=6, ignore_eos=True)))
        while engine.stats()["decode_tokens_total"] < 1:
            await asyncio.sleep(0.01)
        await collect(engine, request(PROMPTS[1], max_tokens=2, ignore_eos=True))
        await first
        stats = await idle_stats(engine)
    finally:
        engine.stop()
    r, rope, stored = 32, 8, 32 + 128          # the page row: latent + the key's tile
    nope = v_dim = 16
    pair = 2 * HEADS * (r + rope) + 2 * HEADS * r
    # a key that is a row of the same window: decompressed, every head's own
    own_pair = 2 * HEADS * (nope + rope) + 2 * HEADS * v_dim
    tri = lambda a, b: (b * (b + 1) - a * (a + 1)) // 2  # noqa: E731
    # ragged launches: prompt 0 alone, then prompt 1 beside lane 0's decode;
    # decode launches: whatever ran with no prompt in the window
    windows = stats["decode_windows_unified_total"]
    assert windows >= 1
    # the unified steps' own keys: both prompts whole (no resident part), and
    # the decode row that rode beside prompt 1 (if lane 0 still ran), itself
    own_ctx, attended_ctx = stats["mla_window_ctx_total"], stats["mla_attended_ctx_total"]
    rode = own_ctx - tri(0, 41) - tri(0, 27)
    assert rode in (0, 1)
    resident_flops = stats["ragged_attn_flops_total"] - LAYERS * own_pair * own_ctx
    resident_ctx = resident_flops // (LAYERS * pair)
    assert resident_flops == LAYERS * pair * resident_ctx
    assert own_ctx + resident_ctx == attended_ctx
    assert resident_ctx in ([0] if not rode else range(41, 46))   # that row's context
    decode_ctx = stats["decode_attn_flops_total"] // (LAYERS * pair)
    # every attended (query, key) pair is in one launch or another: both
    # prompts' triangles and each decode token's context
    served_ctx = tri(0, 41) + tri(0, 27) + sum(41 + i for i in range(1, 6)) + 28
    assert attended_ctx + decode_ctx == served_ctx
    page_bytes = BLOCK * stored * 4             # float32 pages here
    # the pages the walk copied (the decode row's context, nothing of a whole
    # prompt) and the window launch's q, k, v and output once a row
    own_row = 4 * (HEADS * (2 * nope + rope + 2 * v_dim) + rope)
    assert stats["ragged_kv_read_bytes_total"] == LAYERS * (
        page_bytes * stats["ragged_live_pages_total"] + own_row * (41 + 27 + rode))
    assert stats["ragged_live_pages_total"] == -(-(resident_ctx) // BLOCK)
    assert stats["ragged_page_slots_total"] == stats["ragged_kv_steps_total"] * kv_step_pages(BLOCK)
    # the expert layers: every token of every step, k assignments each, all held
    tokens = 41 + 27 + 5 + 1
    # (the device's counters are taken a step or two behind: what has come)
    routed = stats["moe_assignments_routed_total"]
    assert 0 < routed <= SPARSE * K * tokens and routed % (SPARSE * K) == 0
    assert stats["moe_assignments_held_total"] == routed
    assert stats["moe_gmm_flops_total"] == 2 * 3 * 64 * 32 * routed
    assert stats["moe_gmm_bytes_total"] == 4 * (
        stats["moe_experts_touched_total"] * 3 * 64 * 32 + routed * 3 * (64 + 32))
    assert stats["moe_rows_walked_total"] >= stats["moe_assignments_held_total"]
    # every expert of the router is held: one gather summed every held row
    assert stats["moe_rows_gathered_total"] == stats["moe_assignments_held_total"]
    assert stats["moe_experts_touched_total"] <= 8 * stats["moe_expert_layers_total"]


def _step_program(program, cfg, *, attention, blocks=32, lanes=2, tokens=16):
    """``(function, its abstract arguments after the parameters, the cache)``
    of a step forward at a tiny size."""
    cache = jax.eval_shape(lambda: deepseek.init_kv_cache(cfg, blocks, BLOCK))
    cos, sin = deepseek.make_rope_tables(cfg)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    tables = i32(lanes, 8)
    if program == "decode":
        fn = lambda p, c, tok, bt, cl, sl: deepseek.deepseek_forward_decode(  # noqa: E731
            p, cfg, tok, c, bt, cl, sl, cos, sin, attention=attention)
        return fn, (i32(lanes), tables, i32(lanes), i32(lanes)), cache
    tb = 4
    fn = lambda p, c, tok, bt, cl, pos, slot, lane, sl, sf, sc, steps, rows: (  # noqa: E731
        deepseek.deepseek_forward_unified(
            p, cfg, tok, c, bt, cl, pos, slot, lane, sl, sf, sc, steps, rows, cos, sin,
            attention=attention, tb_tokens=tb))
    args = (i32(tokens), tables, i32(lanes), *(i32(tokens) for _ in range(6)),
            i32(tokens // tb), i32(lanes))
    return fn, args, cache


def _equations(jaxpr):
    """Every equation of a traced program, the kernels' own bodies left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax_core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_step_programs_hold_no_pad_relayout_or_copy_of_the_latent_cache(served, program):
    """(v) in the traced step program (as the chip runs it: the Pallas
    kernels, not their interpreter) an array as large as a cache leaf is only
    ever made by a reshape (the flat pages' view and back: leading axes
    merged), by the scatter that writes a step's latents, or carried by the
    layer loop: no pad, slice, concatenate, transpose, gather, convert or
    copy of it; each kernel launch that reads the cache takes the two flat
    leaves whole (the unified step's window launch reads none of it: the
    rows' own keys, from the activations); and the compiled program (kernels
    interpreted) aliases both leaves to its outputs."""
    cfg, params, _, _ = served
    fn, args, cache = _step_program(program, cfg, attention="pallas")
    sizes = {int(np.prod(cache[k].shape)): k for k in ("k", "v")}
    jaxpr = jax.make_jaxpr(fn)(params, cache, *args)
    makers, launches, off_cache = set(), 0, 0
    pages = [(shape[0] * shape[1], *shape[2:]) for shape in (cache[k].shape for k in ("k", "v"))]
    for eqn in _equations(jaxpr.jaxpr):
        if any(int(np.prod(v.aval.shape)) in sizes for v in eqn.outvars):
            makers.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            flat = {tuple(v.aval.shape) for v in eqn.invars}
            if not any(int(np.prod(shape)) in sizes for shape in flat):
                off_cache += 1
                continue
            launches += 1
            assert all(page in flat for page in pages), flat
    assert launches == 2     # one a run of layers (dense, sparse), inside its scan
    assert off_cache == (2 if program == "unified" else 0)    # the window's own keys
    assert makers <= {"reshape", "scatter", "scan", "while", "pjit"}, makers
    fn, args, cache = _step_program(program, cfg, attention="pallas_interpret")
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, *args).compile()
    pages = sum(int(np.prod(cache[k].shape)) * cache[k].dtype.itemsize for k in ("k", "v"))
    assert compiled.memory_analysis().alias_size_in_bytes >= pages


def _watch_windows(engine, monkeypatch):
    """Record, for every unified window the engine dispatches: its spans
    ``(start, end)``, the positions ``pack_spans`` was handed beside the rows'
    lanes, and what the window added to the kernels' counters."""
    import dynamo_tpu.ops.pallas as kernels

    seen, packed = [], []
    run, pack = engine._run_unified, kernels.pack_spans

    def spy_pack(token_lane, token_pos, **kw):
        packed.append((np.array(token_lane), np.array(token_pos)))
        return pack(token_lane, token_pos, **kw)

    def spy_run(spans, decodes, bucket, overlap):
        before = dict(engine._kernel_work)
        done = run(spans, decodes, bucket, overlap)
        seen.append({
            "spans": [(a, b) for _, a, b in spans], "decodes": len(decodes),
            "packed": packed[-1],
            "work": {k: v - before[k] for k, v in engine._kernel_work.items()},
        })
        return done

    monkeypatch.setattr(kernels, "pack_spans", spy_pack)
    engine._run_unified = spy_run
    return seen


async def test_chunked_prompts_serve_the_same_tokens_on_both_routes_and_count_their_windows(
        served, monkeypatch):
    """(vi) the 41-token prompt in windows of 16, then the 27-token one beside
    its decode: the Pallas route (window keys decompressed, resident pages
    absorbed, one softmax) serves the tokens and log-probabilities of the XLA
    route (absorbed in one piece).  Each window's counters by hand:
    ``mla_attended_ctx_total`` is ``_attended_ctx`` over the window's spans
    plus its decode rows' contexts, ``mla_window_ctx_total`` the part that lay
    in the window (a span's own triangle, a decode row itself), and
    ``ragged_attn_flops_total`` the two rates' sum; the walk was handed each
    lane's last RESIDENT position."""
    results = {}
    for attention in ("jax", "pallas_interpret"):
        engine = make_engine(
            served, attention_impl=attention, prefill_chunk_tokens=16, max_batch_size=2,
            decode_overlap=False)
        windows = _watch_windows(engine, monkeypatch) if attention != "jax" else None
        try:
            results[attention] = await serve_staggered(engine, PROMPTS[:2], max_tokens=8)
            stats = await idle_stats(engine)
        finally:
            engine.stop()
    for (tokens, lps), (twin, twin_lps) in zip(results["pallas_interpret"], results["jax"]):
        assert tokens == twin
        np.testing.assert_allclose(lps, twin_lps, atol=2e-4)
    r, rope, nope, v_dim = 32, 8, 16, 16
    pair = 2 * HEADS * (r + rope) + 2 * HEADS * r
    own_pair = 2 * HEADS * (nope + rope) + 2 * HEADS * v_dim
    # (a window's budget is the chunk: the first two are the long prompt's alone)
    assert [w["spans"] for w in windows[:2]] == [[(0, 16)], [(16, 32)]]
    attended = own = 0
    for w in windows:
        lane, walk = w["packed"]
        n_own = w["decodes"] + sum((b - a) * (b - a + 1) // 2 for a, b in w["spans"])
        # decode rows are packed first, each walking up to the position before
        # its own; then each span, all its rows up to the one before the span
        cursor = w["decodes"]
        for a, b in w["spans"]:
            assert walk[cursor:cursor + b - a].tolist() == [a - 1] * (b - a)
            cursor += b - a
        assert (walk[cursor:] == -1).all()
        resident = int((walk[:cursor] + 1).sum())
        work = w["work"]
        assert work["mla_window_ctx_total"] == n_own
        assert work["mla_attended_ctx_total"] == n_own + resident
        assert work["mla_attended_ctx_total"] == sum(
            engine._attended_ctx(a, b)[0] for a, b in w["spans"]
        ) + int((walk[: w["decodes"]] + 2).sum())
        assert work["ragged_attn_flops_total"] == LAYERS * (own_pair * n_own + pair * resident)
        attended += n_own + resident
        own += n_own
    # the second window of the 41-token prompt, by hand: 16 rows, each 16
    # resident keys and its own triangle of 136
    assert windows[1]["work"]["ragged_attn_flops_total"] == LAYERS * (own_pair * 136 + pair * 256)
    assert (stats["mla_attended_ctx_total"], stats["mla_window_ctx_total"]) == (attended, own)
    assert stats["mla_window_ctx_total"] < stats["mla_attended_ctx_total"]


@pytest.mark.parametrize("family", ["llama", "deepseek_v3"])
async def test_the_walk_is_told_resident_positions_only_by_a_family_that_attends_its_window(
        served, monkeypatch, family):
    """(vii) ``pack_spans`` is handed the rows' own positions, the very array
    the step program gets, for a family without ``unified_attends_window``
    (every llama-family cell: byte for byte what it was), and each lane's
    last resident position for one with it."""
    from dynamo_tpu.models.registry import get_family
    from tests.engine.test_jax_engine import make_engine as make_llama_engine

    assert get_family(family).unified_attends_window == (family != "llama")
    kw = dict(attention_impl="pallas_interpret", prefill_chunk_tokens=16, decode_overlap=False)
    engine = (make_engine(served, **kw) if family != "llama"
              else make_llama_engine(unified_batch=True, block_size=8, num_blocks=32, **kw))
    windows = _watch_windows(engine, monkeypatch)
    try:
        await collect(engine, request(PROMPTS[1], max_tokens=3, ignore_eos=True))
    finally:
        engine.stop()
    assert [w["spans"] for w in windows] == [[(0, 16)], [(16, 27)]]
    first, second = (w["packed"][1] for w in windows)
    if family == "llama":
        assert first[:16].tolist() == list(range(16)) and second[:11].tolist() == list(range(16, 27))
    else:
        assert first[:16].tolist() == [-1] * 16 and second[:11].tolist() == [15] * 11
    assert (first[16:] == -1).all() and (second[11:] == -1).all()
