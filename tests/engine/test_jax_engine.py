"""JaxLlmEngine behavior: greedy correctness vs dense recompute, continuous
batching, stop conditions, cancellation, preemption under KV pressure, stats.
"""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.llm.protocols.common import (
    Annotated,
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig, init_params
from dynamo_tpu.runtime.engine import Context

from tests.models.test_llama import dense_reference_logits

CFG = LlamaConfig.tiny()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def make_engine(**overrides) -> JaxLlmEngine:
    defaults = dict(
        model=CFG,
        num_blocks=64,
        block_size=4,
        max_batch_size=4,
        prefill_buckets=(16, 32, 64),
        max_model_len=128,
    )
    defaults.update(overrides)
    engine = JaxLlmEngine(EngineConfig(**defaults), params=PARAMS)
    engine.start()
    return engine


def request(tokens, max_tokens=8, **kw) -> dict:
    return PreprocessedRequest(
        token_ids=list(tokens),
        sampling=SamplingOptions(use_greedy=True),
        stop=StopConditions(max_tokens=max_tokens, **kw),
        eos_token_ids=[1],
    ).to_wire()


async def collect(engine, req_wire) -> tuple[list[int], FinishReason | None]:
    stream = await engine.generate(Context(req_wire))
    tokens, finish = [], None
    async for item in stream:
        ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
        if ann.data is None:
            continue
        tokens.extend(ann.data.token_ids)
        if ann.data.finish_reason is not None:
            finish = ann.data.finish_reason
    return tokens, finish


def greedy_reference(prompt, n_steps):
    """Dense full-recompute greedy decoding."""
    current = list(prompt)
    out = []
    for _ in range(n_steps):
        logits = dense_reference_logits(PARAMS, CFG, current)
        nxt = int(jnp.argmax(logits[len(current) - 1]))
        out.append(nxt)
        if nxt == 1:
            break
        current.append(nxt)
    return out


async def test_greedy_matches_dense_reference():
    engine = make_engine()
    try:
        prompt = list(range(3, 13))
        tokens, finish = await collect(engine, request(prompt, max_tokens=6))
        ref = greedy_reference(prompt, 6)
        assert tokens == ref
        assert finish in (FinishReason.LENGTH, FinishReason.STOP)
    finally:
        engine.stop()


async def test_concurrent_requests_batch_together():
    engine = make_engine()
    try:
        prompts = [list(range(3 + i, 10 + i)) for i in range(4)]
        results = await asyncio.gather(
            *[collect(engine, request(p, max_tokens=5)) for p in prompts]
        )
        for prompt, (tokens, _) in zip(prompts, results):
            ref = greedy_reference(prompt, 5)
            assert tokens == ref
        # all four ran concurrently through the batched decode path
        assert engine.stats()["iterations_total"] < 40
    finally:
        engine.stop()


async def test_max_tokens_finish_reason():
    engine = make_engine()
    try:
        tokens, finish = await collect(engine, request(range(3, 9), max_tokens=3))
        assert len(tokens) == 3
        assert finish == FinishReason.LENGTH
    finally:
        engine.stop()


async def test_cancellation_frees_resources():
    engine = make_engine()
    try:
        req = Context(request(range(3, 9), max_tokens=10_000))
        stream = await engine.generate(req)
        got = 0
        async for _ in stream:
            got += 1
            if got >= 2:
                req.ctx.stop_generating()
        for _ in range(100):
            if engine.allocator.used_blocks == 0:
                break
            await asyncio.sleep(0.02)
        assert engine.allocator.used_blocks == 0
        assert engine.scheduler.num_running == 0
    finally:
        engine.stop()


async def test_too_long_prompt_rejected():
    engine = make_engine()
    try:
        with pytest.raises(ValueError, match="exceeds engine max length"):
            await engine.generate(Context(request(range(3, 3 + 500))))
    finally:
        engine.stop()


async def test_preemption_under_kv_pressure():
    # 8 blocks of 4 tokens = 32 slots total; two long-running requests can't
    # both fit to completion, so the scheduler must preempt + recompute
    engine = make_engine(num_blocks=8, max_model_len=24, max_batch_size=2)
    try:
        prompts = [list(range(3, 11)), list(range(4, 12))]  # 8 tokens each
        results = await asyncio.gather(
            *[collect(engine, request(p, max_tokens=8)) for p in prompts]
        )
        for prompt, (tokens, finish) in zip(prompts, results):
            ref = greedy_reference(prompt, 8)
            assert tokens[: len(ref)] == ref
            assert finish is not None
    finally:
        engine.stop()


async def test_stats_shape():
    engine = make_engine()
    try:
        stats = engine.stats()
        assert stats["kv_total_blocks"] == 64
        assert stats["gpu_cache_usage_perc"] == 0.0
        assert stats["request_total_slots"] == 4
    finally:
        engine.stop()


async def test_pallas_attention_engine_matches_reference():
    """Engine with the Pallas paged-attention path (interpret on CPU) must
    produce identical greedy output."""
    engine = make_engine(attention_impl="pallas_interpret", block_size=8, num_blocks=32)
    try:
        prompt = list(range(3, 13))
        tokens, _ = await collect(engine, request(prompt, max_tokens=5))
        assert tokens == greedy_reference(prompt, 5)
    finally:
        engine.stop()


# ------------------------------------------------------------- multi-step


async def test_multistep_decode_matches_single_step():
    """decode_steps=4 (fused on-device loop) must produce exactly the same
    greedy tokens as decode_steps=1."""
    prompt = list(range(3, 10))
    single = make_engine(decode_steps=1)
    try:
        tokens_1, finish_1 = await collect(single, request(prompt, max_tokens=11))
    finally:
        single.stop()
    multi = make_engine(decode_steps=4)
    try:
        tokens_4, finish_4 = await collect(multi, request(prompt, max_tokens=11))
    finally:
        multi.stop()
    assert tokens_4 == tokens_1
    assert finish_4 == finish_1 == FinishReason.LENGTH


async def test_multistep_decode_concurrent_and_stop_midwindow():
    """Concurrent sequences with different lengths finish correctly even when
    a stop lands mid-window; token counts are exact (no overshoot)."""
    engine = make_engine(decode_steps=4, max_batch_size=4)
    try:
        results = await asyncio.gather(
            collect(engine, request(range(3, 10), max_tokens=3)),   # mid-window
            collect(engine, request(range(5, 14), max_tokens=9)),
            collect(engine, request(range(2, 8), max_tokens=6)),
        )
        for (tokens, finish), expect in zip(results, (3, 9, 6)):
            assert len(tokens) == expect
            assert finish == FinishReason.LENGTH
    finally:
        engine.stop()


async def test_multistep_greedy_matches_dense_reference():
    """Fused decode must agree with dense full-recompute greedy decoding."""
    prompt = list(range(3, 12))
    engine = make_engine(decode_steps=4)
    try:
        tokens, _ = await collect(engine, request(prompt, max_tokens=8))
    finally:
        engine.stop()
    assert tokens == greedy_reference(prompt, 8)


async def test_multistep_decode_under_preemption():
    """Tight block pool forces victim/self preemption mid-window; the
    two-phase lane rebuild must keep output identical to dense greedy."""
    engine = make_engine(decode_steps=4, max_batch_size=4, num_blocks=10, max_model_len=40)
    try:
        prompts = [list(range(3, 10)), list(range(5, 12)), list(range(2, 9))]
        results = await asyncio.gather(
            *[collect(engine, request(p, max_tokens=8)) for p in prompts]
        )
        for (tokens, finish), prompt in zip(results, prompts):
            assert len(tokens) == 8
            assert tokens == greedy_reference(prompt, 8)
    finally:
        engine.stop()


# ---------------------------------------------------- sampling state


def sampled_request(tokens, max_tokens=8, **sampling_kw):
    return PreprocessedRequest(
        token_ids=list(tokens),
        sampling=SamplingOptions(**sampling_kw),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        eos_token_ids=[],
    ).to_wire()


async def test_seed_reproducible_sampling():
    """Same request seed → identical sampled tokens across runs and engines;
    different seed → different stream (overwhelmingly likely)."""
    prompt = list(range(3, 10))
    outs = []
    for seed in (1234, 1234, 99):
        engine = make_engine()
        try:
            tokens, _ = await collect(
                # high temperature flattens the tiny model's peaked logits so
                # different seeds actually diverge
                engine, sampled_request(prompt, temperature=8.0, seed=seed)
            )
        finally:
            engine.stop()
        outs.append(tokens)
    assert outs[0] == outs[1]
    assert outs[0] != outs[2]


async def test_frequency_penalty_blocks_repeats():
    """A huge frequency penalty makes every generated token distinct (greedy
    would otherwise loop on a tiny random-weight model)."""
    prompt = list(range(3, 10))
    engine = make_engine()
    try:
        base, _ = await collect(engine, request(prompt, max_tokens=12, ignore_eos=True))
    finally:
        engine.stop()
    assert len(set(base)) < len(base)  # sanity: greedy does repeat

    engine = make_engine()
    try:
        penalized, _ = await collect(
            engine,
            sampled_request(prompt, max_tokens=12, use_greedy=True, frequency_penalty=100.0),
        )
    finally:
        engine.stop()
    assert len(set(penalized)) == len(penalized)


async def test_penalties_with_multistep_decode():
    """Penalty counts update inside the fused decode scan too."""
    prompt = list(range(3, 10))
    engine = make_engine(decode_steps=4)
    try:
        penalized, _ = await collect(
            engine,
            sampled_request(prompt, max_tokens=12, use_greedy=True, frequency_penalty=100.0),
        )
    finally:
        engine.stop()
    assert len(set(penalized)) == len(penalized)


async def test_preemption_preserves_penalty_state():
    """Preemption recompute must keep prompt vs generated token counts exact:
    a frequency-penalized request that gets preempted still emits the same
    tokens as on an uncontended engine (the gen_row re-seed defect)."""
    prompts = [list(range(3, 10)), list(range(5, 12)), list(range(2, 9))]

    refs = []
    for p in prompts:
        engine = make_engine()  # roomy: no preemption
        try:
            tokens, _ = await collect(
                engine,
                sampled_request(p, max_tokens=12, use_greedy=True, frequency_penalty=100.0),
            )
        finally:
            engine.stop()
        refs.append(tokens)

    # tight pool: 3 seqs × ceil(19/4)=5 blocks > 10 blocks → preemption
    engine = make_engine(max_batch_size=4, num_blocks=10, max_model_len=40)
    preempts = []
    orig_preempt = engine.scheduler.preempt
    engine.scheduler.preempt = lambda seq: (preempts.append(seq.seq_id), orig_preempt(seq))[1]
    try:
        results = await asyncio.gather(
            *[
                collect(
                    engine,
                    sampled_request(p, max_tokens=12, use_greedy=True, frequency_penalty=100.0),
                )
                for p in prompts
            ]
        )
    finally:
        engine.stop()
    assert preempts, "test geometry failed to force preemption"
    for (tokens, _), ref in zip(results, refs):
        assert tokens == ref
        assert len(set(tokens)) == len(tokens)  # penalty still blocks repeats


async def test_pallas_failure_is_loud_not_a_quiet_xla_rebuild():
    """A Pallas kernel the compiler refuses is a start-up failure, not a
    mid-serve rebuild onto the XLA twin that then reports success: warmup
    raises, a request fails with an error, and the engine still says
    ``pallas``.  On CPU the TPU kernel never lowers, so forcing
    ``attention_impl="pallas"`` exercises exactly that."""
    engine = make_engine(attention_impl="pallas")
    try:
        with pytest.raises(Exception, match="(?i)pallas|mosaic|interpret|lower"):
            await engine.warmup()
        tokens, finish = await collect(engine, request([5, 6, 7, 8], max_tokens=4))
        assert finish is FinishReason.ERROR and not tokens
        assert engine.attention_impl == "pallas"
        assert not hasattr(engine, "_attention_fallback")
    finally:
        engine.stop()


async def test_pp_mesh_engine_matches_dense_reference():
    """Serving through a pp=2 mesh: the pipelined decode (GPipe stages over
    ppermute) produces exactly the single-device greedy output."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    engine = make_engine(mesh=MeshConfig(pp=2), attention_impl="jax")
    try:
        prompt = [5, 6, 7, 8, 9, 10]
        tokens, finish = await collect(engine, request(prompt, max_tokens=6))
        assert finish in (FinishReason.LENGTH, FinishReason.STOP)
        assert tokens == greedy_reference(prompt, len(tokens))
    finally:
        engine.stop()


async def test_sp_mesh_engine_matches_dense_reference():
    """Serving through an sp=2 mesh: ring-attention prefill (sequence
    sharded over sp) produces exactly the single-device greedy output —
    and for the llama family prefix caching STAYS ON (the continued-
    prefill path rings the tail and merges the resident prefix)."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    engine = make_engine(mesh=MeshConfig(sp=2))
    try:
        assert engine.prefix_caching
        prompt = [5, 6, 7, 8, 9, 10]
        tokens, finish = await collect(engine, request(prompt, max_tokens=6))
        assert finish in (FinishReason.LENGTH, FinishReason.STOP)
        assert tokens == greedy_reference(prompt, len(tokens))
    finally:
        engine.stop()


async def test_sp_mesh_prefix_hit_and_chunked_prefill_exact():
    """sp × prefix caching × chunked prefill (the round-3 composition
    hole): a repeated prompt must prefix-HIT (tail-only ring prefill with
    the resident prefix merged) and long prompts must chunk — all
    token-exact vs the single-device reference."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    engine = make_engine(
        mesh=MeshConfig(sp=2), num_blocks=64, block_size=4,
        prefill_buckets=(16, 32), max_model_len=64,
        prefill_chunk_tokens=16,
    )
    try:
        assert engine.prefix_caching
        assert engine.chunk_tokens == 16
        # long prompt: chunks of 16 through the ring'd continued-prefill
        prompt = list(range(3, 3 + 24))
        ref = greedy_reference(prompt, 4)
        tokens, _ = await collect(engine, request(prompt, max_tokens=4, ignore_eos=True))
        assert tokens == ref
        # identical prompt again: block-aligned prefix resident → hit
        tokens2, _ = await collect(engine, request(prompt, max_tokens=4, ignore_eos=True))
        assert tokens2 == ref
        assert engine.allocator.prefix_hits_total > 0
    finally:
        engine.stop()


async def test_warmup_compiles_and_leaves_no_state():
    """warmup() drives every prefill bucket then flushes: no resident
    blocks, empty prefix registry, and a following request is exact."""
    engine = make_engine()
    try:
        await engine.warmup()
        assert engine.allocator.used_blocks == 0
        assert not engine.allocator._hash_to_block  # registry flushed
        assert engine.allocator.cached_blocks == 0
        prompt = [5, 6, 7, 8]
        tokens, _ = await collect(engine, request(prompt, max_tokens=4))
        assert tokens == greedy_reference(prompt, 4)
    finally:
        engine.stop()


async def test_tp_mesh_pallas_attention_matches_reference():
    """TP-sharded decode with the Pallas kernel under shard_map (interpret
    mode on the CPU mesh): output must equal the single-device greedy
    reference exactly."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    engine = make_engine(
        mesh=MeshConfig(tp=2), attention_impl="pallas_interpret",
        block_size=8, num_blocks=32,
    )
    try:
        prompt = list(range(3, 13))
        tokens, finish = await collect(engine, request(prompt, max_tokens=5))
        assert finish in (FinishReason.LENGTH, FinishReason.STOP)
        assert tokens == greedy_reference(prompt, 5)
    finally:
        engine.stop()


async def test_warmup_compiles_decode_at_max_len_bucket():
    """Even when the only bucket IS max_len, warmup leaves room for a full
    decode window (the decode jit must compile, not just prefill)."""
    engine = make_engine(prefill_buckets=(128,), max_model_len=32, decode_steps=1)
    try:
        traced = {"n": 0}
        orig = engine._jit_decode

        def counting(*a, **k):
            traced["n"] += 1
            return orig(*a, **k)

        counting.lower = orig.lower  # warmup AOT-compiles through .lower
        engine._jit_decode = counting
        await engine.warmup()
        assert traced["n"] >= 1  # decode ran (hence compiled) during warmup
    finally:
        engine.stop()


def test_min_tokens_suppresses_eos():
    """min_tokens holds off EOS/stop-token finishes until the minimum is
    generated (vLLM semantics); max_tokens still applies."""
    from dynamo_tpu.engine.sequence import Sequence

    pre = PreprocessedRequest(
        token_ids=[1, 2, 3],
        stop=StopConditions(max_tokens=10, min_tokens=3, stop_token_ids=[42]),
        eos_token_ids=[7],
    )
    seq = Sequence(seq_id="s", request=pre)
    # below the minimum: EOS and stop tokens pass through
    seq.output_ids.append(7)
    assert seq.hit_stop(7) is None
    seq.output_ids.append(42)
    assert seq.hit_stop(42) is None
    # at the minimum: stop token fires
    seq.output_ids.append(42)
    assert seq.hit_stop(42) is FinishReason.STOP
    # max_tokens is never suppressed
    pre2 = PreprocessedRequest(
        token_ids=[1], stop=StopConditions(max_tokens=2, min_tokens=5),
        eos_token_ids=[],
    )
    seq2 = Sequence(seq_id="s2", request=pre2)
    seq2.output_ids.extend([9, 9])
    assert seq2.hit_stop(9) is FinishReason.LENGTH


def test_rope_tables_sliced_and_passed_as_args():
    """Serving programs must not bake the rope tables in as HLO constants:
    families build them to max_position_embeddings (131k for llama3 — 33MB
    of fp32 per table), and a closed-over concrete array is embedded into
    every compiled program (observed: 350MB of trig constants inside one
    prefill executable, which is what wedged the remote compile service on
    the TPU bench).  The engine slices to max_len and threads cos/sin
    through the jits as arguments."""
    import dataclasses
    import inspect

    cfg = dataclasses.replace(CFG, max_position_embeddings=131072)
    engine = JaxLlmEngine(
        EngineConfig(model=cfg, num_blocks=64, block_size=4,
                     max_batch_size=4, prefill_buckets=(16,), max_model_len=128)
    )
    # sliced: the device table covers max_len positions, not 131k
    assert engine.cos.shape[0] == engine.max_len == 128
    assert engine.cos.nbytes < 100_000
    # threaded as args: every serving jit's wrapped function ends (cos, sin)
    for jit_fn in (engine._jit_prefill, engine._jit_prefill_prefix,
                   engine._jit_decode):
        params = list(inspect.signature(jit_fn.__wrapped__).parameters)
        assert params[-2:] == ["cos", "sin"], params


def test_embedding_engine_rope_tables_sliced_and_passed_as_args():
    """Same guarantee for JaxEmbeddingEngine: tables sliced to the served
    window and threaded through the jit as arguments, not closure
    constants."""
    import dataclasses
    import inspect

    from dynamo_tpu.engine.embedding import EmbeddingEngineConfig, JaxEmbeddingEngine

    cfg = dataclasses.replace(CFG, max_position_embeddings=131072)
    eng = JaxEmbeddingEngine(
        EmbeddingEngineConfig(model=cfg, max_length=64), tokenizer=None
    )
    assert eng.cos.shape[0] == 64
    assert eng.cos.nbytes < 100_000
    params = list(inspect.signature(eng._embed.__wrapped__).parameters)
    assert params[-2:] == ["cos", "sin"], params


@pytest.mark.slow
@pytest.mark.parametrize(
    "extra",
    [
        {},
        # the newly-composable mode: speculative drafting + fused
        # multi-step decode under preemption/cancellation churn
        {"speculative": "ngram", "spec_tokens": 3, "decode_steps": 4},
    ],
    ids=["plain", "spec_fused"],
)
async def test_soak_random_load_cancellations_preemption(extra):
    """Engine soak: 48 requests with random lengths and budgets, a third
    cancelled mid-stream, over a KV pool far too small for the offered
    load (constant preemption + recompute).  Afterwards: zero leaked
    blocks, zero stuck lanes, and the engine still serves correctly."""
    import random

    engine = make_engine(
        num_blocks=24, block_size=4, max_batch_size=4,
        prefill_buckets=(16, 64), max_model_len=64, **extra,
    )
    try:
        async def one(i: int) -> int:
            r = random.Random(i)
            n = r.randint(2, 30)
            max_toks = r.randint(1, 20)
            req = Context(request(range(3, 3 + n), max_tokens=max_toks))
            stream = await engine.generate(req)
            cancel_at = r.randint(1, 5) if i % 3 == 0 else None
            got = 0
            async for _ in stream:
                got += 1
                if cancel_at is not None and got >= cancel_at:
                    req.ctx.stop_generating()
            return got

        results = await asyncio.gather(
            *[one(i) for i in range(48)], return_exceptions=True
        )
        errs = [r for r in results if isinstance(r, BaseException)]
        assert not errs, errs
        assert all(r >= 1 for r in results if not isinstance(r, BaseException))

        # no leaks: every block and lane reclaimed once streams drained
        for _ in range(200):
            if engine.allocator.used_blocks == 0 and engine.scheduler.num_running == 0:
                break
            await asyncio.sleep(0.02)
        assert engine.allocator.used_blocks == 0
        assert engine.scheduler.num_running == 0
        assert engine.scheduler.num_waiting == 0

        # liveness + correctness after the storm
        tokens, finish = await collect(engine, request(range(3, 9), max_tokens=3))
        assert len(tokens) == 3 and finish == FinishReason.LENGTH
    finally:
        engine.stop()


async def test_single_device_mesh_offset_pins_device():
    """MeshConfig(tp=1, device_offset=k) must actually pin the engine to
    device k (disagg with one chip per role), not silently land on the
    default device."""
    import jax

    from dynamo_tpu.parallel.mesh import MeshConfig

    engine = make_engine(mesh=MeshConfig(tp=1, device_offset=1))
    try:
        assert engine.mesh is not None
        cache_devices = set().union(
            *(leaf.devices() for leaf in jax.tree.leaves(dict(engine.cache)))
        )
        assert cache_devices == {jax.devices()[1]}, cache_devices
        prompt = list(range(3, 11))
        out, _ = await collect(engine, request(prompt, max_tokens=3, ignore_eos=True))
        assert out == greedy_reference(prompt, 3)
    finally:
        engine.stop()


def test_resident_arrays_sit_uncommitted_on_the_default_device():
    """Params, cache, rope tables and penalty counts are built on the host
    CPU backend and must end up on the DEFAULT device, uncommitted: an
    array left on the CPU backend is uploaded again every step, and a
    committed one marks its argument in every lowered module, so the
    programs stop matching their AOT twins (both seen on the chip)."""
    engine = make_engine()
    leaves = jax.tree.leaves(
        (engine.params, engine.cache, engine.cos, engine.sin,
         engine._gen_counts, engine._prompt_counts)
    )
    assert leaves and all(not x.committed for x in leaves)
    assert all(x.devices() == {jax.devices()[0]} for x in leaves)


def test_no_table_routes_a_tpu_engine_off_the_kernel(monkeypatch):
    """attention_impl=auto on a TPU IS the Pallas path: the backend decides
    it, and the engine reads no table that could switch the kernel off."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = make_engine()
    assert engine.attention_impl == "pallas"
    assert engine.stats()["attention_impl"] == "pallas"
    monkeypatch.undo()
    assert make_engine().attention_impl == "jax"  # the CPU backend's path


async def test_sampling_tail_upload_cache():
    """Steady-state decode windows with unchanged sampling state reuse the
    same device copies of the sampling tail (the cache equality-checks
    host values each window); changed state gets fresh copies."""

    def seeded(temp=None):
        return PreprocessedRequest(
            token_ids=list(range(3, 9)),
            sampling=SamplingOptions(
                use_greedy=temp is None, temperature=temp, seed=7,
            ),
            stop=StopConditions(max_tokens=4, ignore_eos=True),
            eos_token_ids=[1],
        ).to_wire()

    # synchronous decode: lane assignment is deterministic across requests
    # (the overlapped pipeline releases a finished lane one window later,
    # so a back-to-back request can land on a different lane — a cache
    # miss by design, not a defect in the tail cache)
    engine = make_engine(decode_overlap=False)
    try:
        await collect(engine, seeded())
        cache1 = engine._tail_cache
        assert cache1 is not None
        # identical sampling state (pinned seed → identical lane key): the
        # cached device tuple survives a whole second request
        await collect(engine, seeded())
        assert engine._tail_cache is not None
        assert engine._tail_cache[1] is cache1[1]
        # different sampling config → fresh device copies
        await collect(engine, seeded(temp=0.7))
        assert engine._tail_cache[1] is not cache1[1]
    finally:
        engine.stop()


async def test_pp_tp_mesh_engine_matches_dense_reference():
    """Serving through a pp=2 x tp=2 mesh: pipeline stages carry
    tp-sharded weights (partial-manual shard_map — pp manual, tp auto
    inside each stage) and greedy output is exactly the single-device
    reference."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    engine = make_engine(mesh=MeshConfig(pp=2, tp=2), attention_impl="jax")
    try:
        assert engine.mesh.shape["pp"] == 2 and engine.mesh.shape["tp"] == 2
        prompt = [5, 6, 7, 8, 9, 10]
        tokens, finish = await collect(engine, request(prompt, max_tokens=6))
        assert finish in (FinishReason.LENGTH, FinishReason.STOP)
        assert tokens == greedy_reference(prompt, len(tokens))
    finally:
        engine.stop()


def test_sp_mesh_rejects_bad_buckets_at_construction():
    """sp bucket divisibility fails at engine construction (fail-fast
    config validation), never as a mid-serving jit trace error."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    with pytest.raises(ValueError, match="not divisible by the sp axis"):
        JaxLlmEngine(
            EngineConfig(
                model=CFG, num_blocks=32, block_size=4, max_batch_size=2,
                prefill_buckets=(16, 33), max_model_len=33,
                mesh=MeshConfig(sp=2),
            ),
            params=PARAMS,
        )



async def test_pp_ep_mesh_engine_matches_single_device():
    """Serving a MoE family through a pp=2 x ep=2 mesh: pipeline stages
    carry expert-sharded weights (pp manual, the expert all-to-alls ride
    the automatic ep axis inside each stage) and greedy output is
    token-exact vs an identical engine without a mesh."""
    import jax as _jax

    from dynamo_tpu.models import mixtral as mx
    from dynamo_tpu.parallel.mesh import MeshConfig

    mcfg = mx.MixtralConfig.tiny_moe()
    import numpy as np

    mparams = jax.tree.map(np.asarray, mx.init_params(mcfg, _jax.random.PRNGKey(5)))

    def moe_engine(mesh=None):
        engine = JaxLlmEngine(
            EngineConfig(
                model=mcfg, model_family="mixtral", num_blocks=64,
                block_size=4, max_batch_size=4, prefill_buckets=(16, 32),
                max_model_len=64, mesh=mesh, attention_impl="jax",
            ),
            params=jax.tree.map(np.copy, mparams),
        )
        engine.start()
        return engine

    prompt = [5, 6, 7, 8, 9, 10]
    ref = moe_engine()
    try:
        expected, _ = await collect(ref, request(prompt, max_tokens=6))
    finally:
        ref.stop()

    engine = moe_engine(MeshConfig(pp=2, ep=2))
    try:
        assert engine.mesh.shape["pp"] == 2 and engine.mesh.shape["ep"] == 2
        tokens, finish = await collect(engine, request(prompt, max_tokens=6))
        assert finish in (FinishReason.LENGTH, FinishReason.STOP)
        assert tokens == expected
    finally:
        engine.stop()


async def test_phase_timing_stats():
    """The host-phase accounting is always on: stats()["phase_ms"] carries
    the six phase names from engine start, on every branch that serves a
    window (split overlap, split synchronous, unified), with no knob."""
    from dynamo_tpu.engine.engine import STEP_PHASES

    engine = make_engine()
    try:
        fresh = engine.stats()["phase_ms"]
        assert set(STEP_PHASES) <= set(fresh)
        assert all((fresh[name]["total_ms"], fresh[name]["n"], fresh[name]["mean_ms"])
                   == (0.0, 0, 0.0) for name in STEP_PHASES)
    finally:
        engine.stop()
    served = ("schedule", "upload", "dispatch", "readback", "post")
    for kwargs in ({"decode_overlap": True, "unified_batch": False},
                   {"decode_overlap": False, "unified_batch": False},
                   {"decode_overlap": True, "unified_batch": True},
                   {"decode_overlap": False, "unified_batch": True}):
        engine = make_engine(**kwargs)
        try:
            prompt = list(range(3, 9))
            await collect(engine, request(prompt, max_tokens=4, ignore_eos=True))
            stats = engine.stats()
            phases = stats["phase_ms"]
            assert set(phases) == set(STEP_PHASES), sorted(phases)
            for name in served:
                assert phases[name]["n"] >= 1, (kwargs, name, phases)
                assert phases[name]["total_ms"] >= 0
            # `pack` is the Pallas worklist: the XLA twin (CPU default) has none
            assert phases["pack"]["n"] == 0
            # host time is what the finished iterations took less `readback`
            assert 0 < stats["engine_host_time_total_s"] < stats["engine_step_time_total_s"]
        finally:
            engine.stop()


@pytest.mark.parametrize("mode", [
    {},                                                   # unified windows, overlapped
    {"unified_batch": False, "decode_overlap": False},    # split steps, read back at once
    {"decode_steps": 4},                                  # the fused multi-step scan
], ids=["unified_overlap", "split_sync", "multistep"])
async def test_a_step_of_greedy_lanes_is_booked_as_sorting_no_vocabulary(mode):
    """``sample_sort_skipped_steps_total`` rises by one for each busy step
    of an all-greedy batch and stays put while a request with a temperature
    is on a lane (its window's program sorts for every lane); the greedy
    requests' tokens are the same with and without that neighbour."""
    prompts = [list(range(3 + i, 10 + i)) for i in range(2)]
    greedy = [request(p, max_tokens=6, ignore_eos=True) for p in prompts]
    # the neighbour arrives first and answers longest: on a lane in every
    # busy step
    neighbour = sampled_request(prompts[0], max_tokens=24, temperature=0.8, seed=5)

    async def serve(*requests):
        engine = make_engine(**mode)
        try:
            answers = await asyncio.gather(*[collect(engine, r) for r in requests])
        finally:
            engine.stop()
        stats = engine.stats()
        return (
            [tokens for tokens, _ in answers],
            stats["engine_busy_steps_total"],
            stats["sample_sort_skipped_steps_total"],
        )

    alone, busy, skipped = await serve(*greedy)
    assert busy > 0 and skipped == busy
    beside, busy, skipped = await serve(neighbour, *greedy)
    assert busy > 0 and skipped == 0
    assert beside[1:] == alone
