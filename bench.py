"""Offline serving throughput benchmark (single chip) with MFU.

Geometry matches the reference's headline benchmark: 8B-class model,
ISL 3000 / OSL 150 (reference: examples/llm/benchmarks/README.md:309-319,
benchmarks/llm/perf.sh:23-29).  Reports generated tokens/s/chip, MFU
against the chip's peak bf16 FLOPs, and TTFT percentiles.  ``vs_baseline``
compares against the reference's 145 tok/s/GPU disaggregated H100 number
(BASELINE.md).

One process, which holds the chip.  Without a TPU it exits non-zero and
prints no result: a number from another backend is not this benchmark's
number.  A model whose params+cache exceed the chip's HBM (``DoesNotFit``)
steps down the ladder (8B → 3B → 1B) and the result says which model ran;
any other failure, auxiliary phases included, fails the run.  ROADMAP S1
replaces this script with a benchmark of cells; do not grow it.

Prints exactly one JSON line on stdout.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

BASELINE_TOK_S_PER_GPU = 145.0
# the reference's KV-routing headline: ~3x TTFT from KV-aware routing
# (reference docs/architecture/architecture.md:86-91)
BASELINE_ROUTING_SPEEDUP = 3.0


def _progress(note: str) -> None:
    print(f"bench: {note}", file=sys.stderr)


# (model, weight-only quant) ladder.  int8-first mirrors the reference's
# headline model being FP8-quantized (examples/llm/benchmarks/README.md:66)
# and is what makes an 8B-class model fit one v5e's 16GB HBM; the ladder
# steps down only when a rung does not fit the chip (DoesNotFit).
MODEL_LADDER = [
    ("llama3_8b", "int8"),
    ("llama32_3b", "int8"),
    ("llama32_3b", None),
    ("llama32_1b", None),
]


class DoesNotFit(Exception):
    """Pre-flight estimate: params+cache exceed this chip's HBM."""


async def _run_model(
    model_name: str, quant: str | None, *, aot_parallel: int = 6
) -> dict:
    import jax
    import numpy as np

    from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.models.registry import get_family

    cfg = getattr(LlamaConfig, model_name)()
    num_requests = int(os.environ.get("DYN_BENCH_REQUESTS", "32"))
    prompt_len = int(os.environ.get("DYN_BENCH_ISL", "3000"))
    output_len = int(os.environ.get("DYN_BENCH_OSL", "150"))
    # fp8 KV (vLLM --kv-cache-dtype fp8 equivalent) halves cache bytes,
    # which is what lets 16 decode lanes at ISL 3000 sit next to the
    # int8 8B params in 16GB of HBM; decode throughput scales with
    # lanes because every step streams the weights once for the batch
    max_batch = int(os.environ.get("DYN_BENCH_BATCH", "16"))
    decode_steps = int(os.environ.get("DYN_BENCH_DECODE_STEPS", "8"))
    kv_dtype = os.environ.get("DYN_BENCH_KV_DTYPE", "fp8")
    kv_dtype = kv_dtype if kv_dtype not in ("", "none", "model") else None

    max_len = prompt_len + output_len + 16
    block_size = 16
    per_seq_blocks = (max_len + block_size - 1) // block_size
    num_blocks = int(
        os.environ.get("DYN_BENCH_BLOCKS", per_seq_blocks * max_batch + 32)
    )

    # Chunked prefill by default: the monolithic ISL-3000 prefill program is
    # the biggest single compile in the serving path; a 512-token
    # continued-prefill window compiles small and is reused for every chunk
    # of every request.  DYN_BENCH_CHUNK=0 forces whole-prompt.
    chunk = int(os.environ.get("DYN_BENCH_CHUNK", "512")) or None
    _progress(f"rung {model_name}/{quant or 'bf16'} starting")
    t_init = time.monotonic()

    family = get_family("llama")

    def shaped_params(k):
        p = family.init_params(cfg, k)
        if quant:
            from dynamo_tpu.ops.quant import quantize_params

            p = quantize_params(p, family.quant_leaves)
        return p

    param_shapes = jax.eval_shape(shaped_params, jax.random.PRNGKey(0))
    from dynamo_tpu.engine.engine import resolve_kv_cache_dtype

    cache_shapes = jax.eval_shape(
        lambda: family.cache_init(
            cfg, num_blocks, block_size, resolve_kv_cache_dtype(kv_dtype)
        )
    )
    tree_bytes = lambda t: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(t)
    )
    need = tree_bytes(param_shapes) + tree_bytes(cache_shapes)
    # pre-flight HBM check: don't spend minutes initializing a model the
    # chip cannot hold.  Monolithic ISL-3000 prefill was observed to need
    # ~4.5G of HLO temps on top of params+cache; chunked prefill (the
    # accelerator default) keeps activations to the chunk window, so a
    # 2G margin suffices there.
    temps = 2.0e9 if chunk else 4.5e9
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    if need + temps > limit:
        raise DoesNotFit(
            f"{model_name}: params+cache {need/1e9:.1f}GB + ~{temps/1e9:.1f}GB "
            f"temps > HBM {limit/1e9:.1f}GB"
        )

    # constant-fill init: throughput/MFU are weight-agnostic, and real RNG
    # init of 8B params on host cost ~15 min of the round-2/3 bench budget.
    # Quantized leaves fill with 1 (int8) — pre-quantized trees pass through
    # the engine's quantize step untouched.
    params = None
    if os.environ.get("DYN_BENCH_INIT", "const") == "const":
        params = jax.tree.map(
            lambda s: np.full(
                s.shape, 1 if np.issubdtype(s.dtype, np.integer) else 0.01,
                dtype=s.dtype,
            ),
            param_shapes,
        )

    engine = JaxLlmEngine(
        EngineConfig(
            model=cfg,
            num_blocks=num_blocks,
            block_size=block_size,
            max_batch_size=max_batch,
            max_model_len=max_len,
            prefill_buckets=(chunk,) if chunk else (prompt_len,),
            decode_steps=decode_steps,
            prefill_chunk_tokens=chunk,
            top_logprobs_k=0,  # no top-k tax on the measured decode loop
            logit_bias_k=0,    # nor a bias scatter
            quantize=quant,
            kv_cache_dtype=kv_dtype,
        ),
        params=params,
    )
    # parallel AOT compile of the serving programs before the first drive
    # (results reach the serving path through the persistent compile cache)
    t0 = time.monotonic()
    n = engine.aot_precompile(
        [prompt_len],
        parallel=aot_parallel,
        on_program=lambda name: _progress(f"aot compiled {name}"),
    )
    _progress(f"aot precompile: {n} programs in {time.monotonic()-t0:.1f}s")
    try:
        return await _measure(engine, cfg, model_name, quant, num_requests, prompt_len,
                              output_len, max_batch, decode_steps, t_init)
    finally:
        # release HBM before a ladder step-down retries in this process
        engine.stop()
        engine.params = engine.cache = None


async def _measure(engine, cfg, model_name, quant, num_requests, prompt_len, output_len,
                   max_batch, decode_steps, t_init) -> dict:
    import jax
    import numpy as np

    from dynamo_tpu.llm.protocols.common import (
        Annotated,
        FinishReason,
        LLMEngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    engine.start()
    _progress(f"engine up ({model_name}) in {time.monotonic()-t_init:.1f}s")
    rng = np.random.default_rng(0)

    def make_request() -> dict:
        tokens = rng.integers(10, cfg.vocab_size - 10, size=prompt_len).tolist()
        return PreprocessedRequest(
            token_ids=tokens,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=output_len, ignore_eos=True),
            eos_token_ids=[],
        ).to_wire()

    itls: list[float] = []  # per-request mean inter-token latency
    decode_spans: list[tuple[float, float, int]] = []  # (t_first, t_last, n)

    async def drive(req: dict) -> tuple[int, float]:
        t0 = time.monotonic()
        ttft = None
        count = 0
        stream = await engine.generate(Context(req))
        async for item in stream:
            ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
            if ann.data is None:
                continue
            if ann.data.finish_reason is FinishReason.ERROR:
                # surface engine-side failures (OOM → ladder step-down)
                # instead of recording a 0-token "measurement"
                raise RuntimeError(ann.data.error or "sequence failed in engine")
            if ann.data.token_ids:
                t_last = time.monotonic()
                if ttft is None:
                    ttft = t_last - t0
                count += len(ann.data.token_ids)
        if ttft is not None and count > 1:
            itls.append((t_last - t0 - ttft) / (count - 1))
            decode_spans.append((t0 + ttft, t_last, count))
        return count, ttft or 0.0

    # warmup: trigger prefill + decode compiles (first device use)
    print("bench: warming up (compiles)...", file=sys.stderr)
    t0 = time.monotonic()
    await drive(make_request())
    _progress(f"warmup done in {time.monotonic()-t0:.1f}s")
    itls.clear()  # warmup's compile-inflated ITL must not enter the stats
    decode_spans.clear()

    t0 = time.monotonic()
    results = await asyncio.gather(*[drive(make_request()) for _ in range(num_requests)])
    wall = time.monotonic() - t0
    _progress(f"measurement done in {wall:.1f}s")
    # snapshot counters NOW: the auxiliary microbenchmarks below replay
    # prompts and would pollute cumulative prefix/spec counts
    run_stats = engine.stats()
    run_itls = list(itls)
    # Decode-phase throughput: generated tokens after each request's first,
    # over the window in which any request was decoding.  This is the
    # apples-to-apples for the reference's 145 tok/s/GPU headline, which is
    # measured on disaggregated DECODE workers (prefill on other GPUs) —
    # the end-to-end `value` above keeps prefill in the denominator.
    decode_phase_tok_s = None
    if decode_spans:
        span_t0 = min(s[0] for s in decode_spans)
        span_t1 = max(s[1] for s in decode_spans)
        decode_tokens = sum(s[2] - 1 for s in decode_spans)
        if span_t1 > span_t0:
            decode_phase_tok_s = decode_tokens / (span_t1 - span_t0)

    xfer = await _measure_kv_xfer(engine)
    _progress("kv-xfer microbench done")
    # the same workload through the FULL serving stack (HTTP/SSE/router/
    # codec in the measured path).  SAME request count as the direct rung —
    # decode throughput scales with batch occupancy, so a smaller fleet
    # would mis-bill lost occupancy as serving overhead
    pipeline = await _measure_pipeline(
        engine, cfg, num_requests, prompt_len, output_len
    )
    # below ~512 tokens the prefix machinery's fixed overhead (table
    # gather, allocator matching) outweighs the saved prefill compute and
    # the ratio is meaningless noise
    prefix = (
        await _measure_prefix_ttft(engine, make_request, drive)
        if prompt_len >= 512 else {}
    )

    from dynamo_tpu.ops.quant import QuantizedMatrix

    n_params = sum(
        int(np.prod(x.q.shape if isinstance(x, QuantizedMatrix) else x.shape))
        for x in jax.tree.leaves(
            engine.params, is_leaf=lambda x: isinstance(x, QuantizedMatrix)
        )
    )

    total_tokens = sum(c for c, _ in results)
    tok_s = total_tokens / wall

    def pctile(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else None

    p50 = pctile([t for _, t in results], 0.5)
    p99 = pctile([t for _, t in results], 0.99)

    # model FLOPs: 2*P per token (matmuls) + 4*L*H*D*ctx attention per token
    # (QK^T and AV, 2 flops/MAC each); summed exactly over every position of
    # every request.  MFU is total FLOPs over wall time at the chip's peak.
    dev = jax.devices()[0]
    total_len = prompt_len + output_len
    attn_coeff = 4.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim
    flops_per_req = 2.0 * n_params * total_len + attn_coeff * total_len * (total_len - 1) / 2.0
    total_flops = flops_per_req * num_requests
    # MFU denominator: the published peak of this device_kind, from the one
    # table (observability/perf.py); a kind it does not list raises
    from dynamo_tpu.observability.perf import device_peaks

    peak, _ = device_peaks(dev.device_kind)
    mfu = total_flops / wall / peak

    print(
        f"bench: {num_requests} reqs isl={prompt_len} osl={output_len} "
        f"wall={wall:.2f}s tokens={total_tokens} tok/s={tok_s:.1f} "
        f"mfu={round(mfu, 4)} "
        f"ttft p50={p50*1000:.0f}ms p99={p99*1000:.0f}ms "
        f"req/s={num_requests/wall:.2f} platform={dev.platform} kind={dev.device_kind}",
        file=sys.stderr,
    )
    return {
        "metric": "decode_tok_s_per_chip",
        "value": round(tok_s, 2),
        "unit": "tok/s/chip",
        # ratio vs the reference's 145 tok/s/GPU disagg H100 figure
        "vs_baseline": round(tok_s / BASELINE_TOK_S_PER_GPU, 3),
        "detail": {
            "model": model_name,
            "quantize": quant,
            "kv_cache_dtype": str(jax.tree.leaves(dict(engine.cache))[0].dtype),
            "n_params": n_params,
            "num_requests": num_requests,
            "isl": prompt_len,
            "osl": output_len,
            "wall_s": round(wall, 2),
            "mfu": round(mfu, 4),
            "mfu_basis": "published_peak",
            "peak_flops": round(peak / 1e12, 2),
            "achieved_tflops_per_s": round(total_flops / wall / 1e12, 3),
            "total_tflops": round(total_flops / 1e12, 1),
            "ttft_p50_ms": round(p50 * 1000, 1),
            "ttft_p99_ms": round(p99 * 1000, 1),
            # per-request mean ITL percentiles (decode_steps>1 emits in
            # bursts; the request-level mean amortizes that honestly)
            "itl_p50_ms": (
                round(pctile(run_itls, 0.5) * 1000, 2) if run_itls else None
            ),
            "itl_p99_ms": (
                round(pctile(run_itls, 0.99) * 1000, 2) if run_itls else None
            ),
            "decode_phase_tok_s": (
                None if decode_phase_tok_s is None
                else round(decode_phase_tok_s, 2)
            ),
            # decode-worker-equivalent score vs the reference's 145 tok/s
            # (that figure excludes prefill; see decode_phase_tok_s note)
            "vs_baseline_decode_phase": (
                None
                if decode_phase_tok_s is None
                else round(decode_phase_tok_s / BASELINE_TOK_S_PER_GPU, 3)
            ),
            "prefix_hits_total": run_stats.get("prefix_hits_total"),
            "spec_accepted_tokens_total": run_stats.get("spec_accepted_tokens_total"),
            "req_s": round(num_requests / wall, 3),
            "decode_steps": decode_steps,
            "batch": max_batch,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            **xfer,
            **prefix,
            **pipeline,
            # serving-stack tax: (direct engine ITL) vs (through HTTP/SSE);
            # both rates measure the same engine, so the gap IS the per-
            # token Python/codec/SSE overhead
            **(
                {
                    "pipeline_overhead_pct": round(
                        (1.0 - pipeline["pipeline_tok_s"] / tok_s) * 100.0, 1
                    )
                }
                if pipeline.get("pipeline_tok_s")
                else {}
            ),
        },
    }


def _synth_tokenizer(vocab_size: int):
    """In-memory word-level tokenizer covering the model's full vocab, so
    the detokenizer does REAL per-token vocab lookups for sampled ids of a
    synthetic-geometry model (no checkpoint tokenizer exists to use)."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit

    from dynamo_tpu.llm.tokenizer import HfTokenizer

    vocab = {f"t{i}": i for i in range(vocab_size)}
    tk = Tokenizer(WordLevel(vocab, unk_token="t0"))
    tk.pre_tokenizer = WhitespaceSplit()
    return HfTokenizer(tk)


async def _measure_pipeline(
    engine, cfg, num_requests: int, prompt_len: int, output_len: int
) -> dict:
    """The headline path through the FULL serving stack — HTTP frontend →
    preprocessor → push router → ingress → engine → detokenizer → SSE —
    so per-token Python/asyncio/SSE overhead is in the measured number
    (SURVEY hard-part (c): the reason the reference runs a Rust data
    plane).  Returns pipeline tok/s for comparison with the direct-engine
    figure measured by the caller.

    The driver is a minimal raw-socket reader on purpose: a full HTTP
    client library in the same process competes with the server for the
    event loop and GIL and bills ITS parsing cost to the serving stack
    (measured: httpx-as-client read ~500 tok/s where a raw reader shows
    the server actually sustaining ~1200 on the same workload)."""
    import re

    import numpy as np

    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.http import HttpService, ModelManager
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import CompletionPreprocessor
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.client import PushRouter, RemoteEngine, RouterMode
    from dynamo_tpu.runtime.controlplane.memory import MemoryControlPlane
    from dynamo_tpu.utils.config import RuntimeConfig

    MemoryControlPlane.reset_named()
    rt = await DistributedRuntime.create(
        RuntimeConfig(control_plane="memory://bench-pipeline")
    )
    tokenizer = _synth_tokenizer(cfg.vocab_size)
    mdc = ModelDeploymentCard(
        name="bench", context_length=engine.max_len,
        kv_block_size=engine.config.block_size,
    ).finalize()
    service = worker_service = None
    try:
        ep = rt.namespace(None).component("backend").endpoint("generate")
        worker_service = await ep.serve(engine)
        router = await PushRouter.from_endpoint(ep, RouterMode.ROUND_ROBIN)
        pipeline = CompletionPreprocessor(mdc, tokenizer).wrap(
            Backend(tokenizer).wrap(RemoteEngine(router))
        )
        manager = ModelManager()
        manager.add_completion_model("bench", pipeline)
        service = HttpService(manager, host="127.0.0.1", port=0)
        await service.start()

        rng = np.random.default_rng(1)
        usage_re = re.compile(rb'"completion_tokens":\s*(\d+)')

        async def drive() -> int:
            prompt = rng.integers(10, cfg.vocab_size - 10, size=prompt_len).tolist()
            body = json.dumps({
                "model": "bench", "prompt": prompt, "stream": True,
                "max_tokens": output_len,
                "stream_options": {"include_usage": True},
                "ext": {"ignore_eos": True, "greed_sampling": True},
            }).encode()
            reader, writer = await asyncio.open_connection("127.0.0.1", service.port)
            try:
                # Connection: close → error responses and mid-stream engine
                # failures (which never emit [DONE]) end in EOF instead of
                # an idle keep-alive socket; the wait_for is the backstop
                writer.write(
                    b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                    b"Content-Type: application/json\r\nConnection: close\r\n"
                    + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
                )
                await writer.drain()
                buf = b""

                async def read_all() -> None:
                    nonlocal buf
                    while True:
                        chunk = await reader.read(65536)
                        if not chunk:
                            break
                        buf += chunk
                        if b"[DONE]" in buf:
                            break

                await asyncio.wait_for(read_all(), timeout=600)
                if b" 200 " not in buf.split(b"\r\n", 1)[0]:
                    raise RuntimeError(
                        f"pipeline bench HTTP error: {buf[:200]!r}"
                    )
                match = usage_re.search(buf)
                return int(match.group(1)) if match else 0
            finally:
                writer.close()

        await drive()  # warm the serving-path programs/codec
        t0 = time.monotonic()
        counts = await asyncio.gather(*[drive() for _ in range(num_requests)])
        wall = time.monotonic() - t0
        total = sum(counts)
        _progress(f"pipeline rung done: {total} tokens in {wall:.1f}s")
        return {
            "pipeline_tok_s": round(total / wall, 2),
            "pipeline_wall_s": round(wall, 2),
            "pipeline_requests": num_requests,
        }
    finally:
        if service is not None:
            await service.stop()
        if worker_service is not None:
            await worker_service.shutdown(drain_timeout=5)
        await rt.close()


async def _measure_prefix_ttft(engine, make_request, drive) -> dict:
    """Engine-side prefix-cache reuse benefit — the mechanism behind the
    reference's 3x-TTFT KV-routing headline (docs/architecture/
    architecture.md:86-91): TTFT for a fresh long prompt vs the SAME
    prompt again (block-aligned prefix resident, tail-only prefill)."""
    if not getattr(engine, "prefix_caching", False):
        return {}

    def one_token(req: dict) -> dict:
        # TTFT only needs the first token; decoding OSL more would stream
        # the full weights ~OSL times per sample for nothing
        req = dict(req)
        req["stop"] = {"max_tokens": 1, "ignore_eos": True}
        return req

    # the FIRST prefix hit in the process compiles the continued-
    # prefill program — warm it on a throwaway prompt pair first
    warm = one_token(make_request())
    await drive(dict(warm))
    await drive(dict(warm))
    misses, hits = [], []
    for _ in range(3):  # median over pairs: one GC pause must not
        # become the reported headline ratio
        req = one_token(make_request())
        _, m = await drive(dict(req))
        _, h = await drive(dict(req))
        if m and h:
            misses.append(m)
            hits.append(h)
    if not misses:
        return {}
    miss = sorted(misses)[len(misses) // 2]
    hit = sorted(hits)[len(hits) // 2]
    return {
        "prefix_ttft_miss_ms": round(miss * 1000, 1),
        "prefix_ttft_hit_ms": round(hit * 1000, 1),
        "prefix_ttft_speedup": round(miss / hit, 2),
    }


async def _measure_kv_xfer(engine, n_blocks: int = 64, iters: int = 5) -> dict:
    """Prefill→decode KV block transfer bandwidth through the real transfer
    stack (BASELINE.json headline metric), both strategies:
    - device: same-process path, blocks stay as device arrays end-to-end
    - host_tcp: device→host staging + two-part codec over TCP loopback +
      host→device scatter (the DCN path's per-process cost floor)
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.parallel.kv_transfer import (
        LOCAL_SERVERS,
        KvTransferClient,
        KvTransferPayload,
        KvTransferServer,
    )

    n_blocks = min(n_blocks, engine.config.num_blocks // 2)
    if n_blocks < 1:
        return {}
    ids = jnp.asarray(np.arange(n_blocks, dtype=np.int32))
    dst = list(range(n_blocks, 2 * n_blocks))
    payload_bytes = sum(
        int(np.prod((x.shape[0], n_blocks, *x.shape[2:]))) * x.dtype.itemsize
        for x in jax.tree.leaves(dict(engine.cache))
    )

    server = KvTransferServer(lambda p: engine.inject_blocks(p.block_ids, p.blocks))
    await server.start()
    client = KvTransferClient()
    out = {}
    try:
        for strategy in ("device", "host_tcp"):
            if strategy == "host_tcp":
                LOCAL_SERVERS.pop(server.address, None)  # force TCP
            gathered = engine._jit_extract(engine.cache, ids)
            if strategy == "host_tcp":
                blocks = jax.tree.map(np.asarray, gathered)
            else:
                blocks = dict(gathered)
            payload = KvTransferPayload(
                seq_id="bench", first_token=0, block_ids=dst, blocks=blocks
            )
            await client.send(server.address, payload)  # warm (compiles)
            t0 = time.monotonic()
            for _ in range(iters):
                gathered = engine._jit_extract(engine.cache, ids)
                if strategy == "host_tcp":
                    blocks = jax.tree.map(np.asarray, gathered)
                else:
                    blocks = dict(gathered)
                await client.send(
                    server.address,
                    KvTransferPayload(
                        seq_id="bench", first_token=0, block_ids=dst, blocks=blocks
                    ),
                )
            # the device-strategy scatter is async-dispatched: synchronize
            # before stopping the clock or GB/s reads high
            jax.block_until_ready(jax.tree.leaves(dict(engine.cache)))
            elapsed = time.monotonic() - t0
            out[f"kv_xfer_gbps_{strategy}"] = round(
                payload_bytes * iters / elapsed / 1e9, 3
            )
        out["kv_xfer_block_mb"] = round(payload_bytes / n_blocks / 1e6, 3)
    finally:
        await client.close()
        await server.stop()
    return out


async def run_bench() -> dict:
    forced = os.environ.get("DYN_BENCH_MODEL")
    forced_quant = os.environ.get("DYN_BENCH_QUANT")  # "int8" | "none" | unset
    if forced_quant not in (None, "", "int8", "none", "0"):
        raise ValueError(
            f"DYN_BENCH_QUANT={forced_quant!r} not understood (want int8|none)"
        )
    # validate up front (bench env contract): a bad value must fail fast,
    # not burn one full engine construction per ladder rung before erroring
    try:
        aot_parallel = int(os.environ.get("DYN_BENCH_AOT_PARALLEL", "6"))
    except ValueError:
        raise ValueError(
            f"DYN_BENCH_AOT_PARALLEL="
            f"{os.environ['DYN_BENCH_AOT_PARALLEL']!r} is not an integer"
        ) from None
    if forced:
        # default matches the ladder's headline rung (int8); set
        # DYN_BENCH_QUANT=none for bf16
        ladder = [(forced, None if forced_quant in ("none", "0") else "int8")]
    else:
        ladder = list(MODEL_LADDER)
        if forced_quant == "int8":
            ladder = list(dict.fromkeys((m, "int8") for m, _ in ladder))
        elif forced_quant in ("none", "0"):
            ladder = list(dict.fromkeys((m, None) for m, _ in ladder))
    for i, (model_name, quant) in enumerate(ladder):
        try:
            return await _run_model(model_name, quant, aot_parallel=aot_parallel)
        except DoesNotFit as err:
            # the one reason to try a smaller model; anything else is a
            # failure of this run
            if i + 1 == len(ladder):
                raise
            _progress(f"{model_name}/{quant or 'bf16'}: {err}; stepping down")
    raise AssertionError("unreachable: empty ladder")


async def _measure_kv_routing() -> dict:
    """KV-aware vs random routing TTFT on multi-turn traffic — the
    reference's 3x-TTFT routing claim (docs/architecture/architecture.md:
    86-91), measured through the real router/indexer/dispatch stack over a
    mocker fleet (device-independent; the full artifact is
    ROUTED_FLEET.json via `python -m dynamo_tpu.bench.routed_fleet`)."""
    from dynamo_tpu.bench.data_generator import SessionConfig, generate_sessions
    from dynamo_tpu.bench.routed_fleet import FleetConfig, run_fleet

    cfg = SessionConfig(num_sessions=24, turns_per_session=4)
    fleet = FleetConfig()
    sessions = generate_sessions(cfg)
    # median of 3 repeats: the compressed-sleep sim is sensitive to host
    # load spikes (observed 1.6x-3.1x for the SAME config depending on
    # what else the machine ran), and one spike must not become the
    # recorded headline
    speedups, followups, last = [], [], None
    for _ in range(3):
        rnd = await run_fleet("random", sessions, fleet)
        kv = await run_fleet("kv", sessions, fleet)
        speedups.append(rnd["ttft_p50_ms"] / kv["ttft_p50_ms"])
        followups.append(
            rnd["followup_ttft_p50_ms"] / kv["followup_ttft_p50_ms"]
        )
        last = (rnd, kv)
    rnd, kv = last
    speedup = round(sorted(speedups)[1], 2)
    return {
        "ttft_p50_speedup": speedup,
        "ttft_p50_speedup_runs": [round(x, 2) for x in speedups],
        "followup_ttft_p50_speedup": round(sorted(followups)[1], 2),
        # scored against the reference's 3x routing claim — this ratio is
        # device-independent, so it is ALWAYS a real vs_baseline
        "vs_baseline": round(speedup / BASELINE_ROUTING_SPEEDUP, 3),
        "kv_prefix_hits": kv["prefix_hits_total"],
        "random_prefix_hits": rnd["prefix_hits_total"],
    }


def main() -> int:
    import jax

    from dynamo_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    t0 = time.monotonic()
    devs = jax.devices()
    _progress(f"devices {devs} in {time.monotonic()-t0:.1f}s")
    if devs[0].platform != "tpu":
        print(
            f"bench: needs a TPU, found platform {devs[0].platform!r}; "
            "no result", file=sys.stderr,
        )
        return 1
    result = asyncio.run(run_bench())
    result["detail"]["kv_routing"] = asyncio.run(_measure_kv_routing())
    _progress("kv-routing fleet microbench done")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
