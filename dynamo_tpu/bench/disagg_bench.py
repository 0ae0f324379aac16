"""Disaggregated prefill/decode throughput benchmark (one process).

Produces the disagg analog of the reference's headline number — req/s and
decode-phase tok/s with prefill running on a DIFFERENT engine than decode,
KV shipped via the transfer plane (reference measurement:
examples/llm/benchmarks/README.md:309-319, where decode workers report
tok/s/GPU with prefill disaggregated onto other GPUs).

On one chip both engines share the accelerator, so this is NOT two-chip
disagg — what it measures end-to-end is the full disagg machinery in the
serving path at realistic geometry: router decision, prefill queue, remote
prefill, block-exact KV landing, decode continuation.  The useful outputs
are (a) disagg_req_s / decode-phase tok/s through that path, and (b)
``disagg_overhead_pct`` vs the same workload on a single aggregated
engine — the cost of the disagg plumbing itself, which on real multi-chip
deployments is the part this framework owns (compute overlap is the
hardware's business).

Two further sections exercise this PR's streamed-transfer path:

- ``streamed_ab`` — same disagg stack, chunked prefill engine, streamed
  (DYN_KV_STREAM-style multi-part) vs single-shot transfer: TTFT p50/p99
  per mode, parts shipped, and the transfer-hidden fraction (share of
  transfer wall time overlapped with prefill compute).
- ``fleet`` — a second decode candidate behind an unequal link: requests
  share a prefix held by the "near" (ici) worker while the "far" worker
  sits behind dcn; the KV-locality/link-cost scorer routes each request
  and the section records pick counts + fleet TTFT.

Usage:
    python -m dynamo_tpu.bench.disagg_bench                # auto geometry
    python -m dynamo_tpu.bench.disagg_bench --model tiny   # CPU smoke
Writes DISAGG_BENCH.json (or --out) and prints one JSON line.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time


def _build_engine(model: str, quant: str | None, kv_dtype: str, isl: int,
                  osl: int, batch: int, prefill_only: bool = False,
                  chunk: int | None | str = "auto"):
    import jax
    import numpy as np

    from dynamo_tpu.engine.engine import EngineConfig, JaxLlmEngine
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.models.registry import get_family

    family = get_family("llama")
    if model == "tiny":
        cfg = LlamaConfig.tiny()
    else:
        cfg = getattr(LlamaConfig, model)()
    max_len = isl + osl + 32
    block_size = 16 if model != "tiny" else 4
    num_blocks = batch * ((max_len + block_size - 1) // block_size) + 8

    def shaped(k):
        p = family.init_params(cfg, k)
        if quant:
            from dynamo_tpu.ops.quant import quantize_params

            p = quantize_params(p, family.quant_leaves)
        return p

    shapes = jax.eval_shape(shaped, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: np.full(
            s.shape, 1 if np.issubdtype(s.dtype, np.integer) else 0.01,
            dtype=s.dtype,
        ),
        shapes,
    )
    engine = JaxLlmEngine(
        EngineConfig(
            model=cfg,
            num_blocks=num_blocks,
            block_size=block_size,
            max_batch_size=batch,
            max_model_len=max_len,
            # chunked prefill keeps the compile small at ISL 3000 (same
            # rationale as bench.py's accelerator default); callers force
            # ``chunk`` when streamed transfer needs chunks at tiny ISL
            prefill_buckets=(min(512, isl),),
            prefill_chunk_tokens=(
                (min(512, isl) if isl > 512 else None)
                if chunk == "auto" else chunk
            ),
            decode_steps=1 if prefill_only else 8,
            top_logprobs_k=0,
            logit_bias_k=0,
            quantize=quant,
            kv_cache_dtype=kv_dtype,
        ),
        params=params,
    )
    engine.start()
    return engine, cfg


async def run(args: argparse.Namespace) -> dict:
    import jax
    import numpy as np

    from dynamo_tpu.llm.disagg import (
        DisaggConfig,
        DisaggDecodeEngine,
        DisaggRouter,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.llm.protocols.common import (
        Annotated,
        LLMEngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import Context, DistributedRuntime
    from dynamo_tpu.runtime.controlplane.memory import MemoryControlPlane
    from dynamo_tpu.utils.config import RuntimeConfig

    quant = None if args.quant in (None, "none") else args.quant

    # HBM pre-flight (same rationale as bench.py's DoesNotFit check, which
    # shares this construction recipe — keep the two in sync): don't burn
    # minutes of a live-TPU window initializing engines the chip cannot
    # hold, and don't crash the roundup stage — report a clean skip.
    from dynamo_tpu.engine.engine import resolve_kv_cache_dtype
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.models.registry import get_family

    cfg_pre = (LlamaConfig.tiny() if args.model == "tiny"
               else getattr(LlamaConfig, args.model)())
    family = get_family("llama")

    def tree_bytes(tree):
        return sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(tree)
        )

    def shaped(k):
        p = family.init_params(cfg_pre, k)
        if quant:
            from dynamo_tpu.ops.quant import quantize_params

            p = quantize_params(p, family.quant_leaves)
        return p

    param_bytes = tree_bytes(jax.eval_shape(shaped, jax.random.PRNGKey(0)))
    max_len = args.isl + args.osl + 32
    bs = 16 if args.model != "tiny" else 4
    blocks_per_seq = (max_len + bs - 1) // bs
    cache_bytes = tree_bytes(jax.eval_shape(
        lambda: family.cache_init(
            cfg_pre, (args.batch + 2) * blocks_per_seq + 16, bs,
            resolve_kv_cache_dtype(args.kv_dtype),
        )
    ))
    need = 2 * param_bytes + cache_bytes + 2.0e9  # both engines + HLO temps
    try:
        limit = jax.devices()[0].memory_stats().get("bytes_limit")
    except Exception:  # noqa: BLE001 — backends without memory stats
        limit = None
    if limit and need > limit:
        return {
            "skipped": f"{args.model}: 2x params + caches "
                       f"{need/1e9:.1f}GB > HBM {limit/1e9:.1f}GB",
            "model": args.model,
        }

    print(
        f"disagg-bench: building decode + prefill engines "
        f"({args.model}/{quant or 'bf16'})", file=sys.stderr,
    )
    t0 = time.monotonic()
    decode_engine, cfg = _build_engine(
        args.model, quant, args.kv_dtype, args.isl, args.osl, args.batch
    )
    # the PrefillWorker handles one request at a time (its loop awaits each
    # _handle serially), so the prefill engine needs blocks for ~1 sequence
    # — batch-sizing it would waste several GB of the shared chip's HBM
    # chunked even at tiny ISL so the streamed transfer has parts to
    # overlap (chunk = 2 blocks for the tiny smoke; 512 for real models)
    prefill_engine, _ = _build_engine(
        args.model, quant, args.kv_dtype, args.isl, args.osl, batch=2,
        prefill_only=True,
        chunk=min(512, args.isl) if args.isl > 512 else 8,
    )
    print(
        f"disagg-bench: engines up in {time.monotonic()-t0:.1f}s",
        file=sys.stderr,
    )

    MemoryControlPlane.reset_named()
    rt = await DistributedRuntime.create(
        RuntimeConfig(control_plane="memory://disagg-bench")
    )
    rng = np.random.default_rng(0)

    def make_request(tokens: list[int] | None = None) -> dict:
        if tokens is None:
            tokens = rng.integers(10, cfg.vocab_size - 10, size=args.isl).tolist()
        return PreprocessedRequest(
            token_ids=tokens,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=args.osl, ignore_eos=True),
            eos_token_ids=[],
        ).to_wire()

    itls: list[float] = []
    ttfts: list[float] = []
    spans: list[tuple[float, float, int]] = []

    async def drive(gen, req: dict) -> int:
        t0 = time.monotonic()
        ttft = t_last = None
        count = 0
        stream = await gen(Context(req))
        async for item in stream:
            ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
            if ann.data is None or not ann.data.token_ids:
                continue
            t_last = time.monotonic()
            if ttft is None:
                ttft = t_last - t0
                ttfts.append(ttft)
            count += len(ann.data.token_ids)
        if ttft is not None and count > 1:
            itls.append((t_last - t0 - ttft) / (count - 1))
            spans.append((t0 + ttft, t_last, count))
        return count

    def _pctile(xs: list[float], q: float) -> float | None:
        if not xs:
            return None
        s = sorted(xs)
        return s[min(len(s) - 1, round(q * (len(s) - 1)))]

    def ttft_stats() -> dict:
        return {
            "ttft_p50_ms": round(1e3 * _pctile(ttfts, 0.5), 2) if ttfts else None,
            "ttft_p99_ms": round(1e3 * _pctile(ttfts, 0.99), 2) if ttfts else None,
        }

    def phase_stats() -> dict:
        if not spans:
            return {}
        t0g = min(s[0] for s in spans)
        t1g = max(s[1] for s in spans)
        toks = sum(s[2] - 1 for s in spans)
        return {
            "decode_phase_tok_s": (
                round(toks / (t1g - t0g), 2) if t1g > t0g else None
            ),
            "itl_mean_ms": round(1e3 * sum(itls) / len(itls), 2),
        }

    result: dict = {
        "model": args.model,
        "quantize": quant,
        "num_requests": args.requests,
        "isl": args.isl,
        "osl": args.osl,
        "batch": args.batch,
    }
    disagg = prefill_worker = router = None
    disagg2 = decode2 = None
    try:
        # -- aggregated reference: same workload, one engine does both ----
        await drive(decode_engine.generate, make_request())  # warm compiles
        itls.clear(); spans.clear(); ttfts.clear()
        t0 = time.monotonic()
        counts = await asyncio.gather(
            *[drive(decode_engine.generate, make_request())
              for _ in range(args.requests)]
        )
        agg_wall = time.monotonic() - t0
        result["aggregated"] = {
            "wall_s": round(agg_wall, 2),
            "req_s": round(args.requests / agg_wall, 3),
            "tok_s": round(sum(counts) / agg_wall, 2),
            **ttft_stats(),
            **phase_stats(),
        }

        # -- disaggregated: every prefill goes remote ---------------------
        router = DisaggRouter(
            rt, args.model,
            DisaggConfig(max_local_prefill_length=1,
                         max_prefill_queue_size=args.requests + 1),
        )
        queue = PrefillQueue(rt, "bench", "disagg")
        disagg = DisaggDecodeEngine(rt, decode_engine, router, queue)
        await disagg.start()
        prefill_worker = PrefillWorker(rt, prefill_engine, queue)
        prefill_worker.start()

        await drive(disagg.generate, make_request())  # warm prefill engine
        itls.clear(); spans.clear(); ttfts.clear()
        warm_remote = disagg.remote_prefills  # exclude warmup from the count
        t0 = time.monotonic()
        counts = await asyncio.gather(
            *[drive(disagg.generate, make_request())
              for _ in range(args.requests)]
        )
        dis_wall = time.monotonic() - t0
        remote = disagg.remote_prefills - warm_remote
        result["disagg"] = {
            "wall_s": round(dis_wall, 2),
            "req_s": round(args.requests / dis_wall, 3),
            "tok_s": round(sum(counts) / dis_wall, 2),
            # must equal num_requests — a shortfall means a measured request
            # silently fell back to local prefill
            "remote_prefills": remote,
            "all_prefills_remote": remote == args.requests,
            **ttft_stats(),
            **phase_stats(),
        }
        result["disagg_overhead_pct"] = round(
            (dis_wall - agg_wall) / agg_wall * 100, 1
        )

        # -- streamed vs single-shot A/B over the same disagg stack -------
        # (the main disagg section above already ran with the default
        # streaming knob; these two runs pin the worker's mode explicitly)
        ab: dict = {}
        # single-shot first so the worker left running for the fleet section
        # below is the (default-on) streamed one
        for mode_name, mode in (("single_shot", False), ("streamed", True)):
            await prefill_worker.stop()
            prefill_worker = PrefillWorker(
                rt, prefill_engine, queue, stream=mode
            )
            prefill_worker.start()
            base = disagg.stats()
            itls.clear(); spans.clear(); ttfts.clear()
            t0 = time.monotonic()
            await asyncio.gather(
                *[drive(disagg.generate, make_request())
                  for _ in range(args.requests)]
            )
            wall = time.monotonic() - t0
            cur = disagg.stats()
            xfer_s = (cur["disagg_kv_transfer_seconds_total"]
                      - base["disagg_kv_transfer_seconds_total"])
            hidden_s = (cur["disagg_kv_transfer_hidden_seconds_total"]
                        - base["disagg_kv_transfer_hidden_seconds_total"])
            ab[mode_name] = {
                "wall_s": round(wall, 2),
                "kv_parts": (cur["disagg_kv_transfer_parts_total"]
                             - base["disagg_kv_transfer_parts_total"]),
                "transfer_hidden_fraction": (
                    round(hidden_s / xfer_s, 3) if xfer_s > 0 else 0.0
                ),
                **ttft_stats(),
            }
        if ab["streamed"]["ttft_p50_ms"] and ab["single_shot"]["ttft_p50_ms"]:
            ab["ttft_p50_speedup"] = round(
                ab["single_shot"]["ttft_p50_ms"] / ab["streamed"]["ttft_p50_ms"], 3
            )
        result["streamed_ab"] = ab

        # -- routed fleet: 2 decode candidates, unequal overlap + links ---
        # requests share a prefix the "near" candidate already holds; the
        # "far" candidate sits on another slice — the KV-locality/link-cost
        # scorer should send the traffic near.  Link classes are NOT
        # hand-fed (DYN_TRANSFER_HOP stays unset): each worker publishes a
        # TopologyCard and the watcher-discovered map feeds the cost model.
        from dynamo_tpu.llm.kv_router import (
            KvScheduler,
            RadixTree,
            TransferCostModel,
            compute_block_hashes,
        )
        from dynamo_tpu.llm.kv_router.protocols import KvCacheEvent, RouterEvent
        from dynamo_tpu.topology import TopologyWatcher, local_card

        decode2, _ = _build_engine(
            args.model, quant, args.kv_dtype, args.isl, args.osl, args.batch
        )
        disagg2 = DisaggDecodeEngine(rt, decode2, router, queue)
        await disagg2.start()
        shared = rng.integers(10, cfg.vocab_size - 10, size=args.isl // 2).tolist()
        tree = RadixTree()
        tree.apply(RouterEvent(
            worker_id=1,
            event=KvCacheEvent(
                kind="stored", block_hashes=compute_block_hashes(shared, bs)
            ),
        ))
        # discovery: the prefill source shares slice s0 with decode worker 1;
        # decode worker 2 reports slice s1, so the map classifies the
        # prefill→2 pair dcn and the scorer prices its transfers accordingly
        for wid, role, slice_label in (
            (17, "prefill", "s0"), (1, "decode", "s0"), (2, "decode", "s1"),
        ):
            card = local_card(wid, role=role, slice_label=slice_label)
            await rt.plane.kv.put(card.key(), card.to_json())
        topo_watch = TopologyWatcher(rt)
        await topo_watch.start()
        for _ in range(200):
            if len(topo_watch.map.nodes) >= 3:
                break
            await asyncio.sleep(0.01)
        cost_model = TransferCostModel()
        cost_model.attach_topology(topo_watch.map)
        sched = KvScheduler()
        fleet_engines = {1: disagg, 2: disagg2}
        picks = {1: 0, 2: 0}
        itls.clear(); spans.clear(); ttfts.clear()

        async def fleet_one() -> None:
            tokens = shared + rng.integers(
                10, cfg.vocab_size - 10, size=args.isl - len(shared)
            ).tolist()
            hashes = compute_block_hashes(tokens, bs)
            overlap = tree.find_matches(hashes)
            missing = {
                w: len(hashes) - overlap.scores.get(w, 0) for w in (1, 2)
            }
            costs = cost_model.costs([1, 2], missing)
            wid, _ratio = sched.select_worker(
                [1, 2], overlap, len(hashes), transfer_costs=costs
            )
            picks[wid] += 1
            await drive(fleet_engines[wid].generate, make_request(tokens))

        t0 = time.monotonic()
        await asyncio.gather(*[fleet_one() for _ in range(args.requests)])
        fleet_wall = time.monotonic() - t0
        result["fleet"] = {
            "decode_workers": 2,
            "topology_discovered": topo_watch.map.informative(),
            "near": {"worker": 1,
                     "hop": topo_watch.map.inbound_hop(1),
                     "bandwidth_bps": topo_watch.map.pair_bandwidth(17, 1),
                     "overlap_blocks": len(compute_block_hashes(shared, bs)),
                     "picks": picks[1]},
            "far": {"worker": 2,
                    "hop": topo_watch.map.inbound_hop(2),
                    "bandwidth_bps": topo_watch.map.pair_bandwidth(17, 2),
                    "overlap_blocks": 0,
                    "picks": picks[2]},
            "preferred_is_near": picks[1] > picks[2],
            "wall_s": round(fleet_wall, 2),
            **ttft_stats(),
        }
        await topo_watch.stop()
        dev = jax.devices()[0]
        result["platform"] = dev.platform
        result["device_kind"] = dev.device_kind
        result["note"] = (
            "single-chip: both engines share the accelerator, so compute "
            "does not overlap; overhead_pct prices the disagg plumbing "
            "(router/queue/KV transfer/landing), not two-chip speedup"
        )
    finally:
        if prefill_worker is not None:
            await prefill_worker.stop()
        if disagg is not None:
            await disagg.stop()
        if disagg2 is not None:
            await disagg2.stop()
        if router is not None:
            await router.stop()
        await rt.close()
        decode_engine.stop()
        prefill_engine.stop()
        if decode2 is not None:
            decode2.stop()
    return result


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--model", default=None,
                        help="llama config name (default llama32_3b; a "
                        "device measurement, needs a TPU) or 'tiny' (a "
                        "functional run on any backend, stamped with it)")
    parser.add_argument("--quant", default=None,
                        help="int8 or none (default: int8 for real models)")
    parser.add_argument("--kv-dtype", default="bf16")
    parser.add_argument("--isl", type=int, default=None)
    parser.add_argument("--osl", type=int, default=None)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--requests", type=int, default=16)
    parser.add_argument("--out", default="DISAGG_BENCH.json")
    args = parser.parse_args()

    import jax

    if args.model is None:
        args.model = "llama32_3b"
    platform = jax.devices()[0].platform
    if args.model != "tiny" and platform != "tpu":
        parser.error(
            f"--model {args.model} is a device measurement and needs a TPU "
            f"(found platform {platform!r}); say --model tiny for a "
            "functional run"
        )
    if args.quant is None:
        args.quant = "int8" if args.model.startswith("llama3") else "none"
    if args.isl is None:
        args.isl = 3000 if args.model != "tiny" else 24
    if args.osl is None:
        args.osl = 150 if args.model != "tiny" else 8
    if args.model == "tiny":
        args.batch = min(args.batch, 4)
        args.requests = min(args.requests, 6)

    result = asyncio.run(run(args))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
