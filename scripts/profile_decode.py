"""Decode hot-loop phase profile on the current accelerator.

Builds a const-init engine (constant weights, no checkpoint), drives
a fixed batch of greedy requests, and prints one JSON line with per-phase
wall time from the engine's always-on host-phase accounting
(schedule / pack / upload / dispatch / readback / post) plus ITL
and throughput.  Exists to answer "where do the decode milliseconds go":
host<->device round-trips versus compute (what the fused decode_steps>1
path, the overlapped decode pipeline, and upload caching exist to
amortize).

A/B mode (``--ab``) runs the same workload twice — synchronous decode
(``decode_overlap=False``) then the overlapped pipeline — and reports
steps/s plus each mode's per-phase share of decode wall.  Exits nonzero
when overlap regresses throughput below ``--ab-min-speedup`` (default:
any regression fails).  ``readback`` is the host blocked on the device in
both modes; in overlap mode that wait is for the PREVIOUS window and runs
while the next one computes on device.

Mixed A/B mode (``--mixed``) drives a CONTINUOUS ARRIVAL stream — requests
land every ``--arrival-ms`` while earlier ones decode, with chunked prefill
on — twice: the split prefill/decode step, then the ragged unified-batch
step (``unified_batch=True``).  Reports steps/s (scheduler iterations over
wall), the admission-drain count (pipeline drains forced by new-sequence
admission — the sync point the unified step removes; must stay 0 in
unified mode), unified-window count, and per-phase shares.  Exits nonzero
when unified regresses steps/s below ``--mixed-min-speedup``.

``--family`` picks the model family for the mixed A/B: ``llama`` (the
``--model`` LlamaConfig geometry), ``moe`` (Mixtral tiny_moe routed
experts) or ``mla`` (DeepSeek tiny_mla latent attention) — every family
with a registered unified forward.  ``--decode-heavy`` switches the
arrival pattern to one burst plus a mid-decode straggler: the window is
decode lanes wall-to-wall, the regime the packed-lane kernel exists for.

Usage: python scripts/profile_decode.py [--model llama32_1b|tiny]
           [--quant int8] [--isl 256] [--osl 64] [--batch 16]
           [--decode-steps 1] [--overlap 0|1] [--ab]
           [--mixed] [--family llama|moe|mla] [--decode-heavy]
           [--requests 12] [--arrival-ms 50] [--chunk 32]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _family_setup(args: argparse.Namespace):
    """Resolve ``--family`` to (registry key, model config, model label)."""
    from dynamo_tpu.models.llama import LlamaConfig

    family = getattr(args, "family", "llama") or "llama"
    if family == "llama":
        return "llama", getattr(LlamaConfig, args.model)(), args.model
    if family == "moe":
        from dynamo_tpu.models.mixtral import MixtralConfig

        return "mixtral", MixtralConfig.tiny_moe(), "tiny_moe"
    if family == "mla":
        from dynamo_tpu.models.deepseek import DeepseekConfig

        return "deepseek_v2", DeepseekConfig.tiny_mla(), "tiny_mla"
    raise SystemExit(f"unknown --family {family!r} (llama|moe|mla)")


def _decode_phase_shares(phase_ms: dict) -> dict:
    """Each step phase's share of the step loop's wall (0..1)."""
    from dynamo_tpu.engine.engine import STEP_PHASES

    step = {k: phase_ms[k]["total_ms"] for k in STEP_PHASES if k in phase_ms}
    total = sum(step.values())
    if total <= 0:
        return {}
    return {k: round(v / total, 4) for k, v in step.items()}


async def run(args: argparse.Namespace, *, overlap: bool | None = None) -> dict:
    import jax
    import numpy as np

    from dynamo_tpu.engine.engine import EngineConfig, JaxLlmEngine
    from dynamo_tpu.llm.protocols.common import (
        Annotated,
        LLMEngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.registry import get_family
    from dynamo_tpu.models.llama import LlamaConfig

    cfg = getattr(LlamaConfig, args.model)()
    family = get_family("llama")
    max_len = args.isl + args.osl + 16
    block_size = 16
    num_blocks = args.batch * ((max_len + block_size - 1) // block_size) + 8

    def shaped(k):
        p = family.init_params(cfg, k)
        if args.quant and args.quant != "none":
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
            max_batch_size=args.batch,
            max_model_len=max_len,
            prefill_buckets=(args.isl,),
            decode_steps=args.decode_steps,
            top_logprobs_k=0,
            logit_bias_k=0,
            quantize=None if args.quant in (None, "none") else args.quant,
            kv_cache_dtype=args.kv_dtype,
            decode_overlap=overlap,
        ),
        params=params,
    )
    engine.start()
    mode = "overlap" if engine.decode_overlap else "sync"
    print(f"profile: engine up ({args.model}, {mode})", file=sys.stderr)
    rng = np.random.default_rng(0)

    from dynamo_tpu.runtime.engine import Context

    def make_request() -> dict:
        tokens = rng.integers(10, cfg.vocab_size - 10, size=args.isl).tolist()
        return PreprocessedRequest(
            token_ids=tokens,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=args.osl, ignore_eos=True),
            eos_token_ids=[],
        ).to_wire()

    itls: list[float] = []
    started = 0
    all_started = asyncio.Event()

    async def drive(req: dict) -> int:
        nonlocal started
        t0 = time.monotonic()
        ttft = t_last = None
        count = 0
        stream = await engine.generate(Context(req))
        async for item in stream:
            ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
            if ann.data is None or not ann.data.token_ids:
                continue
            t_last = time.monotonic()
            if ttft is None:
                ttft = t_last - t0
                started += 1
                if started == args.batch:
                    all_started.set()
            count += len(ann.data.token_ids)
        if ttft is not None and count > 1:
            itls.append((t_last - t0 - ttft) / (count - 1))
        return count

    t0 = time.monotonic()
    await drive(make_request())  # warmup: compiles
    print(f"profile: warmup {time.monotonic()-t0:.1f}s", file=sys.stderr)
    itls.clear()
    before = engine.stats()
    steps_before = before.get("decode_steps_total", 0)
    # delta the window counters too: cumulative totals would include
    # warmup and not reconcile with the steady-state phase stats
    over_before = before.get("decode_windows_overlapped_total", 0)
    sync_before = before.get("decode_windows_sync_total", 0)

    # Steady-state isolation: phase stats restart once every lane has
    # produced a first token, so prefill interleave doesn't pollute the
    # decode-window attribution (a window's readback otherwise waits on
    # queued prefill programs and bills them to decode).
    async def clear_at_steady():
        await all_started.wait()
        engine.loop_account.reset()

    t0 = time.monotonic()
    results = await asyncio.gather(
        clear_at_steady(), *[drive(make_request()) for _ in range(args.batch)]
    )
    counts = results[1:]
    wall = time.monotonic() - t0
    stats = engine.stats()
    engine.stop()
    dev = jax.devices()[0]
    phase_ms = stats.get("phase_ms", {})
    decode_steps = stats.get("decode_steps_total", 0) - steps_before
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "model": args.model,
        "quant": args.quant,
        "batch": args.batch,
        "isl": args.isl,
        "osl": args.osl,
        "decode_steps": args.decode_steps,
        "overlap": engine.decode_overlap,
        "windows_overlapped": stats.get("decode_windows_overlapped_total", 0) - over_before,
        "windows_sync": stats.get("decode_windows_sync_total", 0) - sync_before,
        "wall_s": round(wall, 2),
        "tok_s": round(sum(counts) / wall, 1),
        "steps_s": round(decode_steps / wall, 2),
        "itl_mean_ms": round(1e3 * sum(itls) / max(len(itls), 1), 2),
        "decode_phase_share": _decode_phase_shares(phase_ms),
        "phase_ms": phase_ms,
    }


async def run_mixed(args: argparse.Namespace, *, unified: bool) -> dict:
    """One continuous-arrival mixed prefill+decode run (chunked prefill on,
    overlap per ``--overlap``/engine default) on the split or the unified
    step.  ``steps_s`` counts DECODE steps (see the inline note below);
    raw scheduler iterations ride along as ``iterations``."""
    import jax
    import numpy as np

    from dynamo_tpu.engine.engine import EngineConfig, JaxLlmEngine
    from dynamo_tpu.llm.protocols.common import (
        Annotated,
        LLMEngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.registry import get_family
    from dynamo_tpu.runtime.engine import Context

    fam_name, cfg, model_label = _family_setup(args)
    family = get_family(fam_name)
    max_len = args.isl + args.osl + 16
    block_size = 16
    num_blocks = args.batch * ((max_len + block_size - 1) // block_size) + 8
    shapes = jax.eval_shape(
        lambda k: family.init_params(cfg, k), jax.random.PRNGKey(0)
    )
    params = jax.tree.map(
        lambda s: np.full(
            s.shape, 1 if np.issubdtype(s.dtype, np.integer) else 0.01,
            dtype=s.dtype,
        ),
        shapes,
    )
    overlap = None if args.overlap is None else bool(args.overlap)
    engine = JaxLlmEngine(
        EngineConfig(
            model=cfg,
            model_family=fam_name,
            num_blocks=num_blocks,
            block_size=block_size,
            max_batch_size=args.batch,
            max_model_len=max_len,
            prefill_buckets=(args.isl,),
            prefill_chunk_tokens=args.chunk,
            top_logprobs_k=0,
            logit_bias_k=0,
            # model-dtype cache in BOTH modes: the unified step auto-disables
            # on narrowed cache dtypes (parity contract), and an A/B must
            # not compare different cache byte counts anyway
            kv_cache_dtype=None,
            decode_overlap=overlap,
            unified_batch=unified,
        ),
        params=params,
    )
    engine.start()
    mode = "unified" if engine.unified_batch else "split"
    print(f"profile: mixed engine up ({model_label}, {mode})", file=sys.stderr)
    rng = np.random.default_rng(0)

    def make_request() -> dict:
        tokens = rng.integers(10, cfg.vocab_size - 10, size=args.isl).tolist()
        return PreprocessedRequest(
            token_ids=tokens,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=args.osl, ignore_eos=True),
            eos_token_ids=[],
        ).to_wire()

    async def drive(req: dict) -> int:
        count = 0
        stream = await engine.generate(Context(req))
        async for item in stream:
            ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
            if ann.data is not None:
                count += len(ann.data.token_ids)
        return count

    # warmup: two OVERLAPPING requests, so the mixed-window buckets (chunk
    # plus live decode lanes) compile here and not mid-measurement
    warm = [asyncio.ensure_future(drive(make_request()))]
    await asyncio.sleep(args.arrival_ms / 1e3)
    warm.append(asyncio.ensure_future(drive(make_request())))
    await asyncio.gather(*warm)
    before = engine.stats()
    engine.loop_account.reset()
    t0 = time.monotonic()
    tasks = []
    if getattr(args, "decode_heavy", False):
        # decode-heavy packing scenario: admit everything in one burst so
        # the steady-state window is decode lanes wall-to-wall (the regime
        # the packed-lane kernel compresses from one block per lane to
        # dense rows), then one straggler lands mid-decode to prove a
        # chunk can still ride a packed decode window
        for _ in range(args.requests - 1):
            tasks.append(asyncio.ensure_future(drive(make_request())))
        await asyncio.sleep(args.arrival_ms / 1e3)
        tasks.append(asyncio.ensure_future(drive(make_request())))
    else:
        for _ in range(args.requests):
            tasks.append(asyncio.ensure_future(drive(make_request())))
            await asyncio.sleep(args.arrival_ms / 1e3)
    counts = await asyncio.gather(*tasks)
    wall = time.monotonic() - t0
    stats = engine.stats()
    engine.stop()
    dev = jax.devices()[0]
    # decode-step cadence, not scheduler iterations: a unified iteration
    # serves prefill AND decode in one window, so raw iteration counts
    # would under-credit exactly the merge being measured
    steps = stats["decode_steps_total"] - before["decode_steps_total"]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "model": model_label,
        "family": getattr(args, "family", "llama") or "llama",
        "mode": mode,
        "decode_heavy": bool(getattr(args, "decode_heavy", False)),
        "iterations": (
            stats["iterations_total"] - before["iterations_total"]
        ),
        "batch": args.batch,
        "isl": args.isl,
        "osl": args.osl,
        "chunk": args.chunk,
        "requests": args.requests,
        "arrival_ms": args.arrival_ms,
        "overlap": engine.decode_overlap,
        "wall_s": round(wall, 2),
        "tok_s": round(sum(counts) / wall, 1),
        "steps_s": round(steps / wall, 2),
        "admission_drains": (
            stats["admission_drains_total"] - before["admission_drains_total"]
        ),
        "windows_unified": (
            stats["decode_windows_unified_total"]
            - before["decode_windows_unified_total"]
        ),
        "windows_overlapped": (
            stats["decode_windows_overlapped_total"]
            - before["decode_windows_overlapped_total"]
        ),
        "windows_sync": (
            stats["decode_windows_sync_total"]
            - before["decode_windows_sync_total"]
        ),
        "decode_phase_share": _decode_phase_shares(stats.get("phase_ms", {})),
        "phase_ms": stats.get("phase_ms", {}),
    }


async def amain(args: argparse.Namespace) -> tuple[int, dict]:
    """Run the requested profile; returns (exit_code, result).  Importable
    so the tier-1 smoke tests can drive the A/Bs in-process."""
    if getattr(args, "mixed", False):
        split = await run_mixed(args, unified=False)
        uni = await run_mixed(args, unified=True)
        speedup = uni["steps_s"] / split["steps_s"] if split["steps_s"] else 0.0
        result = {
            "mixed": True,
            "model": uni["model"],
            "family": uni["family"],
            "decode_heavy": uni["decode_heavy"],
            "batch": args.batch,
            "isl": args.isl,
            "osl": args.osl,
            "chunk": args.chunk,
            "requests": args.requests,
            "arrival_ms": args.arrival_ms,
            "unified_speedup_steps_s": round(speedup, 3),
            "unified_speedup_tok_s": round(
                uni["tok_s"] / split["tok_s"], 3
            ) if split["tok_s"] else 0.0,
            "admission_drains_split": split["admission_drains"],
            "admission_drains_unified": uni["admission_drains"],
            "windows_unified": uni["windows_unified"],
            "split": split,
            "unified": uni,
        }
        rc = 0
        if speedup < args.mixed_min_speedup:
            print(
                f"profile: unified REGRESSED steps/s ({speedup:.3f}x < "
                f"{args.mixed_min_speedup}x)", file=sys.stderr,
            )
            rc = 1
        if uni["windows_unified"] and uni["admission_drains"]:
            print(
                "profile: unified mode still drained on admission "
                f"({uni['admission_drains']} drains)", file=sys.stderr,
            )
            rc = 1
        return rc, result
    if not args.ab:
        overlap = None if args.overlap is None else bool(args.overlap)
        return 0, await run(args, overlap=overlap)

    sync = await run(args, overlap=False)
    over = await run(args, overlap=True)
    speedup = over["tok_s"] / sync["tok_s"] if sync["tok_s"] else 0.0
    result = {
        "ab": True,
        "model": args.model,
        "batch": args.batch,
        "isl": args.isl,
        "osl": args.osl,
        "decode_steps": args.decode_steps,
        "overlap_speedup_tok_s": round(speedup, 3),
        "overlap_speedup_steps_s": round(
            over["steps_s"] / sync["steps_s"], 3
        ) if sync["steps_s"] else 0.0,
        "readback_share_sync": sync["decode_phase_share"].get("readback", 0.0),
        "readback_share_overlap": over["decode_phase_share"].get("readback", 0.0),
        "sync": sync,
        "overlap": over,
    }
    rc = 0
    if speedup < args.ab_min_speedup:
        print(
            f"profile: overlap REGRESSED throughput ({speedup:.3f}x < "
            f"{args.ab_min_speedup}x)", file=sys.stderr,
        )
        rc = 1
    return rc, result


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="llama32_1b",
                        help="LlamaConfig classmethod name (llama32_1b, tiny, ...)")
    parser.add_argument("--quant", default="none")
    parser.add_argument("--kv-dtype", default="bf16")
    parser.add_argument("--isl", type=int, default=256)
    parser.add_argument("--osl", type=int, default=64)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--decode-steps", type=int, default=1)
    parser.add_argument("--overlap", type=int, choices=(0, 1), default=None,
                        help="force the overlapped pipeline on/off "
                             "(default: engine default / DYN_DECODE_OVERLAP)")
    parser.add_argument("--ab", action="store_true",
                        help="run sync AND overlap, report both + speedup; "
                             "exit nonzero if overlap regresses throughput")
    parser.add_argument("--ab-min-speedup", type=float, default=1.0,
                        help="minimum overlap/sync tok_s ratio for --ab to "
                             "exit 0 (1.0 = fail on any regression)")
    parser.add_argument("--mixed", action="store_true",
                        help="continuous-arrival mixed prefill+decode A/B: "
                             "split step vs ragged unified-batch step; exit "
                             "nonzero if unified regresses steps/s or still "
                             "drains on admission")
    parser.add_argument("--mixed-min-speedup", type=float, default=1.0,
                        help="minimum unified/split steps_s ratio for "
                             "--mixed to exit 0")
    parser.add_argument("--family", default="llama",
                        choices=("llama", "moe", "mla"),
                        help="--mixed: model family (llama uses --model; "
                             "moe/mla use the tiny Mixtral/DeepSeek "
                             "geometries)")
    parser.add_argument("--decode-heavy", action="store_true",
                        help="--mixed: burst admission + one mid-decode "
                             "straggler — windows are packed decode lanes "
                             "nearly wall-to-wall")
    parser.add_argument("--requests", type=int, default=12,
                        help="--mixed: requests in the arrival stream")
    parser.add_argument("--arrival-ms", type=int, default=50,
                        help="--mixed: inter-arrival gap (tight enough that "
                             "admissions land while earlier requests decode)")
    parser.add_argument("--chunk", type=int, default=32,
                        help="--mixed: prefill_chunk_tokens for both modes")
    parser.add_argument("--out", default=None,
                        help="also write the JSON result to this path")
    args = parser.parse_args()
    rc, result = asyncio.run(amain(args))
    # shared provenance header (dynamo_tpu/bench/perfgate.py): lets the perf
    # gate refuse to diff artifacts from an incompatible schema generation
    from dynamo_tpu.bench.perfgate import provenance_stamp

    result["provenance"] = provenance_stamp()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    print(json.dumps(result))
    return rc


if __name__ == "__main__":
    sys.exit(main())
