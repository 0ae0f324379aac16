"""dynamo-tpu run — the single-command launcher.

``dynamo-tpu run in=<http|text|batch:FILE|none> out=<jax|echo|mocker|dyn>``
(reference: launch/dynamo-run/src/{opt.rs,lib.rs} ``dynamo run in=X out=Y``).

- ``out=jax|echo|mocker`` spawns the in-process engine and (unless
  ``in=none``) a frontend in the same process over the memory control plane.
- ``out=dyn`` runs frontend-only against a dynctl control plane; workers
  register themselves from other processes (``in=none out=jax`` there).

Examples:
  dynamo-tpu run in=http out=jax --model-path /models/llama-3-8b --port 8080
  dynamo-tpu run in=text out=echo --model-path tests/data/tiny-chat-model
  dynamo-tpu run in=none out=jax --model-path ... --control-plane 127.0.0.1:2379
  dynamo-tpu run in=http out=dyn --control-plane 127.0.0.1:2379 --router-mode kv
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from dynamo_tpu.runtime.client import RouterMode
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.utils.config import RuntimeConfig
from dynamo_tpu.utils.logging import configure_logging, get_logger

logger = get_logger("cli.run")


def parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(prog="dynamo-tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser("run", help="serve a model")
    run.add_argument("io", nargs="*", help="in=<http|text|batch:FILE|none> out=<jax|echo|mocker|dyn>")
    run.add_argument("--model-path", help="local model dir (tokenizer/config/weights)")
    run.add_argument("--model-name", help="served model name (default: dir name)")
    run.add_argument("--host", default="0.0.0.0")
    run.add_argument("--port", type=int, default=8080)
    run.add_argument("--control-plane", default=None, help="dynctl host:port (default: in-process memory)")
    run.add_argument("--namespace", default="dynamo")
    run.add_argument("--component", default="backend")
    run.add_argument("--endpoint", default="generate")
    run.add_argument("--router-mode", choices=[m.value for m in RouterMode], default="round_robin")
    run.add_argument("--request-template", default=None,
                     help="JSON file with default model/temperature/max_tokens")
    run.add_argument("--num-blocks", type=int, default=256, help="KV cache blocks in HBM")
    run.add_argument("--kv-block-size", type=int, default=16)
    run.add_argument("--max-batch-size", type=int, default=8)
    run.add_argument("--context-length", type=int, default=None)
    run.add_argument("--tensor-parallel-size", type=int, default=1)
    run.add_argument("--warmup", action="store_true",
                     help="pre-compile every serving program before "
                          "registering (in parallel, through the persistent "
                          "compile cache: JAX_COMPILATION_CACHE_DIR, else "
                          "<checkout>/.jax_cache)")
    run.add_argument("--speculative", choices=["ngram"], default=None,
                     help="speculative decoding (ngram = prompt-lookup "
                          "self-drafting with exact greedy verification)")
    run.add_argument("--spec-tokens", type=int, default=4,
                     help="draft tokens verified per step")
    run.add_argument("--spec-ngram", type=int, default=2,
                     help="lookup n-gram width for ngram drafting")
    run.add_argument("--kv-cache-dtype", choices=["fp8", "bf16", "f32"],
                     default=None,
                     help="KV cache storage dtype (fp8 halves KV bytes; "
                          "default: model dtype)")
    run.add_argument("--quantize", choices=["int8"], default=None,
                     help="weight-only quantization (all served families; "
                          "halves decode HBM traffic — the TPU analog of "
                          "the reference's FP8 serving)")
    run.add_argument("--host-offload-blocks", type=int, default=0,
                     help="G2 host-DRAM KV tier size (0 = off): HBM "
                          "evictions offload here and restore on prefix hit")
    run.add_argument("--disk-offload-blocks", type=int, default=0,
                     help="G3 SSD KV tier size (needs --host-offload-blocks)")
    run.add_argument("--remote-kv-store", default=None, metavar="HOST:PORT",
                     help="G4 remote KV tier: a block-store server "
                          "(python -m dynamo_tpu.llm.block_manager.remote); "
                          "bottom-tier evictions cascade there over DCN")
    args = parser.parse_args(argv)

    args.input, args.output = "http", "jax"
    for tok in args.io:
        if tok.startswith("in="):
            args.input = tok[3:]
        elif tok.startswith("out="):
            args.output = tok[4:]
        else:
            parser.error(f"unrecognized positional {tok!r} (want in=... / out=...)")
    return args


async def _run(args) -> int:
    configure_logging()
    if args.output == "jax":
        from dynamo_tpu.parallel.mesh import device_summary
        from dynamo_tpu.utils.compile_cache import ensure_compile_cache

        logger.info("persistent compile cache: %s", ensure_compile_cache())
        logger.info("jax devices: %s", json.dumps(device_summary()))
    control_plane = args.control_plane or "memory"
    runtime = await DistributedRuntime.create(
        RuntimeConfig(control_plane=control_plane, namespace=args.namespace)
    )
    from dynamo_tpu.serve import serve_frontend, serve_worker

    worker = None
    if args.output in ("jax", "echo", "mocker"):
        if not args.model_path:
            print("error: --model-path required for local engines", file=sys.stderr)
            return 2
        overrides = {}
        if args.output == "jax":
            overrides = dict(
                num_blocks=args.num_blocks,
                max_batch_size=args.max_batch_size,
            )
            if args.context_length:
                overrides["max_model_len"] = args.context_length
            if args.tensor_parallel_size > 1:
                from dynamo_tpu.parallel.mesh import MeshConfig

                overrides["mesh"] = MeshConfig(tp=args.tensor_parallel_size)
            if args.warmup:
                overrides["warmup"] = True
            if args.quantize:
                overrides["quantize"] = args.quantize
            if args.kv_cache_dtype:
                overrides["kv_cache_dtype"] = args.kv_cache_dtype
            if args.speculative:
                overrides["speculative"] = args.speculative
                overrides["spec_tokens"] = args.spec_tokens
                overrides["spec_ngram"] = args.spec_ngram
            if args.host_offload_blocks:
                overrides["host_offload_blocks"] = args.host_offload_blocks
            if args.disk_offload_blocks:
                overrides["disk_offload_blocks"] = args.disk_offload_blocks
            if args.remote_kv_store:
                overrides["remote_store_addr"] = args.remote_kv_store
        worker = await serve_worker(
            runtime,
            args.model_path,
            model_name=args.model_name,
            namespace=args.namespace,
            component=args.component,
            endpoint=args.endpoint,
            engine_kind=args.output,
            **overrides,
        )
    elif args.output != "dyn":
        print(f"error: unknown out={args.output}", file=sys.stderr)
        return 2

    try:
        if args.input == "http":
            service, watcher = await serve_frontend(
                runtime,
                host=args.host,
                port=args.port,
                router_mode=RouterMode(args.router_mode),
                request_template=args.request_template,
            )
            print(f"listening on http://{args.host}:{service.port}/v1", file=sys.stderr)
            await runtime.wait_for_shutdown()
            await watcher.stop()
            await service.stop()
        elif args.input == "text" or args.input.startswith("batch:"):
            await _run_local_io(runtime, args)
        elif args.input == "none":
            print("worker running; ctrl-c to stop", file=sys.stderr)
            await runtime.wait_for_shutdown()
        else:
            print(f"error: unknown in={args.input}", file=sys.stderr)
            return 2
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        if worker is not None:
            await worker.shutdown()
        await runtime.close()
    return 0


async def _run_local_io(runtime, args) -> None:
    """in=text REPL / in=batch:file one-shot, through the full pipeline."""
    from dynamo_tpu.llm.http.service import ModelManager
    from dynamo_tpu.llm.discovery import ModelWatcher
    from dynamo_tpu.llm.protocols.aggregator import aggregate_chat_stream
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.engine import Context

    manager = ModelManager()
    watcher = ModelWatcher(runtime, manager, router_mode=RouterMode(args.router_mode))
    await watcher.start()
    for _ in range(100):
        if manager.model_names():
            break
        await asyncio.sleep(0.05)
    names = manager.model_names()
    if not names:
        print("no models registered", file=sys.stderr)
        return
    model = names[0]
    engine = manager.chat_engines[model]

    async def ask(prompt: str) -> str:
        req = ChatCompletionRequest.model_validate(
            {"model": model, "messages": [{"role": "user", "content": prompt}]}
        )
        stream = await engine.generate(Context(req))

        async def data_only():
            async for ann in stream:
                if not ann.is_annotation() and ann.data is not None:
                    yield ann.data

        response = await aggregate_chat_stream(data_only())
        return response.choices[0].message.content if response.choices else ""

    if args.input == "text":
        print(f"interactive mode, model={model}; empty line exits", file=sys.stderr)
        loop = asyncio.get_running_loop()
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            line = line.strip()
            if not line:
                break
            print(await ask(line))
    else:
        path = args.input[len("batch:"):]
        with open(path) as f:
            prompts = [json.loads(l)["prompt"] if l.strip().startswith("{") else l.strip()
                       for l in f if l.strip()]
        for prompt in prompts:
            print(json.dumps({"prompt": prompt, "response": await ask(prompt)}))
    await watcher.stop()


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cmd == "run":
        return asyncio.run(_run(args))
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
