"""True cross-process disaggregation: the prefill worker runs in a separate
OS process, connected through the dynctl control plane; KV blocks ship over
the TCP transfer plane and the decode-side output must equal single-engine
greedy decoding bit-for-bit (the distributed mode the reference runs with
etcd+NATS+NIXL, SURVEY.md §3.4)."""

import asyncio
import os
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.llm.disagg import (
    DisaggConfig,
    DisaggDecodeEngine,
    DisaggRouter,
    PrefillQueue,
)
from dynamo_tpu.llm.protocols.common import (
    Annotated,
    LLMEngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig, init_params
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.controlplane.server import ControlPlaneServer
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.utils.config import RuntimeConfig

from tests.engine.test_jax_engine import greedy_reference

PREFILL_WORKER_SCRIPT = textwrap.dedent(
    """
    import asyncio, os, sys

    os.environ["JAX_PLATFORMS"] = "cpu"

    async def main():
        import jax

        from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
        from dynamo_tpu.llm.disagg import PrefillQueue, PrefillWorker
        from dynamo_tpu.models.llama import LlamaConfig, init_params
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.utils.config import RuntimeConfig

        control_plane = sys.argv[1]
        cfg = LlamaConfig.tiny()
        engine = JaxLlmEngine(
            EngineConfig(
                model=cfg, num_blocks=64, block_size=4, max_batch_size=4,
                prefill_buckets=(16, 32), max_model_len=64,
            ),
            params=init_params(cfg, jax.random.PRNGKey(0)),
        )
        engine.start()
        rt = await DistributedRuntime.create(RuntimeConfig(control_plane=control_plane))
        queue = PrefillQueue(rt, "ns", "backend")
        worker = PrefillWorker(rt, engine, queue)
        worker.start()
        print("PREFILL_READY", flush=True)
        await asyncio.sleep(3600)

    asyncio.run(main())
    """
)


@pytest.mark.integration
@pytest.mark.slow
async def test_cross_process_disagg_exactness(tmp_path):
    server = ControlPlaneServer(port=0)
    await server.start()
    address = f"127.0.0.1:{server.port}"

    repo_root = str(Path(__file__).parent.parent.parent)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    script = tmp_path / "prefill_worker.py"
    script.write_text(PREFILL_WORKER_SCRIPT)
    stderr_path = tmp_path / "prefill_worker.stderr"
    with open(stderr_path, "wb") as stderr_file:
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(script), address,
            stdout=asyncio.subprocess.PIPE, stderr=stderr_file, env=env,
        )
    rt = disagg = None
    decode_engine = None
    try:
        try:
            line = await asyncio.wait_for(proc.stdout.readline(), 120)
        except asyncio.TimeoutError:
            raise AssertionError(
                "worker never came up (timeout)\n"
                f"stderr tail:\n{stderr_path.read_text()[-3000:]}"
            ) from None
        assert b"PREFILL_READY" in line, (
            f"worker never came up: stdout={line!r}\n"
            f"stderr tail:\n{stderr_path.read_text()[-3000:]}"
        )

        cfg = LlamaConfig.tiny()
        decode_engine = JaxLlmEngine(
            EngineConfig(
                model=cfg, num_blocks=64, block_size=4, max_batch_size=4,
                prefill_buckets=(16, 32), max_model_len=64,
            ),
            params=init_params(cfg, jax.random.PRNGKey(0)),
        )
        decode_engine.start()
        rt = await DistributedRuntime.create(RuntimeConfig(control_plane=address))
        router = DisaggRouter(rt, "tiny", DisaggConfig(max_local_prefill_length=4))
        queue = PrefillQueue(rt, "ns", "backend")
        disagg = DisaggDecodeEngine(rt, decode_engine, router, queue)
        await disagg.start()

        prompt = list(range(3, 13))  # 10 tokens > threshold → remote prefill
        wire = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(use_greedy=True, top_logprobs=2),
            stop=StopConditions(max_tokens=6),
            eos_token_ids=[1],
        ).to_wire()
        stream = await disagg.generate(Context(wire))
        tokens, logprob_count = [], 0
        async for item in stream:
            ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
            if ann.data is not None:
                tokens.extend(ann.data.token_ids)
                if ann.data.logprobs:
                    logprob_count += len(ann.data.logprobs)

        ref = greedy_reference(prompt, 6)
        assert tokens == ref, f"cross-process disagg {tokens} != reference {ref}"
        assert disagg.remote_prefills == 1
        assert logprob_count == len(tokens)  # logprobs crossed the boundary
        # decode engine freed everything after the request finished
        for _ in range(100):
            if decode_engine.allocator.used_blocks == 0:
                break
            await asyncio.sleep(0.02)
        assert decode_engine.allocator.used_blocks == 0
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        if disagg is not None:
            await disagg.stop()
        if decode_engine is not None:
            decode_engine.stop()
        if rt is not None:
            await rt.close()
        await server.stop()
