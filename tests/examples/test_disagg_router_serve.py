"""The runnable disagg_router example, end to end: one orchestrator command
brings up frontend + decode + prefill as separate OS processes under the
SDK supervisor, and a streaming chat completion flows through the whole
stack (reference deployment shape: examples/llm/graphs/disagg_router.py
served via `dynamo serve`)."""

import asyncio
import os
import signal
import sys
from pathlib import Path

import httpx
import pytest

from tests.conftest import free_port

REPO_ROOT = Path(__file__).parent.parent.parent
MODEL_DIR = REPO_ROOT / "tests" / "data" / "tiny-chat-model"


@pytest.mark.integration
@pytest.mark.slow
async def test_disagg_router_serve_streams_tokens(tmp_path):
    port = free_port()
    env = dict(os.environ)
    env.update(
        PYTHONPATH=str(REPO_ROOT) + os.pathsep + env.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    stderr_path = tmp_path / "orchestrator.stderr"
    with open(stderr_path, "wb") as stderr_file:
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "examples.llm.disagg_router_serve",
            "--model", str(MODEL_DIR),
            "--port", str(port),
            # tiny threshold: the test prompt is longer, so prefill MUST
            # flow through the separate prefill worker process
            "--max-local-prefill-length", "4",
            cwd=str(REPO_ROOT),
            stdout=stderr_file, stderr=stderr_file, env=env,
        )

    def stderr_tail() -> str:
        try:
            return stderr_path.read_text()[-4000:]
        except OSError:
            return "<unreadable>"

    try:
        async with httpx.AsyncClient(
            base_url=f"http://127.0.0.1:{port}"
        ) as client:
            # engines compile on CPU before the model registers — poll
            for _ in range(240):
                if proc.returncode is not None:
                    raise AssertionError(
                        f"orchestrator died rc={proc.returncode}\n{stderr_tail()}"
                    )
                try:
                    r = await client.get("/v1/models")
                    if any(m["id"] == "tiny" for m in r.json()["data"]):
                        break
                except httpx.HTTPError:
                    pass
                await asyncio.sleep(0.5)
            else:
                raise AssertionError(
                    f"model never registered\n{stderr_tail()}"
                )

            content = ""
            async with client.stream(
                "POST", "/v1/chat/completions",
                json={
                    "model": "tiny",
                    "stream": True,
                    "max_tokens": 8,
                    "messages": [
                        {"role": "user", "content": "hello streaming world"}
                    ],
                },
                timeout=120,
            ) as resp:
                assert resp.status_code == 200, await resp.aread()
                async for line in resp.aiter_lines():
                    if not line.startswith("data:"):
                        continue
                    payload = line[len("data:"):].strip()
                    if payload == "[DONE]":
                        break
                    import json

                    chunk = json.loads(payload)
                    for choice in chunk.get("choices", []):
                        content += choice.get("delta", {}).get("content") or ""
            assert content, f"no streamed content\n{stderr_tail()}"
    finally:
        if proc.returncode is None:
            proc.send_signal(signal.SIGTERM)
            try:
                await asyncio.wait_for(proc.wait(), 30)
            except asyncio.TimeoutError:
                proc.kill()
                await proc.wait()
