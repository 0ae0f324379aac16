"""End-to-end request tracing (the acceptance path of the observability
subsystem): one request driven through frontend → KV router → push dispatch
→ worker ingress → JAX engine on the CPU backend must produce ONE trace —
the client-supplied ``x-request-id`` — whose span tree covers every layer,
whose JSONL and Chrome-trace exports parse, and whose metric surfaces
(frontend TTFT/ITL histograms, dyn_worker engine step gauges) are live."""

import asyncio
import json
import uuid
from pathlib import Path

import httpx

from dynamo_tpu.components.metrics_service import MetricsService
from dynamo_tpu.observability import SpanRecorder, get_recorder, set_recorder
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.client import RouterMode
from dynamo_tpu.runtime.controlplane.memory import MemoryControlPlane
from dynamo_tpu.serve import serve_frontend, serve_worker
from dynamo_tpu.utils.config import RuntimeConfig

MODEL_DIR = str(Path(__file__).parent.parent / "data" / "tiny-chat-model")

# spans the tree must contain, with the layer that records each
EXPECTED_SPANS = {
    "http.request": "frontend",
    "preprocess": "preprocessor",
    "router.schedule": "router",
    "dispatch": "frontend",
    "worker.handle": "worker",
    "engine.queue": "engine",
    "engine.prefill": "engine",
    "engine.decode": "engine",
}


async def wait_for_model(client, name, timeout=10.0):
    for _ in range(int(timeout / 0.1)):
        r = await client.get("/v1/models")
        if name in [m["id"] for m in r.json().get("data", [])]:
            return
        await asyncio.sleep(0.1)
    raise TimeoutError(f"model {name} never appeared")


async def test_span_tree_end_to_end(tmp_path):
    set_recorder(SpanRecorder(max_spans=8192))
    MemoryControlPlane.reset_named()
    rt = await DistributedRuntime.create(
        RuntimeConfig(control_plane="memory://trace-e2e")
    )
    service = watcher = worker = metrics_svc = None
    rid = f"trace-e2e-{uuid.uuid4().hex[:12]}"
    try:
        worker = await serve_worker(
            rt, MODEL_DIR, model_name="tiny", engine_kind="jax",
            num_blocks=64, max_batch_size=4, max_model_len=128,
            prefill_buckets=(32, 64),
        )
        service, watcher = await serve_frontend(
            rt, host="127.0.0.1", port=0, router_mode=RouterMode.KV
        )
        metrics_svc = MetricsService(
            rt.namespace().component("backend"), host="127.0.0.1", port=0
        )
        await metrics_svc.start()
        async with httpx.AsyncClient(
            base_url=f"http://127.0.0.1:{service.port}"
        ) as client:
            await wait_for_model(client, "tiny")
            async with client.stream(
                "POST",
                "/v1/chat/completions",
                headers={"x-request-id": rid},
                json={
                    "model": "tiny",
                    "messages": [{"role": "user", "content": "trace me please"}],
                    "max_tokens": 8,
                    "stream": True,
                },
                timeout=120,
            ) as r:
                assert r.status_code == 200
                # the id is echoed on the streaming response too
                assert r.headers["x-request-id"] == rid
                async for _ in r.aiter_bytes():
                    pass

            rec = get_recorder()
            # the engine's decode span and the root span land within a beat
            # of the stream closing; poll instead of sleeping fixed time
            for _ in range(100):
                names = {s.name for s in rec.spans_for(rid)}
                if set(EXPECTED_SPANS) <= names:
                    break
                await asyncio.sleep(0.05)
            spans = rec.spans_for(rid)
            names = {s.name for s in spans}
            assert set(EXPECTED_SPANS) <= names, f"missing: {set(EXPECTED_SPANS) - names}"

            # one trace, a well-formed tree, non-negative durations
            assert {s.trace_id for s in spans} == {rid}
            by_id = {s.span_id: s for s in spans}
            roots = [s for s in spans if s.parent_span_id is None]
            assert [r2.name for r2 in roots] == ["http.request"]
            for s in spans:
                assert s.duration_s >= 0.0, s
                assert s.component == EXPECTED_SPANS.get(s.name, s.component)
                if s.parent_span_id is not None:
                    assert s.parent_span_id in by_id, f"dangling parent: {s}"
            # layering: engine spans hang under the worker, the worker under
            # the frontend's dispatch
            worker_span = next(s for s in spans if s.name == "worker.handle")
            assert by_id[worker_span.parent_span_id].name == "dispatch"
            for s in spans:
                if s.name.startswith("engine."):
                    assert by_id[s.parent_span_id].name == "worker.handle"

            # exports parse
            jl = tmp_path / "spans.jsonl"
            n = rec.export_jsonl(str(jl), rid)
            assert n == len(spans)
            parsed = [json.loads(line) for line in jl.read_text().splitlines()]
            assert {p["trace_id"] for p in parsed} == {rid}
            ct = tmp_path / "chrome.json"
            rec.export_chrome_trace(str(ct), rid)
            doc = json.loads(ct.read_text())
            assert sum(1 for e in doc["traceEvents"] if e["ph"] == "X") == len(spans)

            # lifecycle summary: every phase non-negative, tokens counted
            summary = rec.summary(rid)
            assert summary["status"] == "success"
            assert summary["queue_wait_s"] >= 0
            assert summary["prefill_s"] > 0
            assert summary["decode_s"] > 0
            assert summary["ttft_s"] is not None and summary["ttft_s"] >= 0
            assert summary["tokens_out"] == 8

            # the aggregate keeps what the ring forgets: every recorded span
            # counts under (component, name), and the duration the frontend
            # observes without a span (engine emit on the device thread ->
            # chunk written, the stamp having crossed the data plane on the
            # wire)
            series = rec.aggregate()["series"]
            for name, component in EXPECTED_SPANS.items():
                assert series[component][name]["count"] >= 1, name
            assert series["frontend"]["http.emit_lag"]["count"] == 8
            assert 0 <= series["frontend"]["http.emit_lag"]["max_s"] < 5.0
            pre = next(s for s in spans if s.name == "preprocess")
            assert by_id[pre.parent_span_id].name == "http.request"
            assert pre.attrs["prompt_tokens"] > 0
            # the worker's stats() carries the same aggregate
            assert "engine.queue" in worker.engine.stats()["spans"]["series"]["engine"]

            # frontend /metrics: TTFT + ITL histograms observed samples
            # (8 streamed tokens -> 1 TTFT sample, 7 ITL samples)
            r = await client.get("/metrics")
            text = r.text
            assert (
                'dyn_llm_http_service_time_to_first_token_seconds_count{model="tiny"} 1.0'
                in text
            )
            assert (
                'dyn_llm_http_service_inter_token_latency_seconds_count{model="tiny"} 7.0'
                in text
            )
            assert (
                'dyn_llm_http_service_output_sequence_tokens_count{model="tiny"} 1.0'
                in text
            )

        # engine step gauges reach the dyn_worker surface through the
        # load-metrics publisher (1 Hz) → aggregator → Prometheus
        label = f"{worker.service.instance.instance_id:x}"
        async with httpx.AsyncClient() as client:
            for _ in range(100):
                r = await client.get(
                    f"http://127.0.0.1:{metrics_svc.port}/metrics"
                )
                if f'dyn_worker_batch_occupancy_perc{{worker="{label}"}}' in r.text:
                    break
                await asyncio.sleep(0.1)
            text = r.text
            assert f'dyn_worker_batch_occupancy_perc{{worker="{label}"}}' in text
            assert f'dyn_worker_requests_running{{worker="{label}"}}' in text
            assert f'dyn_worker_preemptions{{worker="{label}"}} 0.0' in text
            assert f'dyn_worker_cache_usage_perc{{worker="{label}"}}' in text
    finally:
        if metrics_svc:
            await metrics_svc.stop()
        if watcher:
            await watcher.stop()
        if service:
            await service.stop()
        if worker:
            await worker.shutdown()
        await rt.close()


async def test_request_id_minted_and_echoed_without_header():
    """No client id: the frontend mints one, echoes it on unary and error
    responses, and the trace exists under the minted id."""
    set_recorder(SpanRecorder(max_spans=2048))
    MemoryControlPlane.reset_named()
    rt = await DistributedRuntime.create(
        RuntimeConfig(control_plane="memory://trace-mint")
    )
    service = watcher = worker = None
    try:
        worker = await serve_worker(rt, MODEL_DIR, model_name="tiny", engine_kind="echo")
        service, watcher = await serve_frontend(rt, host="127.0.0.1", port=0)
        async with httpx.AsyncClient(
            base_url=f"http://127.0.0.1:{service.port}"
        ) as client:
            await wait_for_model(client, "tiny")
            r = await client.post(
                "/v1/chat/completions",
                json={"model": "tiny", "messages": [{"role": "user", "content": "hi"}]},
                timeout=30,
            )
            assert r.status_code == 200
            rid = r.headers.get("x-request-id")
            assert rid
            spans = get_recorder().spans_for(rid)
            assert "http.request" in {s.name for s in spans}
            root = next(s for s in spans if s.name == "http.request")
            assert root.status == "success"
            assert root.attrs["tokens_out"] >= 1

            # error responses carry the id too (unknown model -> 404)
            r = await client.post(
                "/v1/chat/completions",
                headers={"x-request-id": "err-echo-1"},
                json={"model": "nope", "messages": [{"role": "user", "content": "x"}]},
                timeout=30,
            )
            assert r.status_code == 404
            assert r.headers["x-request-id"] == "err-echo-1"
    finally:
        if watcher:
            await watcher.stop()
        if service:
            await service.stop()
        if worker:
            await worker.shutdown()
        await rt.close()
