"""The open-loop client: one process, one event loop, streamed
``/v1/chat/completions`` over real HTTP, every request sent at its due
instant and timed from it.  Never imports JAX."""

from __future__ import annotations

import asyncio
import gc
import json
import time

import aiohttp

from benchmark import traffic


async def _one(session, url, model, request, filled, t0, record, deadline):
    delay = t0 + request["due"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    body = {
        "model": model, "messages": traffic.messages(request, filled),
        "max_tokens": request["output_len"], "temperature": 0.0, "stream": True,
        "stream_options": {"include_usage": True}, "ext": {"ignore_eos": True},
        # the served token's own log-probability rides along (no alternatives:
        # asking for top_logprobs would move the lane to the synchronous path)
        "logprobs": True,
    }
    if request.get("probe"):
        body["top_logprobs"] = int(request["probe"])
    record["sent"] = time.monotonic() - t0
    try:
        timeout = aiohttp.ClientTimeout(total=max(1.0, deadline - time.monotonic()))
        async with session.post(url, json=body, timeout=timeout) as resp:
            if resp.status != 200:
                record["error"] = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                now = time.monotonic() - t0
                line = raw.decode().strip()
                if not line.startswith("data:") or line == "data: [DONE]":
                    continue
                data = json.loads(line[5:])
                if data.get("usage"):
                    record["usage"] = data["usage"]
                for choice in data.get("choices") or []:
                    text = (choice.get("delta") or {}).get("content") or ""
                    for entry in (choice.get("logprobs") or {}).get("content") or []:
                        record["logprobs"].append(entry["logprob"])
                        if "decided_at" in entry:
                            # a server that fixes a block's tokens pass by pass says which
                            # pass fixed this one; the reference is forced along that path
                            record["passes"].append(int(entry["decided_at"]))
                        if request.get("probe"):
                            record["top"].append([[int(a["token"][1:]), a["logprob"]]
                                                  for a in entry.get("top_logprobs") or []])
                    if text:
                        ids = traffic.ids_of(text)
                        record["ids"] += ids
                        record["chunks"].append((now, len(ids)))
                    if choice.get("finish_reason"):
                        record["finish"] = choice["finish_reason"]
        record["done"] = time.monotonic() - t0
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as exc:
        record["error"] = f"{type(exc).__name__}: {exc}"


def new_record(request: dict, prompt_tokens: int) -> dict:
    return {"index": request["index"], "due": request["due"], "prompt_tokens": prompt_tokens,
            "output_len": request["output_len"], "sent": None, "chunks": [], "ids": [], "logprobs": [], "passes": [], "top": [], "probe": request.get("probe", 0),
            "usage": None, "finish": None, "done": None, "error": None}


async def _drive(port, model, plan, filled, seconds, drain_s, hooks):
    url = f"http://127.0.0.1:{port}/v1/chat/completions"
    records = [new_record(r, len(traffic.templated_ids(r, filled))) for r in plan]
    lead = -min([r["due"] for r in plan] + [0.0])
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        t0 = time.monotonic() + lead + 0.25
        deadline = t0 + seconds + drain_s
        tasks = [
            asyncio.create_task(_one(session, url, model, r, filled, t0, rec, deadline))
            for r, rec in zip(plan, records)
        ]
        for at, hook in sorted(hooks, key=lambda h: h[0]):
            await asyncio.sleep(max(0.0, t0 + at - time.monotonic()))
            await asyncio.to_thread(hook)
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        in_flight = sum(1 for t in tasks if not t.done())
        done, pending = await asyncio.wait(tasks, timeout=max(0.0, deadline - time.monotonic()))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for rec, t in zip(records, tasks):
            if t in pending and not rec["error"]:
                rec["error"] = "cancelled at the drain deadline"
    return {"records": records, "t0": t0, "in_flight_at_close": in_flight,
            "cancelled": len(pending)}


def drive(port: int, model: str, plan, filled, seconds: float, drain_s: float, hooks=()):
    """Send ``plan`` open loop and return every request's record.  ``hooks``
    is a list of ``(seconds from the window's start, callable)`` run off the
    event loop (reads of the server's counters, the trace switch)."""
    # the collector stays off while requests are due: a full collection over
    # the records of some thousand chunks would hold the one event loop, and
    # whatever is due meanwhile is sent late
    gc.collect()
    gc.disable()
    try:
        return asyncio.run(_drive(port, model, plan, filled, seconds, drain_s, list(hooks)))
    finally:
        gc.enable()
