"""KvTransferClient/Server over real TCP: payload integrity through the
staged send path (host staging now runs in an executor OUTSIDE the
per-connection lock, so concurrent shipments to one worker pipeline), the
same-process local short-cut, the streamed multi-part wire fields, and the
pool's evict+re-dial hardening."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.parallel.kv_transfer import (
    KvTransferClient,
    KvTransferPayload,
    KvTransferServer,
    assemble_layers,
    split_layerwise,
)
from dynamo_tpu.runtime.codec import TwoPartMessage, encode_frame, read_two_part


def payload(i: int) -> KvTransferPayload:
    rng = np.random.default_rng(i)
    return KvTransferPayload(
        seq_id=f"seq-{i}",
        first_token=100 + i,
        block_ids=[i, i + 1],
        # non-contiguous slice: the staged ascontiguousarray must normalize
        # layout before tobytes
        blocks={
            "k": rng.standard_normal((2, 2, 4, 2, 8)).astype(np.float32)[:, :, ::2],
            "v": rng.standard_normal((2, 2, 2, 2, 8)).astype(np.float32),
        },
        first_token_logprob=-0.5 * i,
    )


async def test_concurrent_sends_over_tcp_arrive_intact():
    received: dict[str, KvTransferPayload] = {}

    async def sink(p: KvTransferPayload) -> None:
        # slow consumer: concurrent sends must still all complete (staging
        # happens outside the lock; only write→ack serializes)
        await asyncio.sleep(0.01)
        received[p.seq_id] = p

    server = KvTransferServer(sink)
    await server.start()
    # force the TCP path (the local registry would short-cut it)
    from dynamo_tpu.parallel import kv_transfer as mod

    mod.LOCAL_SERVERS.pop(server.address, None)
    client = KvTransferClient()
    try:
        sent = [payload(i) for i in range(6)]
        await asyncio.gather(
            *[client.send(server.address, p) for p in sent]
        )
        assert set(received) == {p.seq_id for p in sent}
        for p in sent:
            got = received[p.seq_id]
            assert got.first_token == p.first_token
            assert got.block_ids == p.block_ids
            assert got.first_token_logprob == p.first_token_logprob
            for name, arr in p.blocks.items():
                np.testing.assert_array_equal(got.blocks[name], np.ascontiguousarray(arr))
    finally:
        await client.close()
        await server.stop()


async def test_multipart_fields_roundtrip_over_tcp():
    """Streamed parts carry part_index/last/block_start through the codec;
    the closing part alone holds the sampled first token."""
    received: list[KvTransferPayload] = []

    async def sink(p: KvTransferPayload) -> None:
        received.append(p)

    server = KvTransferServer(sink)
    await server.start()
    from dynamo_tpu.parallel import kv_transfer as mod

    mod.LOCAL_SERVERS.pop(server.address, None)
    client = KvTransferClient()
    try:
        rng = np.random.default_rng(0)
        for idx, last in ((0, False), (1, False), (2, True)):
            await client.send(server.address, KvTransferPayload(
                seq_id="stream-1",
                first_token=42 if last else -1,
                block_ids=[idx * 2, idx * 2 + 1],
                blocks={"k": rng.standard_normal((2, 2, 4)).astype(np.float32)},
                part_index=idx,
                last=last,
                block_start=idx * 2,
            ))
        assert [p.part_index for p in received] == [0, 1, 2]
        assert [p.last for p in received] == [False, False, True]
        assert [p.block_start for p in received] == [0, 2, 4]
        assert [p.first_token for p in received] == [-1, -1, 42]
    finally:
        await client.close()
        await server.stop()


def test_split_layerwise_roundtrips_through_assemble():
    """Layer-range parts cover the leading axis exactly once, only the
    final part carries the sampled token, and reassembly — in any arrival
    order, with a duplicated part — reproduces the original arrays."""
    p = payload(3)
    n_layers = min(a.shape[0] for a in p.blocks.values())
    parts = split_layerwise(p, 1)
    assert len(parts) == n_layers
    assert [q.layer_start for q in parts] == list(range(n_layers))
    assert all(q.layer_count == 1 for q in parts)
    # only the closing part is final: it alone carries first_token/last
    assert [q.first_token for q in parts] == [-1] * (n_layers - 1) + [p.first_token]
    assert [q.last for q in parts] == [False] * (n_layers - 1) + [True]
    assert [q.part_index for q in parts] == list(range(n_layers))
    # reassemble out of order, with one part duplicated
    shuffled = [parts[-1], parts[0], parts[0]] + parts[1:]
    got = assemble_layers(shuffled)
    assert got.first_token == p.first_token
    assert got.block_ids == p.block_ids
    assert got.first_token_logprob == p.first_token_logprob
    for name, arr in p.blocks.items():
        np.testing.assert_array_equal(got.blocks[name], arr)


def test_split_layerwise_degenerate_cases_pass_through():
    p = payload(4)
    # layers_per_part >= n_layers, or granularity off: the payload itself
    assert split_layerwise(p, 0) == [p]
    assert split_layerwise(p, 99)[0] is p
    # a legacy all-layers frame reassembles to itself
    assert assemble_layers([p]) is p


async def test_layerwise_parts_roundtrip_over_tcp():
    """layer_start/layer_count survive the codec; a legacy frame (no layer
    fields staged) decodes as the all-layers degenerate case."""
    received: list[KvTransferPayload] = []

    async def sink(p: KvTransferPayload) -> None:
        received.append(p)

    server = KvTransferServer(sink)
    await server.start()
    from dynamo_tpu.parallel import kv_transfer as mod

    mod.LOCAL_SERVERS.pop(server.address, None)
    client = KvTransferClient()
    try:
        original = payload(5)
        for part in split_layerwise(original, 1):
            await client.send(server.address, part)
        assert [p.layer_start for p in received] == [0, 1]
        assert all(p.layer_count == 1 for p in received)
        got = assemble_layers(received)
        for name, arr in original.blocks.items():
            np.testing.assert_array_equal(
                got.blocks[name], np.ascontiguousarray(arr)
            )
        # legacy frame: default fields decode to all-layers
        received.clear()
        await client.send(server.address, payload(6))
        assert received[0].layer_start == 0
        assert received[0].layer_count == -1
    finally:
        await client.close()
        await server.stop()


async def test_send_redials_after_peer_drops_first_connection():
    """A pooled connection the peer drops before acking is evicted and the
    send retried over a fresh dial — the payload still lands exactly once."""
    received: list[KvTransferPayload] = []

    async def sink(p: KvTransferPayload) -> None:
        received.append(p)

    inner = KvTransferServer(sink)  # only its _handle protocol loop is used
    state = {"dropped": 0}

    async def handler(reader, writer):
        if state["dropped"] == 0:
            state["dropped"] += 1
            writer.close()
            return
        await inner._handle(reader, writer)

    server = await asyncio.start_server(handler, "127.0.0.1", 0)
    address = f"127.0.0.1:{server.sockets[0].getsockname()[1]}"
    client = KvTransferClient()
    try:
        await client.send(address, payload(1))
        assert state["dropped"] == 1
        assert client.evictions_total == 1
        assert [p.seq_id for p in received] == ["seq-1"]
        # the re-dialed connection is pooled and healthy: next send reuses it
        await client.send(address, payload(2))
        assert client.evictions_total == 1
        assert len(received) == 2
    finally:
        await client.close()
        server.close()
        await server.wait_closed()


async def test_refused_ack_is_not_retried():
    """A server that SAW the frame and refused it gets no re-send — the
    same bytes cannot succeed, and blind retry would double-inject."""
    conns = {"n": 0}

    async def handler(reader, writer):
        conns["n"] += 1
        await read_two_part(reader)
        writer.write(encode_frame(TwoPartMessage(header={"ok": False})))
        await writer.drain()
        writer.close()  # or server.wait_closed() below waits for it for ever

    server = await asyncio.start_server(handler, "127.0.0.1", 0)
    address = f"127.0.0.1:{server.sockets[0].getsockname()[1]}"
    client = KvTransferClient()
    try:
        with pytest.raises(ConnectionError, match="failed"):
            await client.send(address, payload(3))
        assert conns["n"] == 1
        assert client.evictions_total == 0
    finally:
        await client.close()
        server.close()
        await server.wait_closed()


async def test_bandwidth_ewma():
    """Successful TCP exchanges feed the per-destination bandwidth EWMA
    (the measured half of the router's transfer-cost model)."""
    client = KvTransferClient(ewma_alpha=0.25)
    client._observe("w:1", 100, 1.0)
    assert client.bandwidth_bps["w:1"] == 100.0
    client._observe("w:1", 200, 1.0)
    assert client.bandwidth_bps["w:1"] == pytest.approx(125.0)
    # degenerate observations never poison the estimate
    client._observe("w:1", 0, 1.0)
    client._observe("w:1", 100, 0.0)
    assert client.bandwidth_bps["w:1"] == pytest.approx(125.0)

    async def sink(p: KvTransferPayload) -> None:
        pass

    server = KvTransferServer(sink)
    await server.start()
    from dynamo_tpu.parallel import kv_transfer as mod

    mod.LOCAL_SERVERS.pop(server.address, None)
    try:
        await client.send(server.address, payload(0))
        assert client.bandwidth_bps[server.address] > 0
    finally:
        await client.close()
        await server.stop()


async def test_dial_timeout_bounds_a_blackholed_peer(monkeypatch):
    """A SYN into a dead route must fail the send within
    ``DYN_KV_DIAL_TIMEOUT_S`` — not park the prefill pump on the kernel's
    connect timeout (minutes)."""
    import time

    monkeypatch.setenv("DYN_KV_DIAL_TIMEOUT_S", "0.2")

    async def blackhole(host, port):
        await asyncio.sleep(3600)

    monkeypatch.setattr(asyncio, "open_connection", blackhole)
    client = KvTransferClient()
    try:
        t0 = time.monotonic()
        with pytest.raises(ConnectionError, match="timed out after 0.2s"):
            await client.send("10.255.255.1:9", payload(0))
        assert time.monotonic() - t0 < 1.5
    finally:
        await client.close()


async def test_local_shortcut_skips_codec():
    received: list[KvTransferPayload] = []

    async def sink(p: KvTransferPayload) -> None:
        received.append(p)

    server = KvTransferServer(sink)
    await server.start()
    client = KvTransferClient()
    try:
        p = payload(0)
        await client.send(server.address, p)
        # same-process: the exact payload object is handed through
        assert received and received[0] is p
    finally:
        await client.close()
        await server.stop()
