"""Control-plane semantics: KV/CAS/lease/watch + bus subjects/queues.

Covers the behaviors the reference gets from etcd + NATS (SURVEY.md §2.1
etcd/NATS transports): CAS create, prefix watch with snapshot, lease expiry
deleting keys and notifying watchers, queue-group load balancing,
request/reply, durable work queue, object store — for both the memory backend
and the dynctl TCP server.
"""

import asyncio

import pytest

from dynamo_tpu.runtime.controlplane import MemoryControlPlane
from dynamo_tpu.runtime.controlplane.client import RemoteControlPlane
from dynamo_tpu.runtime.controlplane.interface import WatchEventType
from dynamo_tpu.runtime.controlplane.server import ControlPlaneServer


@pytest.fixture(params=["memory", "tcp"])
def plane_factory(request):
    return request.param


async def make_plane(kind: str):
    if kind == "memory":
        return MemoryControlPlane(), None
    server = ControlPlaneServer(port=0)
    await server.start()
    plane = RemoteControlPlane("127.0.0.1", server.port)
    await plane.connect()
    return plane, server


async def teardown(plane, server):
    await plane.close()
    if server is not None:
        await server.stop()


async def test_kv_basic(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        rev1 = await plane.kv.put("a/b", b"1")
        rev2 = await plane.kv.put("a/c", b"2")
        assert rev2 > rev1
        entry = await plane.kv.get("a/b")
        assert entry is not None and entry.value == b"1"
        assert await plane.kv.get("missing") is None
        entries = await plane.kv.get_prefix("a/")
        assert [e.key for e in entries] == ["a/b", "a/c"]
        assert await plane.kv.delete("a/b") is True
        assert await plane.kv.delete("a/b") is False
        assert await plane.kv.delete_prefix("a/") == 1
    finally:
        await teardown(plane, server)


async def test_kv_cas_create(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        assert await plane.kv.create("x", b"first") is True
        assert await plane.kv.create("x", b"second") is False
        entry = await plane.kv.get("x")
        assert entry.value == b"first"
    finally:
        await teardown(plane, server)


async def test_watch_snapshot_and_live(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        await plane.kv.put("w/a", b"1")
        watch = plane.kv.watch_prefix("w/")
        await asyncio.sleep(0.05)  # let remote watch register
        await plane.kv.put("w/b", b"2")
        await plane.kv.delete("w/a")

        events = []
        async for ev in watch:
            events.append(ev)
            if len(events) == 3:
                watch.cancel()
        assert events[0].type == WatchEventType.PUT and events[0].entry.key == "w/a"
        kinds = [(e.type, e.entry.key) for e in events]
        assert (WatchEventType.PUT, "w/b") in kinds
        assert (WatchEventType.DELETE, "w/a") in kinds
    finally:
        await teardown(plane, server)


async def test_watch_ready_after_snapshot(plane_factory):
    """watch.ready() resolves only once the initial snapshot has been
    consumed, so a view primed in a consumer loop is complete by then."""
    plane, server = await make_plane(plane_factory)
    try:
        await plane.kv.put("r/a", b"1")
        await plane.kv.put("r/b", b"2")
        watch = plane.kv.watch_prefix("r/")

        seen: dict[str, bytes] = {}

        async def consume():
            async for ev in watch:
                if ev.type == WatchEventType.PUT:
                    seen[ev.entry.key] = ev.entry.value
                else:
                    seen.pop(ev.entry.key, None)

        task = asyncio.ensure_future(consume())
        await asyncio.wait_for(watch.ready(), timeout=5)
        assert seen == {"r/a": b"1", "r/b": b"2"}
        watch.cancel()
        await task
    finally:
        await teardown(plane, server)


async def test_lease_expiry_deletes_and_notifies(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        lease = await plane.kv.grant_lease(0.4)
        await plane.kv.put("inst/1", b"alive", lease_id=lease.id)
        watch = plane.kv.watch_prefix("inst/")
        # swallow the snapshot PUT
        first = await asyncio.wait_for(watch.__anext__(), 2)
        assert first.type == WatchEventType.PUT
        # stop keep-alive: revoke explicitly (remote auto-keepalive would
        # otherwise keep it fresh forever)
        await plane.kv.revoke_lease(lease)
        ev = await asyncio.wait_for(watch.__anext__(), 2)
        assert ev.type == WatchEventType.DELETE and ev.entry.key == "inst/1"
        assert await plane.kv.get("inst/1") is None
        watch.cancel()
    finally:
        await teardown(plane, server)


async def test_lease_ttl_expiry_without_keepalive():
    # memory backend: simulate a crashed client whose lease lapses
    plane = MemoryControlPlane()
    lease = await plane.kv.grant_lease(0.3)
    await plane.kv.put("inst/2", b"alive", lease_id=lease.id)
    await asyncio.sleep(0.8)
    assert await plane.kv.get("inst/2") is None
    assert lease.revoked


async def test_lease_survives_an_event_loop_stall():
    """In-process plane: holder keep-alive and reaper share one loop, so
    time the loop did not run must not count against a lease (on the chip,
    warmup at real widths starved the loop past the 3 s instance lease and
    the worker silently lost its only instance).  A lease nobody renews
    still lapses."""
    import time

    plane = MemoryControlPlane()
    lease = await plane.kv.grant_lease(0.6)
    await plane.kv.put("inst/3", b"alive", lease_id=lease.id)

    async def keepalive():
        while not lease.revoked:
            await asyncio.sleep(0.2)
            await plane.kv.keep_alive(lease)

    task = asyncio.ensure_future(keepalive())
    await asyncio.sleep(0.3)
    time.sleep(1.5)  # the whole loop stalls for 2.5x the TTL
    await asyncio.sleep(0.5)
    assert (await plane.kv.get("inst/3")).value == b"alive" and not lease.revoked
    task.cancel()
    await asyncio.sleep(1.2)  # holder gone: the lease lapses as before
    assert await plane.kv.get("inst/3") is None and lease.revoked


async def test_bus_pubsub_and_queue_groups(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        plain = await plane.bus.subscribe("evt.>")
        g1 = await plane.bus.subscribe("work.q", queue_group="g")
        g2 = await plane.bus.subscribe("work.q", queue_group="g")
        await asyncio.sleep(0.02)

        await plane.bus.publish("evt.kv.stored", b"e1")
        msg = await asyncio.wait_for(plain.__anext__(), 2)
        assert msg.subject == "evt.kv.stored" and msg.payload == b"e1"

        for i in range(4):
            await plane.bus.publish("work.q", f"m{i}".encode())
        await asyncio.sleep(0.05)
        # queue group: each message to exactly one member, balanced
        assert g1.pending() + g2.pending() == 4
        assert g1.pending() == 2 and g2.pending() == 2
        await plain.unsubscribe()
        await g1.unsubscribe()
        await g2.unsubscribe()
    finally:
        await teardown(plane, server)


async def test_publish_reports_delivered_subscriber_count(plane_factory):
    """publish() returns how many subscribers the message reached: a hard
    0 is the frontend's signal that a worker's subject is dark (dead or
    mid-resubscribe after a control-plane reconnect) and the envelope must
    be re-published rather than waited on."""
    plane, server = await make_plane(plane_factory)
    try:
        assert await plane.bus.publish("nobody.home", b"x") == 0
        sub = await plane.bus.subscribe("somebody.home")
        await asyncio.sleep(0.02)
        assert await plane.bus.publish("somebody.home", b"x") == 1
        # queue groups count as one delivery per group
        g1 = await plane.bus.subscribe("grp.subj", queue_group="g")
        g2 = await plane.bus.subscribe("grp.subj", queue_group="g")
        await asyncio.sleep(0.02)
        assert await plane.bus.publish("grp.subj", b"x") == 1
        await sub.unsubscribe()
        await g1.unsubscribe()
        await g2.unsubscribe()
    finally:
        await teardown(plane, server)


async def test_bus_request_reply(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        sub = await plane.bus.subscribe("svc.stats")
        await asyncio.sleep(0.02)

        async def responder():
            msg = await sub.__anext__()
            await plane.bus.publish(msg.reply_to, b"stats:" + msg.payload)

        task = asyncio.ensure_future(responder())
        reply = await plane.bus.request("svc.stats", b"hello", timeout=2)
        assert reply == b"stats:hello"
        await task
        await sub.unsubscribe()
    finally:
        await teardown(plane, server)


async def test_work_queue(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        await plane.bus.queue_publish("prefill", b"req1")
        await plane.bus.queue_publish("prefill", b"req2")
        assert await plane.bus.queue_len("prefill") == 2
        assert await plane.bus.queue_pop("prefill", timeout=1) == b"req1"
        assert await plane.bus.queue_pop("prefill", timeout=1) == b"req2"
        assert await plane.bus.queue_pop("prefill", timeout=0.1) is None
    finally:
        await teardown(plane, server)


async def test_work_queue_pop_meta_age(plane_factory):
    """queue_pop_meta reports the broker's own enqueue→pop age — the
    skew-free staleness signal the disagg prefill worker consumes."""
    plane, server = await make_plane(plane_factory)
    try:
        await plane.bus.queue_publish("prefill", b"req1")
        await asyncio.sleep(0.05)
        item = await plane.bus.queue_pop_meta("prefill", timeout=1)
        assert item is not None
        payload, age = item
        assert payload == b"req1"
        assert age is not None and 0.04 <= age < 5.0
        assert await plane.bus.queue_pop_meta("prefill", timeout=0.1) is None
    finally:
        await teardown(plane, server)


async def test_queue_pop_meta_degrades_on_old_server():
    """A new client against a pre-queue_pop_meta dynctl server must fall
    back to queue_pop with age=None (one failed round trip, then cached),
    not error-loop."""
    from dynamo_tpu.runtime.controlplane.client import RemoteBus

    calls = []

    class FakeConn:
        async def call(self, method, *args, timeout=None):
            calls.append(method)
            if method == "bus.queue_pop_meta":
                raise RuntimeError("ValueError('unknown method bus.queue_pop_meta')")
            assert method == "bus.queue_pop"
            return b"req1"

    bus = RemoteBus(FakeConn())
    assert await bus.queue_pop_meta("q", timeout=1) == (b"req1", None)
    assert await bus.queue_pop_meta("q", timeout=1) == (b"req1", None)
    # the unsupported method was tried exactly once
    assert calls.count("bus.queue_pop_meta") == 1
    assert calls.count("bus.queue_pop") == 2


async def test_object_store(plane_factory):
    plane, server = await make_plane(plane_factory)
    try:
        blob = bytes(range(256)) * 100
        await plane.bus.object_put("models", "card.json", blob)
        assert await plane.bus.object_get("models", "card.json") == blob
        assert await plane.bus.object_get("models", "absent") is None
        assert await plane.bus.object_delete("models", "card.json") is True
        assert await plane.bus.object_delete("models", "card.json") is False
    finally:
        await teardown(plane, server)


async def test_kv_watch_cache(plane_factory):
    """Snapshot-primed local reads, watch-driven updates, write-through."""
    from dynamo_tpu.runtime.controlplane import KvWatchCache

    plane, server = await make_plane(plane_factory)
    cache = None
    try:
        await plane.kv.put("cfg/a", b"1")
        await plane.kv.put("cfg/b", b"2")
        await plane.kv.put("other/x", b"9")

        cache = await KvWatchCache.create(plane.kv, "cfg/")
        assert cache.get("a") == b"1" and cache.get("b") == b"2"
        assert cache.get("x") is None  # outside the prefix
        assert len(cache) == 2
        assert not cache.stale

        # external write lands via the watch
        await plane.kv.put("cfg/c", b"3")
        for _ in range(100):
            if cache.get("c") == b"3":
                break
            await cache.wait_changed(timeout=0.05)
        assert cache.get("c") == b"3"

        # write-through visible locally at once and remotely
        await cache.put("a", b"updated")
        assert cache.get("a") == b"updated"
        entry = await plane.kv.get("cfg/a")
        assert entry.value == b"updated"

        # external delete removes from the view
        await plane.kv.delete("cfg/b")
        for _ in range(100):
            if cache.get("b") is None:
                break
            await cache.wait_changed(timeout=0.05)
        assert cache.get("b") is None
    finally:
        if cache is not None:
            await cache.close()
        await teardown(plane, server)


async def test_kv_watch_cache_goes_stale_on_watch_death(plane_factory):
    """A dead backing watch flags the cache stale and wakes waiters instead
    of serving silently-frozen data forever."""
    from dynamo_tpu.runtime.controlplane import KvWatchCache

    plane, server = await make_plane(plane_factory)
    cache = None
    try:
        await plane.kv.put("cfg/a", b"1")
        cache = await KvWatchCache.create(plane.kv, "cfg/")
        assert not cache.stale
        # kill the watch out from under the cache (connection-loss analog)
        cache._watch.cancel()
        for _ in range(100):
            if cache.stale:
                break
            await cache.wait_changed(timeout=0.05)
        assert cache.stale
        # waiters are not stuck: wait_changed returns promptly
        assert await cache.wait_changed(timeout=1) is not None
    finally:
        if cache is not None:
            await cache.close()
        await teardown(plane, server)


async def test_watch_ready_fails_fast_on_dead_connection():
    """A watch started over a broken connection must surface the error to
    ``ready()`` waiters and iterators instead of hanging forever (the
    Client.start startup-hang defect).  Fail-fast semantics are pinned with
    ``reconnect=False``; the default self-heals instead (covered in
    tests/robustness/)."""
    server = ControlPlaneServer(port=0)
    await server.start()
    plane = RemoteControlPlane("127.0.0.1", server.port, reconnect=False)
    await plane.connect()
    try:
        # sever the transport under the client, then start a watch
        plane._conn._writer.close()
        await asyncio.sleep(0.1)  # let the read loop observe EOF
        watch = plane.kv.watch_prefix("some/prefix")
        with pytest.raises((ConnectionError, RuntimeError)):
            await asyncio.wait_for(watch.ready(), timeout=10)
        # iterating the failed watch raises too (no silent empty stream)
        with pytest.raises((ConnectionError, RuntimeError, StopAsyncIteration)):
            await asyncio.wait_for(watch.__anext__(), timeout=10)
    finally:
        await plane.close()
        await server.stop()


async def test_live_watch_fails_when_connection_drops():
    """With reconnect disabled, an established watch whose connection dies
    mid-stream raises to the consumer instead of ending silently."""
    server = ControlPlaneServer(port=0)
    await server.start()
    plane = RemoteControlPlane("127.0.0.1", server.port, reconnect=False)
    await plane.connect()
    try:
        await plane.kv.put("w/a", b"1")
        watch = plane.kv.watch_prefix("w/")
        first = await asyncio.wait_for(watch.__anext__(), timeout=10)
        assert first.entry.key == "w/a"
        plane._conn._writer.close()
        with pytest.raises((ConnectionError, RuntimeError)):
            await asyncio.wait_for(watch.__anext__(), timeout=10)
    finally:
        await plane.close()
        await server.stop()


async def test_live_watch_heals_when_connection_drops():
    """Default (reconnect on): a dropped connection re-establishes the
    watch transparently — the SAME Watch handle keeps yielding events that
    happen after the outage, and the reconnect is counted."""
    server = ControlPlaneServer(port=0)
    await server.start()
    plane = RemoteControlPlane("127.0.0.1", server.port)
    await plane.connect()
    try:
        await plane.kv.put("w/a", b"1")
        watch = plane.kv.watch_prefix("w/")
        first = await asyncio.wait_for(watch.__anext__(), timeout=10)
        assert first.entry.key == "w/a"
        plane._conn._writer.close()
        # wait for the reconnect before writing, so the put is not racing
        # the resync snapshot
        for _ in range(200):
            if plane.reconnects_total >= 1:
                break
            await asyncio.sleep(0.05)
        assert plane.reconnects_total >= 1
        await plane.kv.put("w/b", b"2")
        seen = {}
        while "w/b" not in seen:
            ev = await asyncio.wait_for(watch.__anext__(), timeout=10)
            if ev.type == WatchEventType.PUT:
                seen[ev.entry.key] = ev.entry.value
        assert seen["w/b"] == b"2"
        watch.cancel()
    finally:
        await plane.close()
        await server.stop()


@pytest.mark.slow
async def test_soak_many_clients_against_tcp_server():
    """Control-plane soak (tcp only): many concurrent client connections
    doing interleaved KV puts/gets, watches, bus publishes, and queue
    work against ONE dynctl server — the topology every distributed
    deployment rides.  Everything must complete and watches must observe
    every put."""
    server = ControlPlaneServer(port=0)
    await server.start()
    planes = []
    wtask = None
    n_workers, n_ops = 23, 20  # + 1 watcher connection
    total = n_workers * n_ops
    try:
        # ≈ a 16-worker + frontends deployment
        for _ in range(n_workers + 1):
            p = RemoteControlPlane("127.0.0.1", server.port)
            await p.connect()
            planes.append(p)

        watcher = planes[0]
        seen: set[str] = set()
        watch = watcher.kv.watch_prefix("soak/")

        async def watch_loop():
            async for ev in watch:
                if ev.type == WatchEventType.PUT:
                    seen.add(ev.entry.key)
                    if len(seen) >= total:
                        return

        wtask = asyncio.ensure_future(watch_loop())
        await watch.ready()

        async def client_work(i: int, plane) -> int:
            done = 0
            for j in range(n_ops):
                await plane.kv.put(f"soak/{i}/{j}", f"{i}:{j}".encode())
                entry = await plane.kv.get(f"soak/{i}/{j}")
                assert entry is not None
                await plane.bus.publish(f"soak.topic.{i % 4}", b"x")
                await plane.bus.queue_publish("soak.work", f"{i}/{j}".encode())
                done += 1
            return done

        totals = await asyncio.gather(
            *[client_work(i, p) for i, p in enumerate(planes[1:], start=1)]
        )
        assert sum(totals) == total

        # queue integrity: exactly every published item pops exactly once
        popped = set()
        for _ in range(total):
            raw = await planes[0].bus.queue_pop("soak.work", timeout=5)
            assert raw is not None
            popped.add(raw.decode())
        assert len(popped) == total
        assert await planes[0].bus.queue_pop("soak.work", timeout=0.1) is None

        # the single watcher saw every key from every client
        await asyncio.wait_for(wtask, timeout=10)
        assert len(seen) == total
    finally:
        if wtask is not None:
            wtask.cancel()  # an assertion mid-test must not leak the watcher
        for p in planes:
            await p.close()
        await server.stop()
