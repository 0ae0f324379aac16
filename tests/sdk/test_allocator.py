"""TPU resource allocator: disjoint per-replica chip assignment (reference
allocator parity: deploy/sdk/src/dynamo/sdk/cli/allocator.py:53-151), the
TPU-first fractional/over-subscription deviations, and the supervisor's
per-replica env plumbing."""

import asyncio
import json
import pathlib
import sys

import pytest

from dynamo_tpu.sdk.allocator import (
    ChipInventory,
    ResourceAllocator,
    ResourceError,
    plan_resource_envs,
)
from dynamo_tpu.sdk.graph import endpoint, service, to_process_specs
from dynamo_tpu.sdk.supervisor import ProcessSpec, ProcessSupervisor


def test_assign_chips_disjoint_and_contiguous():
    alloc = ResourceAllocator(ChipInventory(chips=(0, 1, 2, 3)))
    a = alloc.assign_chips(2, "prefill")
    b = alloc.assign_chips(2, "decode")
    assert sorted(a + b) == [0, 1, 2, 3]
    assert set(a).isdisjoint(b)
    # contiguous runs: tp shards of one replica share ICI-adjacent chips
    assert a[-1] - a[0] == 1 and b[-1] - b[0] == 1


def test_assign_chips_fragmented_falls_back_to_lowest_free():
    alloc = ResourceAllocator(ChipInventory(chips=(0, 1, 2, 3)))
    alloc.assign_chips(1)  # 0
    alloc.assign_chips(2)  # 1,2 (contiguous)
    assert alloc.assign_chips(1) == [3]


def test_fractional_and_oversubscription_raise():
    alloc = ResourceAllocator(ChipInventory(chips=(0, 1)))
    with pytest.raises(ResourceError, match="process-exclusive"):
        alloc.assign_chips(0.5, "frac")
    with pytest.raises(ResourceError, match="remain unassigned"):
        alloc.assign_chips(4, "big")


def test_two_worker2_services_get_disjoint_chips():
    """The reference-parity scenario: two workers=2 services on one host
    must end up with four disjoint chip sets, not all grabbing the slice."""

    @service(name="alloc-prefill", workers=2, resources={"tpu": 1})
    class Prefill:
        @endpoint()
        async def generate(self, request, ctx):
            yield {}

    @service(name="alloc-decode", workers=2, resources={"tpu": 1})
    class Decode:
        @endpoint()
        async def generate(self, request, ctx):
            yield {}

    envs = plan_resource_envs(
        [Prefill, Decode], inventory=ChipInventory(chips=(0, 1, 2, 3))
    )
    assert len(envs["alloc-prefill"]) == 2 and len(envs["alloc-decode"]) == 2
    claimed = [
        e["TPU_VISIBLE_CHIPS"]
        for per_service in envs.values()
        for e in per_service
    ]
    assert sorted(claimed) == ["0", "1", "2", "3"]


def test_plan_skips_when_disabled_or_no_chips(monkeypatch):
    @service(name="alloc-w", workers=1, resources={"tpu": 1})
    class W:
        @endpoint()
        async def generate(self, request, ctx):
            yield {}

    monkeypatch.setenv("DYN_DISABLE_AUTO_TPU_ALLOCATION", "1")
    assert plan_resource_envs([W], inventory=ChipInventory(chips=(0,))) == {}
    monkeypatch.delenv("DYN_DISABLE_AUTO_TPU_ALLOCATION")
    # no chip count known: a CPU-only host plans nothing...
    empty = ChipInventory(chips=())
    assert plan_resource_envs([W], inventory=empty, env={"JAX_PLATFORMS": "cpu"}) == {}
    # ...but where a chip might be, replicas must not be left to claim
    # every chip each (the second would hang): fail at plan time
    with pytest.raises(ResourceError, match="DYN_TPU_CHIP_COUNT"):
        plan_resource_envs([W], inventory=empty, env={})


def test_inventory_detect_prefers_visible_chips_env():
    inv = ChipInventory.detect(env={"TPU_VISIBLE_CHIPS": "2,3"})
    assert inv.chips == (2, 3)
    inv = ChipInventory.detect(env={"DYN_TPU_CHIP_COUNT": "4"})
    assert inv.chips == (0, 1, 2, 3)
    assert ChipInventory.detect(env={}).chips in ((),)  # CPU test host


def test_to_process_specs_carries_chip_envs_and_workers():
    @service(name="alloc-spec-w", workers=2, resources={"tpu": 2})
    class W:
        @endpoint()
        async def generate(self, request, ctx):
            yield {}

    (spec,) = to_process_specs(
        W, control_plane="memory://", chip_inventory=ChipInventory(chips=(0, 1, 2, 3))
    )
    assert spec.replicas == 2
    assert [e["TPU_VISIBLE_CHIPS"] for e in spec.replica_env] == ["0,1", "2,3"]


async def test_supervisor_refuses_scaleup_past_planned_overlays():
    """set_replicas beyond the allocator's plan would spawn a replica that
    sees the whole chip inventory — the spawn must fail loudly instead."""
    sup = ProcessSupervisor()
    sup.add_watcher(ProcessSpec(
        name="capped",
        cmd=[sys.executable, "-c", "import time; time.sleep(60)"],
        replica_env=[{"TPU_VISIBLE_CHIPS": "0"}],
        replicas=1,
    ))
    await sup.start()
    try:
        with pytest.raises(RuntimeError, match="no chip-env overlay"):
            await sup.set_replicas("capped", 2)
    finally:
        await sup.stop()


async def test_supervisor_applies_replica_env_and_restores_on_restart():
    """Each replica process sees ITS overlay; a restarted replica reclaims
    the SAME chips (the allocator's assignment is positional)."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        code = (
            "import json,os,sys,time; "
            f"json.dump(dict(os.environ), open('{td}/'+os.environ['DYN_REPLICA_INDEX']+'.json','w')); "
            "time.sleep(60)"
        )
        sup = ProcessSupervisor()
        sup.add_watcher(ProcessSpec(
            name="chipper",
            cmd=[sys.executable, "-c", code],
            replica_env=[{"TPU_VISIBLE_CHIPS": "0"}, {"TPU_VISIBLE_CHIPS": "1"}],
            replicas=2,
        ))
        await sup.start()
        try:
            assert sup.replica_count("chipper") == 2

            async def read_env(idx, attempts=100):
                path = pathlib.Path(td) / f"{idx}.json"
                for _ in range(attempts):
                    if path.exists():
                        try:
                            return json.loads(path.read_text())
                        except json.JSONDecodeError:
                            pass  # mid-write
                    await asyncio.sleep(0.1)
                raise AssertionError(f"replica {idx} never wrote its env")

            assert (await read_env(0))["TPU_VISIBLE_CHIPS"] == "0"
            assert (await read_env(1))["TPU_VISIBLE_CHIPS"] == "1"

            # crash replica 1: the restart must re-apply overlay 1
            env_file = pathlib.Path(td) / "1.json"
            env_file.unlink()
            victim = sup._replicas["chipper"][1]
            victim.process.kill()
            for _ in range(150):
                current = sup._replicas["chipper"].get(1)
                if current is not None and current is not victim:
                    break
                await asyncio.sleep(0.1)
            assert (await read_env(1))["TPU_VISIBLE_CHIPS"] == "1"
            assert env_file.exists()
        finally:
            await sup.stop()
