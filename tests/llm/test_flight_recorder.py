"""FlightRecorder bounds and triggers (dynamo_tpu/observability/flight.py):
the byte budget holds under event storms, dump-on-crash fires from the
``spawn_logged`` done-callback, and ``DYN_FLIGHT=0`` is bookkeeping-free."""

import asyncio
import json

import pytest

from dynamo_tpu.observability import flight
from dynamo_tpu.observability.flight import FlightRecorder, load_dump
from dynamo_tpu.utils.tasks import spawn_logged


@pytest.fixture
def flight_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT_DIR", str(tmp_path))
    return tmp_path


# -- byte budget --------------------------------------------------------------
def test_byte_budget_holds_under_event_storm():
    rec = FlightRecorder(source="t", capacity_bytes=4096, enabled=True)
    for i in range(5000):
        rec.record_event("fault", point=f"worker.generate.{i}", fire=i,
                         detail="x" * 40)
    assert rec.buffer_bytes <= 4096
    assert rec.records_total == 5000
    assert rec.dropped_total > 0
    assert len(rec) < 5000
    # the ring holds the NEWEST window: the storm's tail survives
    assert rec.records()[-1]["fire"] == 4999


def test_oversized_record_is_dropped_not_wedged():
    rec = FlightRecorder(source="t", capacity_bytes=128, enabled=True)
    rec.record_event("fault", blob="y" * 1024)
    assert len(rec) == 0
    assert rec.buffer_bytes == 0
    assert rec.dropped_total == 1
    # the ring still accepts records that fit
    rec.record_step(iteration=1)
    assert len(rec) == 1


# -- DYN_FLIGHT=0 -------------------------------------------------------------
def test_disabled_recorder_is_bookkeeping_free(monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT", "0")
    rec = FlightRecorder(source="t")
    assert rec.enabled is False
    rec.record_step(iteration=1)
    rec.record_event("preemption")
    rec.record_burn("ttft", 99.0, 5.0)
    assert len(rec) == 0
    assert rec.buffer_bytes == 0
    assert rec.records_total == 0
    assert rec.dump("manual") is None
    assert rec.dumps_total == 0
    # disabled recorders never enter the process registry
    assert rec not in flight.recorders()


# -- dump / load --------------------------------------------------------------
def test_dump_roundtrip_and_latest(flight_tmp):
    rec = FlightRecorder(source="t", capacity_bytes=65536, enabled=True)
    for i in range(10):
        rec.record_step(iteration=i, num_running=i % 3)
    rec.record_event("migration", status="committed", request="r-1")
    path = rec.dump("manual")
    assert path is not None and path.parent == flight_tmp
    header, records = load_dump(path)
    assert header["schema_version"] == flight.FLIGHT_SCHEMA_VERSION
    assert header["source"] == "t"
    assert header["reason"] == "manual"
    assert header["records"] == 11 == len(records)
    assert records[-1]["event"] == "migration"
    # timestamps are monotonic non-decreasing
    ts = [r["t"] for r in records]
    assert ts == sorted(ts)
    # the ring is NOT cleared by a dump: a later trigger sees the window
    assert len(rec) == 11
    assert sorted(flight_tmp.glob("flight-*.jsonl")) == [path]
    # every line is standalone JSON (the JSONL contract)
    for line in path.read_text().splitlines():
        json.loads(line)


def test_maybe_dump_rate_limits_per_reason(flight_tmp):
    rec = FlightRecorder(source="t", capacity_bytes=65536, enabled=True)
    rec.record_step(iteration=0)
    assert rec.maybe_dump("burn_breach") is not None
    assert rec.maybe_dump("burn_breach") is None       # inside the cooldown
    assert rec.maybe_dump("crash") is not None         # other reasons unaffected
    assert rec.dump("burn_breach") is not None         # explicit dump always runs
    assert rec.dumps_total == 3


# -- crash trigger (spawn_logged done-callback) -------------------------------
async def test_dump_on_crash_fires_from_spawn_logged(flight_tmp):
    rec = FlightRecorder(source="crashtest", capacity_bytes=65536, enabled=True)
    rec.record_step(iteration=7)

    async def doomed():
        raise ValueError("injected loop death")

    task = spawn_logged(doomed(), name="doomed-loop")
    with pytest.raises(ValueError):
        await task
    # the done-callback runs on the loop after the await; yield to it
    await asyncio.sleep(0)
    dumps = sorted(flight_tmp.glob("flight-crashtest-*-crash.jsonl"))
    assert dumps, "crash trigger wrote no dump"
    header, records = load_dump(dumps[-1])
    assert header["reason"] == "crash"
    events = [r for r in records if r.get("kind") == "event"]
    assert any(
        e["event"] == "crash" and e.get("task") == "doomed-loop"
        and "injected loop death" in e.get("error", "")
        for e in events
    )
    assert rec.last_dump_reason == "crash"


# -- burn trigger -------------------------------------------------------------
class _FakeSlo:
    def __init__(self, worst: float):
        self.worst = worst

    def worst_burn_rate(self, now=None) -> float:
        return self.worst


def test_check_burn_dumps_on_breach(flight_tmp, monkeypatch):
    monkeypatch.setattr(flight, "_last_burn_check", 0.0)
    rec = FlightRecorder(source="burntest", capacity_bytes=65536, enabled=True)
    assert flight.check_burn(_FakeSlo(worst=0.5)) is False   # below threshold
    monkeypatch.setattr(flight, "_last_burn_check", 0.0)
    assert flight.check_burn(_FakeSlo(worst=99.0)) is True
    assert any(r["kind"] == "burn" for r in rec.records())
    assert rec.last_dump_reason == "burn_breach"
    # the per-second rate limit swallows an immediate re-check
    assert flight.check_burn(_FakeSlo(worst=99.0)) is False


def test_check_burn_disabled_by_threshold(monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT_BURN", "0")
    monkeypatch.setattr(flight, "_last_burn_check", 0.0)
    assert flight.check_burn(_FakeSlo(worst=1e9)) is False


# -- exposition ---------------------------------------------------------------
def test_render_always_declares_families():
    text = flight.render().decode()
    for family in (
        "dyn_flight_records_total",
        "dyn_flight_dropped_total",
        "dyn_flight_dumps_total",
        "dyn_flight_buffer_bytes",
    ):
        assert f"# TYPE {family}" in text
        assert f"\n{family} " in "\n" + text.replace("# HELP ", "# HELP_")


def test_stats_keys_reach_engine_stats():
    """The mocker merges flight_* into stats() → ForwardPassMetrics."""
    from dynamo_tpu.llm.mocker import MockerConfig, MockerEngine

    eng = MockerEngine(MockerConfig())
    stats = eng.stats()
    for key in ("flight_records_total", "flight_dropped_total",
                "flight_dumps_total", "flight_buffer_bytes"):
        assert key in stats
