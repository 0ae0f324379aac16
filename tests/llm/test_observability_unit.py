"""Observability primitives: trace context wire roundtrip, recorder buffer
semantics, JSONL/Chrome exporters, lifecycle summaries, step telemetry."""

import json

from dynamo_tpu.observability import SpanRecorder, StepTelemetry, TraceContext
from dynamo_tpu.observability.step_metrics import StepRecord
from dynamo_tpu.observability.trace import sanitize_request_id


def test_trace_context_roundtrip_and_children():
    root = TraceContext.new_root("req-1")
    assert root.trace_id == "req-1" and root.parent_span_id is None
    child = root.child()
    assert child.trace_id == "req-1"
    assert child.parent_span_id == root.span_id
    assert child.span_id != root.span_id
    assert TraceContext.from_wire(child.to_wire()) == child
    # lenient decode: garbage degrades to None, never raises
    for bad in (None, 17, "x", {}, {"t": "a"}, {"t": 1, "s": 2}, {"s": "only"}):
        assert TraceContext.from_wire(bad) is None


def test_wire_layer_trace_helpers():
    """The control-plane RPC and data-plane frame helpers carry the same
    wire form the request envelope uses."""
    from dynamo_tpu.runtime.codec import attach_trace, extract_trace
    from dynamo_tpu.runtime.controlplane.wire import frame_trace, with_trace

    ctx = TraceContext.new_root("w-1").child()
    header = attach_trace({"t": "prologue", "stream_id": "s"}, ctx)
    assert extract_trace(header) == ctx
    assert attach_trace({"t": "data"}, None) == {"t": "data"}
    assert extract_trace({"t": "data"}) is None

    frame = with_trace({"i": 1, "m": "get", "a": []}, ctx)
    assert frame_trace(frame) == ctx
    assert with_trace({"i": 2}, None) == {"i": 2}
    assert frame_trace({"i": 2}) is None


def test_sanitize_request_id():
    assert sanitize_request_id("abc-123.X_z") == "abc-123.X_z"
    assert sanitize_request_id("a b\nc") == "a_b_c"
    assert sanitize_request_id("x" * 500) == "x" * 128
    assert sanitize_request_id("") is None
    assert sanitize_request_id(None) is None


def test_recorder_buffer_is_bounded_and_untraced_is_free():
    rec = SpanRecorder(max_spans=4)
    root = TraceContext.new_root("t1")
    for i in range(10):
        h = rec.start(f"s{i}", root, component="test")
        h.end()
    assert len(rec.snapshot()) == 4  # ring buffer dropped the oldest
    # no parent AND no root id => nothing recorded, zero cost
    assert rec.start("orphan", None, component="test") is None
    assert rec.record("orphan", None, 0.0, 1.0, component="test") is None


def test_span_tree_and_exporters(tmp_path):
    rec = SpanRecorder(max_spans=64)
    root = rec.start("http.request", None, component="frontend", root_trace_id="rid-9")
    child = rec.start("worker.handle", root.ctx, component="worker")
    rec.record(
        "engine.prefill", child.ctx, 100.0, 100.5, component="engine",
        attrs={"ttft_s": 0.5},
    )
    rec.record(
        "engine.decode", child.ctx, 100.5, 102.5, component="engine",
        attrs={"tokens_out": 5},
    )
    child.end()
    root.end(status="success", tokens_out=5)

    spans = rec.spans_for("rid-9")
    assert [s.name for s in spans if s.parent_span_id is None] == ["http.request"]
    ids = {s.span_id for s in spans}
    assert all(s.parent_span_id in ids for s in spans if s.parent_span_id)
    assert all(s.duration_s >= 0 for s in spans)

    # JSONL export parses line by line
    jl = tmp_path / "spans.jsonl"
    n = rec.export_jsonl(str(jl), "rid-9")
    lines = [json.loads(line) for line in jl.read_text().splitlines()]
    assert n == len(lines) == len(spans)
    assert {line["trace_id"] for line in lines} == {"rid-9"}

    # Chrome trace export parses and has one X event per span + process
    # metadata naming the components
    ct = tmp_path / "chrome.json"
    rec.export_chrome_trace(str(ct), "rid-9")
    doc = json.loads(ct.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == len(spans)
    assert {m["args"]["name"] for m in metas} == {"frontend", "worker", "engine"}
    assert all(e["dur"] >= 0 and e["pid"] >= 1 for e in xs)

    # lifecycle summary assembled from the tree
    summary = rec.summary("rid-9")
    assert summary["status"] == "success"
    assert summary["prefill_s"] == 0.5
    assert summary["decode_s"] == 2.0
    assert summary["ttft_s"] == 0.5
    assert summary["tokens_out"] == 5
    assert abs(summary["itl_avg_s"] - 0.5) < 1e-9


def test_live_jsonl_streaming(tmp_path):
    path = tmp_path / "live.jsonl"
    rec = SpanRecorder(max_spans=8, jsonl_path=str(path))
    root = rec.start("a", None, component="c", root_trace_id="t")
    root.end()
    rec.record("b", root.ctx, 1.0, 2.0, component="c")
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [line["name"] for line in lines] == ["a", "b"]


def test_step_telemetry_snapshot_and_counters():
    t = StepTelemetry(max_batch_size=8)
    t.observe(StepRecord(iteration=1, num_running=4, num_waiting=2, kv_active_blocks=32,
        kv_total_blocks=64, duration_s=0.01))
    t.observe(StepRecord(iteration=2, num_running=0, num_waiting=0, kv_active_blocks=0,
        kv_total_blocks=64, duration_s=0.02))
    stats = t.stats()
    assert stats["engine_steps_total"] == 2
    assert stats["engine_busy_steps_total"] == 1
    assert abs(stats["engine_step_time_total_s"] - 0.03) < 1e-9
    assert stats["batch_occupancy_perc"] == 0.0  # latest step
    assert stats["step_num_running"] == 0 and stats["step_num_waiting"] == 0
    assert stats["step_kv_usage_perc"] == 0.0
    assert t.snapshot.kv_usage_perc == 0.0
    # occupancy of the busy step was 0.5
    t.observe(StepRecord(iteration=3, num_running=8, num_waiting=1, kv_active_blocks=64,
        kv_total_blocks=64, duration_s=0.0))
    assert t.stats()["batch_occupancy_perc"] == 1.0
    assert t.stats()["step_kv_usage_perc"] == 1.0
    assert t.snapshot.kv_usage_perc == 1.0


def test_jsonl_rotation_bounds_disk(tmp_path):
    """DYN_TRACE_MAX_BYTES: the live JSONL export rotates to ``.1`` instead
    of growing without bound; newest spans are always in the live file."""
    path = tmp_path / "spans.jsonl"
    rec = SpanRecorder(max_spans=512, jsonl_path=str(path), max_jsonl_bytes=2048)
    for i in range(100):
        rec.record(
            f"span-{i:03d}", TraceContext.new_root("t"), 1.0, 2.0, component="c"
        )
    rotated = tmp_path / "spans.jsonl.1"
    assert rotated.exists()
    assert path.stat().st_size <= 2048
    assert rotated.stat().st_size <= 2048
    # the newest span landed in the live file
    live_names = [json.loads(line)["name"] for line in path.read_text().splitlines()]
    assert live_names[-1] == "span-099"
    # only one rotated generation is kept (~2x the limit on disk, total)
    assert not (tmp_path / "spans.jsonl.2").exists()


def test_jsonl_rotation_resumes_from_existing_file(tmp_path):
    """A restarted process accounts the bytes already in the file, so the
    limit holds across process lifetimes."""
    path = tmp_path / "spans.jsonl"
    path.write_text("x" * 1900 + "\n")
    rec = SpanRecorder(max_spans=8, jsonl_path=str(path), max_jsonl_bytes=2048)
    rec.record("after-restart", TraceContext.new_root("t"), 1.0, 2.0, component="c")
    # the big pre-existing file rotated away; the new span is live
    assert (tmp_path / "spans.jsonl.1").exists()
    assert "after-restart" in path.read_text()


def test_step_telemetry_token_counts():
    t = StepTelemetry(max_batch_size=8)
    t.observe(StepRecord(iteration=1, num_running=1, num_waiting=0, kv_active_blocks=1,
        kv_total_blocks=64, duration_s=0.01,
        prefill_tokens=32, decode_tokens=4))
    assert t.snapshot.prefill_tokens == 32
    assert t.snapshot.decode_tokens == 4
