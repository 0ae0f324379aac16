"""The per-layer metrics that read the program's own accounting
(``engine.stats()`` at the window's two ends): their readers on hand-built
contexts, the histogram's percentile against the samples themselves, their
entries in BENCHMARK.json, and one tiny run on the CPU through the real
server."""

import importlib
import json
import random
import statistics

import pytest

from benchmark import host_spans
from benchmark.readers import attn_floor_share, counter_ratio, span_percentile

from .helpers import TINY_MIX, tiny_bench
from .trees import ROOT, TREES, bench_of, each

# ISSUE 27's table: each name with its reader and its layer.  Later PRs add
# metrics over the same readers; these fifteen stay, as they are.
TABLE = {
    "step_ms_decode.chat": ("counter_ratio", "engine step loop"),
    "step_ms_prompt.chat": ("counter_ratio", "engine step loop"),
    "step_ms_prompt.long": ("counter_ratio", "engine step loop"),
    "host_ms_per_step.chat": ("counter_ratio", "engine step loop"),
    "host_ms_per_step.long": ("counter_ratio", "engine step loop"),
    "decode_lanes_mean.chat": ("counter_ratio", "engine scheduler + KV manager"),
    "queue_wait_p95_ms.chat": ("span_percentile", "engine scheduler + KV manager"),
    "preemptions.chat": ("counter_delta", "engine scheduler + KV manager"),
    "preemptions.long": ("counter_delta", "engine scheduler + KV manager"),
    "frontend_inbound_p95_ms.chat": ("span_percentile", "HTTP frontend + preprocessor + router"),
    "frontend_emit_lag_p95_ms.chat": ("span_percentile", "HTTP frontend + preprocessor + router"),
    "ragged_live_page_share.chat": ("counter_ratio", "Pallas kernels"),
    "ragged_live_page_share.long": ("counter_ratio", "Pallas kernels"),
    "attn_floor_share.chat": ("attn_floor_share", "Pallas kernels"),
    "attn_floor_share.long": ("attn_floor_share", "Pallas kernels"),
}


def spec_of(metric, root=ROOT):
    return json.loads((root / "benchmark" / "metrics" / f"{metric['name']}.json").read_text())


def table_of(bench):
    """The table's entries that ``bench`` holds."""
    return [m for m in bench["per_layer"] if m["name"] in TABLE]



def ctx_of(stats0, stats1, **more):
    return dict({"stats0": {"stats": stats0}, "stats1": {"stats": stats1}}, **more)


# -- BENCHMARK.json ----------------------------------------------------------

@pytest.mark.parametrize("tree", TREES)
def test_the_table_of_the_issue_is_all_there(tree, roots):
    """Every name of the table, once, over the reader and under the layer it
    came with.  (Other metrics may use the same readers.)"""
    held = table_of(bench_of(tree))
    assert sorted(m["name"] for m in held) == sorted(TABLE)
    assert {m["name"]: (spec_of(m, roots[tree])["reader"], m["layer"]) for m in held} == TABLE


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_a_new_metric_has_its_file_its_cells_and_moves_what_they_report(tree, metric, roots):
    bench = bench_of(tree)
    cells = [w["name"] for w in bench["workloads"]]
    spec = spec_of(metric, roots[tree])
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    assert metric["workloads"] and set(metric["workloads"]) <= set(cells)
    moved = {m["name"]: m for m in bench["end_to_end"]}[metric["moves"]]
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    assert metric["source"] == "program_counter"
    suffix = metric["name"].rsplit(".", 1)[1]
    assert all(("long-prompt" in cell) == (suffix == "long") for cell in metric["workloads"])
    # the layer it came with, one the benchmark named before it or the frontend's
    assert metric["layer"] == TABLE[metric["name"]][1]
    assert metric["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] not in TABLE} | {
        "HTTP frontend + preprocessor + router"}


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_a_program_without_the_accounting_reads_as_nothing(tree, metric, roots):
    """The parent commit's stats() has none of the new keys: the reader
    returns None, the metric is left out, nothing raises."""
    spec = spec_of(metric, roots[tree])
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    old = {"engine_step_time_total_s": 1.0, "engine_busy_steps_total": 10,
           "decode_steps_total": 5}
    ctx = ctx_of(old, dict(old, engine_step_time_total_s=3.0, engine_busy_steps_total=40,
                           decode_steps_total=30), peaks=None)
    assert reader.read(ctx, **spec.get("args", {})) is None
    assert reader.read({"stats0": None, "stats1": None}, **spec.get("args", {})) is None


# -- readers -----------------------------------------------------------------

def test_counter_ratio_is_the_ratio_of_two_deltas():
    ctx = ctx_of({"t": 1.0, "n": 10}, {"t": 2.5, "n": 40})
    assert counter_ratio.read(ctx, "t", "n", scale=1000.0) == pytest.approx(50.0)
    assert counter_ratio.read(ctx, "t", "n") == pytest.approx(0.05)
    assert counter_ratio.read(ctx, "t", "missing") is None
    assert counter_ratio.read(ctx_of({"t": 1.0, "n": 10}, {"t": 2.0, "n": 10}), "t", "n") is None


def test_the_two_kinds_of_step_add_up_to_the_step_time():
    """The cross-check of the issue on a hand-built window."""
    s0 = dict(engine_decode_steps_total=100, engine_decode_step_time_total_s=4.0,
              engine_prompt_steps_total=10, engine_prompt_step_time_total_s=1.5,
              engine_step_time_total_s=5.5)
    s1 = dict(engine_decode_steps_total=900, engine_decode_step_time_total_s=38.4,
              engine_prompt_steps_total=110, engine_prompt_step_time_total_s=15.1,
              engine_step_time_total_s=53.5)
    ctx = ctx_of(s0, s1)
    dec = counter_ratio.read(ctx, "engine_decode_step_time_total_s", "engine_decode_steps_total", 1e3)
    pro = counter_ratio.read(ctx, "engine_prompt_step_time_total_s", "engine_prompt_steps_total", 1e3)
    assert dec == pytest.approx(43.0) and pro == pytest.approx(136.0)
    assert (800 * dec + 100 * pro) / 1e3 == pytest.approx(48.0)


def test_attn_floor_share_takes_the_larger_of_the_two_floors():
    peaks = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    zero = dict(ragged_attn_flops_total=0, decode_attn_flops_total=0,
                ragged_kv_read_bytes_total=0, decode_kv_read_bytes_total=0,
                engine_step_time_total_s=0.0)
    end = dict(ragged_attn_flops_total=150e12, decode_attn_flops_total=50e12,   # 1.0 s
               ragged_kv_read_bytes_total=1.2e12, decode_kv_read_bytes_total=0.4e12,  # 2.0 s
               engine_step_time_total_s=40.0)
    assert attn_floor_share.read(ctx_of(zero, end, peaks=peaks)) == pytest.approx(5.0)
    end["ragged_attn_flops_total"] = 750e12                                     # 4.0 s
    assert attn_floor_share.read(ctx_of(zero, end, peaks=peaks)) == pytest.approx(10.0)
    assert attn_floor_share.read(ctx_of(zero, end, peaks=None)) is None
    assert attn_floor_share.read(ctx_of(zero, dict(end, engine_step_time_total_s=0.0),
                                        peaks=peaks)) is None


@pytest.mark.parametrize("q", [50, 95, 99])
def test_a_percentile_from_the_delta_of_the_programs_histogram(q):
    """Samples observed before the window must not count; the answer lies
    within one bucket (the ratio) of statistics.quantiles on the window's
    own samples."""
    from dynamo_tpu.observability.recorder import HIST_RATIO, SpanRecorder

    rng = random.Random(q)
    rec = SpanRecorder(max_spans=16)
    for _ in range(500):
        rec.observe("engine.queue", rng.lognormvariate(-1.0, 1.0), component="engine")
    before = rec.aggregate()
    window = [rng.lognormvariate(-3.0, 0.8) for _ in range(2000)]
    for s in window:
        rec.observe("engine.queue", s, component="engine")
    ctx = ctx_of({"spans": before}, {"spans": json.loads(json.dumps(rec.aggregate()))})
    got = span_percentile.read(ctx, "engine", "engine.queue", q, scale=1.0)
    want = statistics.quantiles(window, n=100)[q - 1]
    assert want / HIST_RATIO <= got <= want * HIST_RATIO
    assert span_percentile.read(ctx, "engine", "no.such.span", q) is None
    assert span_percentile.read(ctx, "frontend", "engine.queue", q) is None
    # nothing happened in the window: nothing to read
    assert span_percentile.read(ctx_of({"spans": before}, {"spans": before}),
                                "engine", "engine.queue", q) is None


def test_the_histograms_ends():
    hist = {"min_s": 1e-3, "ratio": 2.0, "buckets": 3}
    assert span_percentile.percentile([4, 0, 0, 0, 0], hist, 50) == pytest.approx(0.5e-3)
    assert span_percentile.percentile([0, 0, 0, 0, 7], hist, 50) == pytest.approx(8e-3)
    assert span_percentile.percentile([0, 2, 0, 0, 0], hist, 100) == pytest.approx(2e-3)
    assert span_percentile.percentile([0, 0, 0, 0, 0], hist, 50) is None


# -- the shared clock ----------------------------------------------------------

MS = 1e6


def traced():
    """Two windows under overlap: the host dispatches window 2 while the
    device still runs window 1, then waits for window 1."""
    host = [("dyn.schedule", 0 * MS, 1 * MS), ("dyn.dispatch", 1 * MS, 1 * MS),
            ("dyn.post", 2 * MS, 1 * MS),
            ("dyn.schedule", 3 * MS, 1 * MS), ("dyn.dispatch", 4 * MS, 1 * MS),
            ("dyn.readback", 5 * MS, 37 * MS), ("dyn.post", 42 * MS, 1 * MS),
            ("PjitFunction(dyn_decode_w1)", 4 * MS, 1 * MS)]
    modules = [("jit_dyn_unified_t256(1)", 2 * MS, 40 * MS),
               ("jit_dyn_decode_w1(2)", 45 * MS, 10 * MS)]
    ops = [("fusion.1", 2 * MS, 40 * MS), ("fusion.2", 45 * MS, 10 * MS)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"engine-loop": host}}


def test_host_phases_beside_the_devices_programs():
    out = host_spans.attribute(traced())
    assert out["annotations"] == {"dyn.schedule": 2, "dyn.dispatch": 2, "dyn.post": 2,
                                  "dyn.readback": 1}
    # the first program starts while the host is in `post` of its own window;
    # the second after every host phase has ended (the host was idle)
    assert out["launches_by_host_phase"] == {"dyn.post": 1, "dyn.post (ended)": 1}
    # window 1 started 1 ms after its dispatch opened, window 2 41 ms after
    assert out["dispatch_lead_ms"] == {"n": 2, "median": pytest.approx(21.0),
                                       "min": pytest.approx(1.0), "max": pytest.approx(41.0)}
    # the one idle gap (42..45 ms) falls where `post` had ended last
    assert out["idle_gap_s_by_host_phase"] == {"dyn.post (ended)": pytest.approx(3e-3)}
    assert out["longest_gaps"] == [[pytest.approx(3e-3), "dyn.post (ended)"]]


def test_a_trace_of_a_program_without_annotations_reads_as_none():
    planes = traced()
    planes["/host:CPU"] = {"engine-loop": [("PjitFunction(step)", 0.0, 1 * MS)]}
    out = host_spans.attribute(planes)
    assert out["annotations"] == {} and out["dispatch_lead_ms"] is None
    assert out["launches_by_host_phase"] == {"none (ended)": 2}


# -- one tiny run through the real server --------------------------------------

def test_a_tiny_run_on_the_cpu_reports_the_metrics_from_stats(tmp_path):
    from benchmark import run as runner

    # a cell name of this file's own: the run's working directory is the
    # cell's, and test_end_to_end.py drives ``tiny.tinychat`` in another worker
    bench = tiny_bench(tmp_path, name="acct")
    (tmp_path / "traffic" / "tinychat.json").write_text(json.dumps(TINY_MIX))
    dump = tmp_path / "dump.json"
    argv = ["--workload", "acct.tinychat", "--seed", "5", "--seconds", "3", "--trace", "0",
            "--dump", str(dump)]
    rc, result = runner.run(runner.parse(argv), require_platform=None, bench_path=bench,
                            bench_dir=tmp_path, env_overlay={"JAX_PLATFORMS": "cpu"})
    assert rc == 0 and result["failed"] == 0
    run = json.loads(dump.read_text())
    ctx = {"stats0": run["stats0"], "stats1": run["stats1"],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {}
    for metric in table_of(bench_of("real")):
        spec = spec_of(metric)
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        got[metric["name"]] = reader.read(ctx, **spec.get("args", {}))
    print(got)
    for name in ("step_ms_decode.chat", "step_ms_prompt.chat", "host_ms_per_step.chat",
                 "queue_wait_p95_ms.chat", "frontend_inbound_p95_ms.chat",
                 "frontend_emit_lag_p95_ms.chat"):
        assert got[name] is not None and got[name] > 0, name
    assert 1.0 <= got["decode_lanes_mean.chat"] <= 4.0
    assert got["preemptions.chat"] == 0
    # the XLA twin serves on the CPU: no Pallas worklist, no kernel work
    assert got["ragged_live_page_share.chat"] is None
    assert got["attn_floor_share.chat"] == 0.0
    # the two kinds of step are all the steps
    s0, s1 = run["stats0"]["stats"], run["stats1"]["stats"]
    d = lambda k: s1[k] - s0[k]  # noqa: E731
    assert (d("engine_decode_step_time_total_s") + d("engine_prompt_step_time_total_s")
            == pytest.approx(d("engine_step_time_total_s")))
    assert 0 < d("engine_host_time_total_s") < d("engine_step_time_total_s")
