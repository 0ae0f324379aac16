"""ISSUE 44's eight per-layer metrics over the step loop's own account
(``device_starved_*``, ``engine_no_work_*``, ``engine_host_offcpu_*``,
``engine_post_*`` and the duration series ``engine.starved`` /
``engine.step.decode``): each is an entry of BENCHMARK.json and a file over a
reader the benchmark already had, reads a hand-built window, and reads as
nothing on a program that keeps no such counter (the parent commit)."""

import hashlib
import importlib
import json

import pytest

from dynamo_tpu.observability.recorder import HIST_RATIO, SpanRecorder

from .test_program_accounting import ctx_of, spec_of
from .trees import bench_of, each

CELLS = ["k-exaone-236b-l8.long-mixed", "moonlight-16b-l9.long-doc",
         "qwen3-4b.chat", "qwen3-4b.shared-prefix"]

# name -> (reader, unit, what the hand-built window below reads)
TABLE = {
    "device_starved_share": ("counter_ratio", "%", 8.0),
    "starved_dispatch_share": ("counter_ratio", "%", 25.0),
    "starved_gap_p95_ms": ("span_percentile", "ms", 4.0),
    "host_offcpu_ms_per_step": ("counter_ratio", "ms", 1.5),
    "post_ms_per_step": ("counter_ratio", "ms", 3.5),
    "post_emit_ms_per_step": ("counter_ratio", "ms", 2.0),
    "step_decode_p99_ms": ("span_percentile", "ms", 30.0),
    "no_work_share": ("counter_ratio", "%", 2.0),
}


# the 76 entries the list held when PR 44 appended its eight: their names, in order
BEFORE_THEM = (76, "a1b9cf007f91571b53715e5dacf5295cd4a627b5")


def in_place(names) -> bool:
    """The eight, in order, once, right behind the entries that were there
    before them, those unmoved; whatever comes after is a later PR's."""
    first = names.index("device_starved_share")
    digest = hashlib.sha1("\n".join(names[:first]).encode()).hexdigest()
    return names[first:first + len(TABLE)] == list(TABLE) and (first, digest) == BEFORE_THEM


def table_of(bench):
    return [m for m in bench["per_layer"] if m["name"] in TABLE]


def read(metric, root, ctx):
    spec = spec_of(metric, root)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(ctx, **spec.get("args", {}))


def window() -> dict:
    """A window of 1,000 busy steps in 50 s of step time: 4 s starved in 250
    episodes of 4 ms, 1.5 ms a step without the CPU, `post` 3.5 ms a step of
    which `emit` 2, a second with no work; every decode step 30 ms but for
    five stalls of 2 s.  The counters stood elsewhere before it."""
    rec = SpanRecorder(max_spans=8)
    for _ in range(40):                   # before the window: must not count
        rec.observe("engine.starved", 0.5, component="engine")
        rec.observe("engine.step.decode", 0.5, component="engine")
    before = rec.aggregate()
    for _ in range(250):
        rec.observe("engine.starved", 0.004, component="engine")
    for _ in range(995):
        rec.observe("engine.step.decode", 0.030, component="engine")
    for _ in range(5):
        rec.observe("engine.step.decode", 2.0, component="engine")
    s0 = {"engine_step_time_total_s": 10.0, "engine_busy_steps_total": 100,
          "device_starved_time_total_s": 1.0, "device_starved_dispatches_total": 30,
          "engine_host_offcpu_time_total_s": 0.5, "engine_post_time_total_s": 0.25,
          "engine_post_emit_time_total_s": 0.125, "engine_no_work_time_total_s": 7.0,
          "spans": before}
    s1 = {"engine_step_time_total_s": 60.0, "engine_busy_steps_total": 1100,
          "device_starved_time_total_s": 5.0, "device_starved_dispatches_total": 280,
          "engine_host_offcpu_time_total_s": 2.0, "engine_post_time_total_s": 3.75,
          "engine_post_emit_time_total_s": 2.125, "engine_no_work_time_total_s": 8.0,
          "spans": json.loads(json.dumps(rec.aggregate()))}
    return ctx_of(s0, s1)


@pytest.mark.parametrize("tree", ["real", "next"])
def test_the_eight_are_all_there_once_at_the_end_of_the_list(tree):
    """The eight, in order, once, where PR 44 appended them: nothing of the
    list before them moved, and whatever a later PR appends comes after."""
    bench = bench_of(tree)
    held = table_of(bench)
    assert [m["name"] for m in held] == list(TABLE)
    assert in_place([m["name"] for m in bench["per_layer"]])


def test_an_entry_put_among_or_before_the_eight_is_seen():
    names = [m["name"] for m in bench_of("real")["per_layer"]]
    first = names.index("device_starved_share")
    for at in (0, first, first + 3):
        assert not in_place(names[:at] + ["new.metric"] + names[at:])
    assert in_place(names + ["new.metric"])         # appended after them: as a later PR does


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_an_entry_names_its_cells_its_layer_and_a_reader_the_benchmark_had(tree, metric, roots):
    reader, unit, _ = TABLE[metric["name"]]
    assert spec_of(metric, roots[tree])["reader"] == reader
    assert metric == {"name": metric["name"], "unit": unit, "better": "lower",
                      "source": "program_counter", "layer": "engine step loop",
                      "moves": "itl_p95_ms", "workloads": CELLS}


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_a_metric_reads_the_hand_built_window(tree, metric, roots):
    want = TABLE[metric["name"]][2]
    got = read(metric, roots[tree], window())
    if metric["name"].endswith("_ms") and "p9" in metric["name"]:
        # a percentile from the histogram: within one bucket of the sample
        assert want / HIST_RATIO <= got <= want * HIST_RATIO
    else:
        assert got == pytest.approx(want)


def test_a_stalled_step_shows_in_the_maximum_and_past_the_99th_percentile(roots):
    ctx = window()
    row = ctx["stats1"]["stats"]["spans"]["series"]["engine"]["engine.step.decode"]
    assert row["max_s"] == 2.0
    from benchmark.readers import span_percentile

    p999 = span_percentile.read(ctx, "engine", "engine.step.decode", 99.9)
    assert 2000.0 / HIST_RATIO <= p999 <= 2000.0 * HIST_RATIO


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_a_program_without_the_account_reads_as_nothing(tree, metric, roots):
    """The parent's stats() keeps none of the new counters and none of the
    new series: the reader answers None, the line leaves the metric out."""
    rec = SpanRecorder(max_spans=8)
    rec.observe("engine.queue", 0.01, component="engine")
    old = {"engine_step_time_total_s": 1.0, "engine_busy_steps_total": 10,
           "engine_host_time_total_s": 0.2, "spans": rec.aggregate()}
    ctx = ctx_of(old, dict(old, engine_step_time_total_s=3.0, engine_busy_steps_total=40,
                           engine_host_time_total_s=0.9))
    assert read(metric, roots[tree], ctx) is None
    assert read(metric, roots[tree], {"stats0": None, "stats1": None}) is None


def test_a_window_with_no_episode_leaves_the_gap_out_and_the_shares_at_zero(roots):
    ctx = window()
    s0, s1 = ctx["stats0"]["stats"], ctx["stats1"]["stats"]
    for key in ("device_starved_time_total_s", "device_starved_dispatches_total"):
        s1[key] = s0[key]
    s1["spans"]["series"]["engine"]["engine.starved"] = s0["spans"]["series"]["engine"]["engine.starved"]
    by_name = {m["name"]: m for m in table_of(bench_of("real"))}
    assert read(by_name["starved_gap_p95_ms"], roots["real"], ctx) is None
    assert read(by_name["device_starved_share"], roots["real"], ctx) == 0.0
    assert read(by_name["starved_dispatch_share"], roots["real"], ctx) == 0.0
