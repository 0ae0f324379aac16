"""ISSUE 58's two per-layer metrics over the allocator's publish counters and
the `publish` part of `post`: each an entry of BENCHMARK.json appended behind
what the list held and a data file over ``counter_ratio``, read from a
recorded ``stats()`` pair, and nothing on a program that keeps no such
counter (the parent commit)."""

import pytest

from .test_loop_account_metrics import read
from .test_program_accounting import ctx_of, spec_of
from .trees import bench_of, each

CELLS = ["phi4-mini-flash.reasoning", "k-exaone-236b-l8.long-mixed",
         "moonlight-16b-l9.long-doc", "xing4-29b-l8.long-doc"]

# name -> (unit, what the recorded pair below reads)
TABLE = {
    "post_publish_ms_per_step": ("ms", 0.25),
    "publish_hash_blocks_per_stored": ("ratio", 1.0),
}

# two stats() of one engine, 2,000 busy steps apart: 1.5 blocks a step stored,
# each hashed once, in half a second of `publish`
S0 = {"engine_busy_steps_total": 400, "engine_post_time_total_s": 1.9,
      "engine_post_publish_time_total_s": 0.125,
      "kv_publish_blocks_hashed_total": 9_000, "kv_publish_blocks_stored_total": 9_000}
S1 = {"engine_busy_steps_total": 2_400, "engine_post_time_total_s": 10.3,
      "engine_post_publish_time_total_s": 0.625,
      "kv_publish_blocks_hashed_total": 12_000, "kv_publish_blocks_stored_total": 12_000}


def table_of(bench):
    return [m for m in bench["per_layer"] if m["name"] in TABLE]


@pytest.mark.parametrize("tree", ["real", "next"])
def test_the_two_are_there_once_behind_what_the_list_held(tree):
    names = [m["name"] for m in bench_of(tree)["per_layer"]]
    first = names.index("post_publish_ms_per_step")
    assert names[first:first + 2] == list(TABLE) and names.count(names[first + 1]) == 1
    assert names.index("moe_gather_combine_share.long-mixed") == first - 1   # PR 56's last


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_an_entry_names_its_four_cells_and_reads_two_counters(tree, metric, roots):
    unit, _ = TABLE[metric["name"]]
    assert spec_of(metric, roots[tree])["reader"] == "counter_ratio"
    assert metric == {"name": metric["name"], "unit": unit, "better": "lower",
                      "source": "program_counter", "layer": "engine step loop",
                      "moves": "itl_p95_ms", "workloads": CELLS}


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_a_metric_reads_the_recorded_pair(tree, metric, roots):
    assert read(metric, roots[tree], ctx_of(S0, S1)) == pytest.approx(TABLE[metric["name"]][1])


def test_the_whole_list_hashed_at_every_publish_reads_as_its_blocks(roots):
    """What the counter is for: a program that hashed a 300-block context for
    every block it stored would read 300."""
    by_name = {m["name"]: m for m in table_of(bench_of("real"))}
    again = dict(S1, kv_publish_blocks_hashed_total=9_000 + 300 * 3_000)
    assert read(by_name["publish_hash_blocks_per_stored"], roots["real"], ctx_of(S0, again)) == 300.0


@pytest.mark.parametrize("tree,metric", each(table_of))
def test_a_program_without_the_counters_reads_as_nothing(tree, metric, roots):
    old = {"engine_busy_steps_total": 10, "engine_post_time_total_s": 0.2}
    ctx = ctx_of(old, dict(old, engine_busy_steps_total=40, engine_post_time_total_s=0.9))
    assert read(metric, roots[tree], ctx) is None
    assert read(metric, roots[tree], {"stats0": None, "stats1": None}) is None
    # nothing stored in the window (a cell that publishes nothing): no ratio
    if metric["name"] == "publish_hash_blocks_per_stored":
        assert read(metric, roots[tree], ctx_of(S0, dict(S0, engine_busy_steps_total=900))) is None
