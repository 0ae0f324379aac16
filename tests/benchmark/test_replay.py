"""The tail of a cell that opens on a backlog is read over a fixed set of
answers (``gap_requests``): on each such cell's REAL plan, served on the host
by ``benchmark/replay.py``'s model of today's server, through the entry
points ``run.py`` itself calls.

- only the decode steps get shorter: the reading stays where it was, while
  the tail of every gap that closed inside the window (the reading until
  PR 32, kept as ``itl_p95_window_ms.long``) moves by whole windows at some
  of those speeds, on one of the cells by more than the metric's bound - the
  control: the fault this reduction exists to cure is still shown;
- every prompt window gets 3% longer or shorter: the reading follows;
- a window of 45 s reads what one of 51 s reads;
- and the range keeps to the rules of benchmark/README.md.
"""

import json

import pytest

from benchmark import arith, replay

from .trees import ADDED, ROOT, bench_of, each

# the bound BENCHMARK.json holds ``itl_p95_ms`` to (3% until PR 49, 5.5% since)
BOUND = next(m["bound"] for m in bench_of("real")["end_to_end"] if m["name"] == "itl_p95_ms")


def own_of(cell: str) -> dict:
    path = ROOT / "benchmark" / "cells" / f"{cell}.json"
    return json.loads((path if path.exists() else ADDED / "benchmark" / "cells" / f"{cell}.json").read_text())


def fixed_set_cells(bench):
    return [w for w in bench["workloads"] if "gap_requests" in own_of(w["name"])]


class Cell:
    def __init__(self, tree, entry, roots):
        from benchmark import run as runner

        bench = bench_of(tree)
        self.loaded = runner.load_cell(bench, entry["name"], roots[tree] / "benchmark")
        self.own, self.mix = self.loaded["own"], self.loaded["mix"]
        self.lanes = replay.lanes_of(self.loaded)
        self.seconds = float(bench["run_seconds"])

    def records(self, seconds=None, **scales):
        return replay.serve_cell(self.loaded, seconds or self.seconds, **scales)

    def read(self, seconds=None, **scales) -> dict:
        seconds = seconds or self.seconds
        return arith.end_to_end(self.records(seconds, **scales), seconds, 0.0,
                                self.own["gap_requests"])


@pytest.fixture
def cell(request, roots):
    tree, entry = request.param
    return Cell(tree, entry, roots)


def each_cell():
    return pytest.mark.parametrize("cell", [pytest.param((tree, entry), id=f"{tree}-{entry['name']}")
                                            for tree in ("real", "next")
                                            for entry in fixed_set_cells(bench_of(tree))], indirect=True)


@each_cell()
@pytest.mark.parametrize("scale", [0.94, 0.86, 0.78, 0.7])
def test_shorter_decode_steps_leave_the_fixed_set_reading_where_it_was(cell, scale):
    assert cell.read(decode_scale=scale)["itl_p95_ms"] == pytest.approx(cell.read()["itl_p95_ms"], rel=1e-9)


def window_tail_moves(cell) -> list[float]:
    """How far the tail of every gap that closed inside the window moves
    when the decode steps alone get 2% to 34% shorter."""
    was = cell.read()["itl_p95_window_ms"]
    return [cell.read(decode_scale=k / 100)["itl_p95_window_ms"] / was - 1 for k in range(66, 100, 4)]


@each_cell()
def test_control_the_tail_of_the_window_moves_though_no_step_got_longer(cell):
    """Plateaus and cliffs: which window the percentile names depends on how
    far the server gets, so some speed-ups of the decode step read as nothing
    and others as a whole window's worth of gain or loss.  How far it moves is
    asked of SOME cell (the next test): where 15 gaps in 100 are prompt
    windows at any speed, no shorter decode step moves the 95th percentile."""
    # the same gaps, the same percentile: only the set differs
    assert cell.read()["itl_p95_window_ms"] == arith.percentile(arith.gaps_ms(cell.records(), cell.seconds), 95)


@pytest.mark.parametrize("tree", ("real", "next"))
def test_control_on_some_cell_it_moves_past_the_bound(tree, roots):
    moves = [m for entry in fixed_set_cells(bench_of(tree)) for m in window_tail_moves(Cell(tree, entry, roots))]
    assert max(abs(m) for m in moves) > BOUND


@each_cell()
@pytest.mark.parametrize("scale", [1.03, 0.97])
def test_the_fixed_set_reading_follows_every_prompt_window(cell, scale):
    moved = cell.read(prompt_scale=scale)["itl_p95_ms"] / cell.read()["itl_p95_ms"]
    assert moved == pytest.approx(scale, rel=0.005)


@each_cell()
def test_a_window_of_45_s_reads_what_one_of_51_s_reads(cell):
    long, short = cell.read(51.0), cell.read(45.0)
    assert short["itl_p95_ms"] == long["itl_p95_ms"]
    assert short["itl_p95_window_ms"] != long["itl_p95_window_ms"]


@each_cell()
def test_the_range_keeps_to_the_rules(cell):
    first, last = cell.own["gap_requests"]
    assert cell.mix["backlog"]["requests"] > 0          # the queue never empties
    assert 2 * cell.lanes <= first < last               # two turnovers of the lanes are out
    where = replay.placement(cell.records(), (first, last), cell.seconds)
    assert where["requests"] == last - first >= 28 and where["gaps"] >= 2000
    # on today's server, as the replay has it: inside the window, and with a
    # tenth of it to spare unless the least range allowed leaves no such room
    assert 0.0 <= where["opens_s"] and where["closes_s"] <= cell.seconds
    assert where["closes_s"] <= 0.9 * cell.seconds or last - first == 28
    assert last <= where["first_answered_by_the_close"]


@pytest.mark.parametrize("tree,entry", each(lambda bench: bench["workloads"]))
def test_a_cell_carries_a_range_where_its_mix_opens_on_a_backlog_and_nowhere_else(tree, entry, roots):
    root = roots[tree] / "benchmark"
    mix = json.loads((root / "traffic" / f"{entry['traffic']}.json").read_text())
    own = json.loads((root / "cells" / f"{entry['name']}.json").read_text())
    assert ("gap_requests" in own) == bool((mix.get("backlog") or {}).get("requests"))
    assert ("replay" in own) == ("gap_requests" in own)


def test_the_replay_serves_first_come_one_prompt_a_step():
    model = {"admit_lag_steps": 1, "decode_ms": 10.0, "prompt_ms": {"fixed": 100.0, "per_bucket_token": 0.0, "per_token_sq": 0.0}}
    plan = [{"index": i, "due": -1.0 + 0.01 * i, "prompt_len": 20, "output_len": n, "probe": 0}
            for i, n in enumerate([3, 2, 4])] + [{"index": 3, "due": 9.0, "prompt_len": 20, "output_len": 2, "probe": 20}]
    a, b, c = replay.serve(plan, model, 2, 1.0)
    # two lanes: a and b are admitted a step apart, c waits for a lane and
    # gets it one (empty) step after both were given up
    assert [t for t, _ in a["chunks"]] == pytest.approx([-0.9, -0.8, -0.79])
    assert [t for t, _ in b["chunks"]] == pytest.approx([-0.8, -0.79])
    assert [t for t, _ in c["chunks"]] == pytest.approx([-0.68, -0.67, -0.66, -0.65])
    assert a["prompt_tokens"] == 24 and [r["index"] for r in (a, b, c)] == [0, 1, 2]
    # what the clients saw of the two windows that had an arrival before them
    # (c's holds the empty step: nobody answered in it)
    rows = [[r["index"], r["due"], r["due"], r["prompt_tokens"], None, [t for t, _ in r["chunks"]]] for r in (a, b, c)]
    assert sorted(ms for _, ms in replay.prompt_windows(rows)) == pytest.approx([100.0, 110.0])
