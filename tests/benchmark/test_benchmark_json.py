"""BENCHMARK.json against the rules a refusal would cite, and against the
files it names: on the repository, and on a copy to which the next PR's
files and entries have been added (``trees.py``)."""

import importlib
import json
import re

import pytest

from .trees import TREES, bench_of, each

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
                    r"expansion|experts_per_tok")


def cells(bench):
    return [w["name"] for w in bench["workloads"]]


def metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def cells_of(metric, bench):
    return metric.get("workloads", cells(bench))


def names(bench):
    return ([m["name"] for m in metrics(bench)] + cells(bench) + [c["name"] for c in bench["configs"]]
            + [w["traffic"] for w in bench["workloads"]])


@pytest.mark.parametrize("tree", TREES)
def test_top_level_keys_and_limits(tree, roots):
    bench = bench_of(tree)
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["workloads"]) <= 24 and 1 <= len(bench["configs"]) <= 24
    assert json.loads((roots[tree] / "BENCHMARK.json").read_text()) == bench
    assert len((roots[tree] / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells(bench)) // 4)


@pytest.mark.parametrize("tree,name", each(names, ident=str))
def test_names_keep_to_the_character_rules(tree, name):
    assert NAME.match(name), name


@pytest.mark.parametrize("tree", TREES)
def test_names_are_unique(tree):
    bench = bench_of(tree)
    for group in ([m["name"] for m in metrics(bench)], cells(bench),
                  [c["name"] for c in bench["configs"]]):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("tree,metric", each(metrics))
def test_metric_entries(tree, metric):
    bench = bench_of(tree)
    known = cells(bench)
    end = metric in bench["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == allowed - {"workloads"}
    assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(cells_of(metric, bench)) <= set(known) and cells_of(metric, bench)
    if end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]


@pytest.mark.parametrize("tree,metric", each(lambda bench: bench["per_layer"]))
def test_every_cell_that_reports_a_per_layer_metric_reports_what_it_moves(tree, metric):
    bench = bench_of(tree)
    moved = {m["name"]: m for m in bench["end_to_end"]}[metric["moves"]]
    assert set(cells_of(metric, bench)) <= set(cells_of(moved, bench))
    if "workloads" not in metric:
        assert "workloads" not in moved


@pytest.mark.parametrize("tree,cell", each(cells, ident=str))
def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer(tree, cell):
    bench = bench_of(tree)
    end = [m["name"] for m in bench["end_to_end"] if cell in cells_of(m, bench)]
    assert "setup_s" in end and len(end) >= 2
    assert any(cell in cells_of(m, bench) for m in bench["per_layer"])


@pytest.mark.parametrize("tree,entry", each(lambda bench: bench["workloads"]))
def test_cells_find_their_files(tree, entry, roots):
    bench, root = bench_of(tree), roots[tree]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    assert entry["config"] in [c["name"] for c in bench["configs"]]
    mix = json.loads((root / "benchmark" / "traffic" / f"{entry['traffic']}.json").read_text())
    # a mix of sessions states its turns' lengths there, any other at the top
    assert {"arrivals", "output_tokens", "schedule_seed"} <= set(mix)
    assert "prompt_tokens" in mix or {"first_prompt_tokens", "turn_tokens"} <= set(mix["sessions"])
    own = json.loads((root / "benchmark" / "cells" / f"{entry['name']}.json").read_text())
    assert own["rate_rps"] > 0


@pytest.mark.parametrize("tree,entry", each(lambda bench: bench["configs"]))
def test_configurations_quote_their_source_and_cut_no_width(tree, entry, roots):
    bench, root = bench_of(tree), roots[tree]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"].startswith("https://") and len(entry["source"]) <= 200
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    assert entry["name"] in [w["config"] for w in bench["workloads"]]
    config = json.loads((root / entry["file"]).read_text())
    assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key)
        assert config["published"][key] != config[key]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "num_hidden_layers", "serving", "limits"):
        assert key in config
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("tree,metric", each(lambda bench: bench["per_layer"]))
def test_per_layer_metrics_have_a_reader_of_their_own(tree, metric, roots):
    root = roots[tree]
    spec = json.loads((root / "benchmark" / "metrics" / f"{metric['name']}.json").read_text())
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    assert reader.read({"records": [], "seconds": 1.0, "e2e": {}, "hf": {}},
                       **spec.get("args", {})) is None  # nothing to read: nothing


@pytest.mark.parametrize("tree", TREES)
def test_files_under_paths_are_named_from_name_characters(tree, roots):
    root, seen = roots[tree], 0
    for p in bench_of(tree)["paths"]:      # the copy holds ``benchmark/`` alone
        for f in (root / p).rglob("*") if (root / p).is_dir() else ():
            if "__pycache__" in f.parts:
                continue
            seen += 1
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(f.relative_to(root))), f
    assert seen > 50


@pytest.mark.parametrize("tree", TREES)
def test_setup_keeps_its_bound_and_roofline_names_their_unit(tree):
    bench = bench_of(tree)
    setup = {m["name"]: m for m in bench["end_to_end"]}["setup_s"]
    assert setup["bound"] <= 0.1 and "workloads" not in setup
    rooflines = [m for m in metrics(bench) if "_roofline" in m["name"] or "mfu" in m["name"]]
    assert all(m["unit"] == "%" for m in rooflines)
    assert {"ragged_attn_roofline.long", "decode_attn_roofline.chat"} <= {m["name"] for m in rooflines}
