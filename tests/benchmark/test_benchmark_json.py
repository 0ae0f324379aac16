"""BENCHMARK.json against the rules a refusal would cite, and against the
files it names."""

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
                    r"expansion|experts_per_tok")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("name", [m["name"] for m in METRICS] + CELLS
                         + [c["name"] for c in BENCH["configs"]]
                         + [w["traffic"] for w in BENCH["workloads"]])
def test_names_keep_to_the_character_rules(name):
    assert NAME.match(name), name


def test_names_are_unique():
    for group in ([m["name"] for m in METRICS], CELLS, [c["name"] for c in BENCH["configs"]]):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    end = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == allowed - {"workloads"}
    assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(cells_of(metric)) <= set(CELLS) and cells_of(metric)
    if end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_cell_that_reports_a_per_layer_metric_reports_what_it_moves(metric):
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    assert set(cells_of(metric)) <= set(cells_of(moved))
    if "workloads" not in metric:
        assert "workloads" not in moved


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer(cell):
    end = [m["name"] for m in BENCH["end_to_end"] if cell in cells_of(m)]
    assert "setup_s" in end and len(end) >= 2
    assert any(cell in cells_of(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_find_their_files(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    assert entry["config"] in [c["name"] for c in BENCH["configs"]]
    mix = json.loads((ROOT / "benchmark" / "traffic" / f"{entry['traffic']}.json").read_text())
    assert {"arrivals", "prompt_tokens", "output_tokens", "schedule_seed"} <= set(mix)
    own = json.loads((ROOT / "benchmark" / "cells" / f"{entry['name']}.json").read_text())
    assert own["rate_rps"] > 0


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations_quote_their_source_and_cut_no_width(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"].startswith("https://") and len(entry["source"]) <= 200
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert entry["name"] in [w["config"] for w in BENCH["workloads"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key)
        assert config["published"][key] != config[key]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "num_hidden_layers", "serving", "limits"):
        assert key in config
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_have_a_reader_of_their_own(metric):
    spec = json.loads((ROOT / "benchmark" / "metrics" / f"{metric['name']}.json").read_text())
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    assert reader.read({"records": [], "seconds": 1.0, "e2e": {}, "hf": {}},
                       **spec.get("args", {})) is None  # nothing to read: nothing


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(f.relative_to(ROOT))), f


def test_setup_keeps_its_bound_and_roofline_names_their_unit():
    setup = {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"]
    assert setup["bound"] <= 0.1 and "workloads" not in setup
    for m in METRICS:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
