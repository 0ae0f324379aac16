"""A configuration, a mix, a cell and a per-layer metric are each added as
new files plus one entry: nothing that is there is edited.  The tests of the
real tree run on the repository and on the copy with the next PR's files
added (``trees.py``)."""

import json

import pytest

from benchmark import run as runner
from benchmark import shapes

from .helpers import tiny_bench
from .trees import TREES, bench_of, config_of, each


def test_a_new_cell_configuration_mix_and_metric_are_found_by_name(tmp_path):
    bench_path = tiny_bench(tmp_path)
    bench = json.loads(bench_path.read_text())
    # one more mix, one more cell on it, one more metric: files + entries
    mix = json.loads((tmp_path / "traffic" / "tinychat.json").read_text())
    mix["prompt_tokens"] = {"dist": "constant", "value": 17}
    (tmp_path / "traffic" / "fixed17.json").write_text(json.dumps(mix))
    (tmp_path / "cells" / "tiny.fixed17.json").write_text(json.dumps(
        {"rate_rps": 7.5, "serving_args": ["--max-batch-size", 2]}))
    bench["workloads"].append({"name": "tiny.fixed17", "config": "tiny", "traffic": "fixed17",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "preemptions.new", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "engine scheduler + KV manager",
                               "moves": "itl_p95_ms", "workloads": ["tiny.fixed17"]})
    (tmp_path / "metrics" / "preemptions.new.json").write_text(json.dumps(
        {"reader": "counter_delta", "args": {"counter": "num_preemptions_total"}}))

    loaded = runner.load_cell(bench, "tiny.fixed17", tmp_path)
    assert loaded["rate"] == 7.5 and loaded["config"]["hidden_size"] == 64
    assert loaded["own"]["serving_args"] == ["--max-batch-size", 2]
    from benchmark import traffic

    plan = traffic.schedule(loaded["mix"], loaded["rate"], 4.0)
    assert {r["prompt_len"] for r in plan} == {17}

    ctx = {"records": [], "seconds": 4.0, "e2e": {}, "hf": {},
           "stats0": {"stats": {"num_preemptions_total": 2, "compiles_total": 5}},
           "stats1": {"stats": {"num_preemptions_total": 5, "compiles_total": 5}}}
    new = runner.read_per_layer(bench, "tiny.fixed17", ctx, tmp_path)
    assert new["preemptions.new"] == {"value": 3.0, "unit": "count"}
    old = runner.read_per_layer(bench, "tiny.tinychat", ctx, tmp_path)
    assert "preemptions.new" not in old and old["window_compiles.chat"]["value"] == 0.0


@pytest.mark.parametrize("tree,cell", each(lambda bench: bench["workloads"]))
def test_the_real_cells_load_and_size_as_their_files_say(tree, cell, roots):
    """Each cell is held to its configuration's OWN shapes module, so a
    configuration with experts, a latent cache or a recurrent state is sized
    by the arithmetic it brought."""
    loaded = runner.load_cell(bench_of(tree), cell["name"], roots[tree] / "benchmark")
    hf, own = runner.hf_config(loaded["config"]), loaded["shapes"]
    serving = loaded["config"]["serving"]
    assert own.cache_bytes(hf, serving) == serving["kv_bytes"]
    # weights alone pass a quarter of the chip's 16 GB; weights + two
    # copies of the cache stay under it.  (The one exception is a toy of the
    # next tree that the tests instantiate on the CPU: its file says so under
    # ``notes``, and the repository itself may hold none.)
    toy = "toy" in loaded["config"].get("notes", {})
    assert not (toy and tree == "real")
    assert own.weight_bytes(hf) > 4e9 or toy
    assert 2 * own.total_params(hf) + 2 * serving["kv_bytes"] < 15e9
    assert loaded["reference"].is_file()
    # nothing of the harness's leaks into the served config.json
    assert not {"limits", "reference", "shapes", "serving"} & set(hf)


def llama_like(bench):
    """The configurations sized by ``benchmark/shapes.py``, a per-token cache
    of keys and values; one that names a module of its own says there what
    its cache is made of, and the test above holds it to that."""
    return [c for c in bench["configs"] if config_of(c)["shapes"] == "shapes"]


@pytest.mark.parametrize("tree,entry", each(llama_like))
def test_the_llama_like_cache_is_blocks_of_tokens(tree, entry, roots):
    config = json.loads((roots[tree] / entry["file"]).read_text())
    hf, serving = runner.hf_config(config), config["serving"]
    blocks = serving["args"][serving["args"].index("--num-blocks") + 1]
    assert blocks * 16 == serving["kv_tokens"]
    assert serving["kv_tokens"] * shapes.kv_bytes_per_token(hf) == serving["kv_bytes"]
    assert shapes.cache_bytes(hf, {"args": ["--num-blocks", 10, "--kv-block-size", 32]}) \
        == 320 * shapes.kv_bytes_per_token(hf)


@pytest.mark.parametrize("key", ["reference", "shapes"])
@pytest.mark.parametrize("how", ["left out", "not a bare name", "no such file"])
def test_a_configuration_that_names_no_module_of_its_own_is_an_error(tmp_path, key, how):
    """Nothing falls back to a default reference or a default block."""
    bench_path = tiny_bench(tmp_path)
    bench = json.loads(bench_path.read_text())
    assert runner.load_cell(bench, "tiny.tinychat", tmp_path)["reference"].name == "llama_like.py"
    path = tmp_path / "configs" / "tiny.json"
    config = json.loads(path.read_text())
    if how == "left out":
        del config[key]
    else:
        config[key] = {"not a bare name": "../reference/llama_like", "no such file": "absent"}[how]
    path.write_text(json.dumps(config))
    with pytest.raises(SystemExit, match=f"{key} module"):
        runner.load_cell(bench, "tiny.tinychat", tmp_path)


def test_a_run_is_cold_until_one_of_its_own_cell_has_reached_its_end(tmp_path):
    """The long deadline is decided by two markers the harness writes itself,
    per cell and checkout: one under the cache directory (a cache that is
    warm for another cell says nothing), one in the checkout (a cache
    directory that outlives the machine brings its markers along; the new
    machine compiles all the same)."""
    cache, work = tmp_path / "cache", tmp_path / "work"
    cache.mkdir()
    (cache / "jit_some_program-of-another-cell").write_text("x")
    mine, other = runner.warm_markers(cache, "a.chat", work), runner.warm_markers(cache, "b.chat", work)
    assert not set(mine) & set(other) and (mine[0].parent, mine[1].parent) == (cache, work)
    for marker in other:
        marker.parent.mkdir(exist_ok=True)
        marker.touch()
    # a first run of `a` still compiles; one that follows a run that reached its end does not
    assert runner.run_deadline(100.0, mine) == 100.0 + runner.COLD_DEADLINE_S
    for marker in mine:
        marker.touch()
    assert runner.run_deadline(100.0, runner.warm_markers(cache, "a.chat", work)) == 100.0 + runner.RUN_DEADLINE_S
    # another cache directory, or the old one under a fresh checkout: cold again
    assert runner.run_deadline(100.0, runner.warm_markers(tmp_path / "moved", "a.chat", work)) == 100.0 + runner.COLD_DEADLINE_S
    assert runner.run_deadline(100.0, runner.warm_markers(cache, "a.chat", tmp_path / "fresh")) == 100.0 + runner.COLD_DEADLINE_S


@pytest.mark.parametrize("tree", TREES)
def test_a_cell_without_a_rate_of_its_own_is_an_error(tmp_path, tree, roots):
    """The offered rate is written in one place, the cell's own file: no
    mix carries one and nothing falls back to a default."""
    bench_path = tiny_bench(tmp_path)
    bench = json.loads(bench_path.read_text())
    assert runner.load_cell(bench, "tiny.tinychat", tmp_path)["rate"] == 3.0
    (tmp_path / "cells" / "tiny.tinychat.json").write_text(json.dumps({}))
    with pytest.raises(SystemExit):
        runner.load_cell(bench, "tiny.tinychat", tmp_path)
    (tmp_path / "cells" / "tiny.tinychat.json").unlink()
    with pytest.raises(SystemExit):
        runner.load_cell(bench, "tiny.tinychat", tmp_path)
    mixes = list((roots[tree] / "benchmark" / "traffic").glob("*.json"))
    assert len(mixes) >= 3
    for mix in mixes:
        assert "rate_rps" not in json.loads(mix.read_text()), mix


def test_the_command_line_offers_no_other_load_or_serving():
    for extra in (["--rate", "9"], ["--server-arg", "--quantize"], ["--control", "int8"]):
        with pytest.raises(SystemExit):
            runner.parse(["--workload", "x", "--seed", "1", "--seconds", "1", *extra])


@pytest.mark.parametrize("tree", TREES)
def test_an_unknown_cell_is_an_error(tree, roots):
    with pytest.raises(SystemExit):
        runner.load_cell(bench_of(tree), "no.such-cell", roots[tree] / "benchmark")


@pytest.mark.parametrize("tree", TREES)
def test_shapes_match_the_published_sizes(tree, roots):
    sizes = {}
    for c in llama_like(bench_of(tree)):
        hf = runner.hf_config(json.loads((roots[tree] / c["file"]).read_text()))
        sizes[c["name"]] = (shapes.total_params(hf), shapes.kv_bytes_per_token(hf))
    assert abs(sizes["qwen3-4b"][0] - 4.02e9) < 0.01e9 and sizes["qwen3-4b"][1] == 147456
    assert abs(sizes["mistral-7b-l16"][0] - 3.75e9) < 0.01e9
    assert sizes["mistral-7b-l16"][1] == 65536
    with pytest.raises(KeyError):
        shapes.load_peaks("cpu")
    assert shapes.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
