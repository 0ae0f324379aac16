"""The dry run: what the next PRs of the queue bring (``next_tree/``: a
configuration with a latent cache and a recurrent state a lane, its cell, a
reasoning mix, a mix of sessions, four per-layer metrics; and a configuration
that generates by passes over a block, whose reference says itself which row
decided a served token, its cell, its mix, three per-layer metrics) is added
to a copy of the real tree as new files and appended entries, and the copy is
held to everything the real tree is held to.  The tree-wide tests of the other files
run on that copy too; here is what only the addition can show."""

import copy
import json

import jax
import pytest

from benchmark import modules, run as runner, traffic
from benchmark.readers import kernel_roofline
from benchmark.server import write_model_dir

from . import trees
from .trees import ROOT, bench_of, each

CELL = "hybrid-toy.reasoning-toy"
BLOCKS = "blockdiff-toy.blocks-toy"


def test_the_next_tree_is_the_real_one_with_files_added_and_lists_grown(roots):
    """``make_next`` has already refused a copy in which a file of the real
    tree differs or BENCHMARK.json changed otherwise than by lists growing;
    this counts what was added."""
    added = trees.added_files()
    assert len(added) == 18 and all(not (ROOT / rel).exists() for rel in added)
    assert all((roots["next"] / rel).is_file() for rel in added)
    real, nxt = bench_of("real"), bench_of("next")
    grew = {key: len(nxt[key]) - len(real[key]) for key in ("configs", "workloads", "end_to_end", "per_layer")}
    assert grew == {"configs": 2, "workloads": 2, "end_to_end": 0, "per_layer": 7}
    assert all(nxt[key] == real[key] for key in ("command", "paths", "run_seconds"))


@pytest.mark.parametrize("edit", ["a bound", "an entry gone", "an entry put first", "a key more"])
def test_anything_but_a_list_grown_at_its_end_is_refused(edit):
    real = bench_of("real")
    changed = copy.deepcopy(real)
    if edit == "a bound":
        changed["end_to_end"][1]["bound"] = 0.05
    elif edit == "an entry gone":
        del changed["per_layer"][3]
    elif edit == "an entry put first":
        changed["workloads"].insert(0, dict(changed["workloads"][0], name="new.cell"))
    else:
        changed["per_layer"][0]["why"] = "no such key"
    with pytest.raises(AssertionError):
        trees.grown_only(real, changed)
    trees.grown_only(real, bench_of("next"))


@pytest.mark.parametrize("tree,entry", each(lambda bench: bench["configs"]))
def test_every_configuration_brings_the_two_interfaces(tree, entry, roots):
    config = json.loads((roots[tree] / entry["file"]).read_text())
    bench_dir = roots[tree] / "benchmark"
    reference = modules.load(modules.path_of(config, "reference", bench_dir, entry["name"]))
    shapes = modules.load(modules.path_of(config, "shapes", bench_dir, entry["name"]))
    for name in ("init_weights", "hidden", "logits", "quantize"):
        assert callable(getattr(reference, name)), name
    # the fifth is offered by the one configuration whose tokens are decided
    # by a pass over their block, and by no configuration of the repository
    assert hasattr(reference, "decided_by") == (entry["name"] == BLOCKS.split(".")[0])
    hf = runner.hf_config(config)
    for name in ("total_params", "matmul_params", "weight_bytes", "kv_bytes_per_token", "flops_per_token"):
        assert getattr(shapes, name)(hf) > 0, name
    assert shapes.cache_bytes(hf, config["serving"]) == config["serving"]["kv_bytes"]
    assert shapes.flops_per_token(hf) == 2 * shapes.matmul_params(hf) <= 2 * shapes.total_params(hf)


def test_the_hybrid_configuration_is_sized_by_its_own_module(roots, tmp_path):
    loaded = runner.load_cell(bench_of("next"), CELL, roots["next"] / "benchmark")
    config, own = loaded["config"], loaded["shapes"]
    hf, serving = runner.hf_config(config), config["serving"]
    # what reaches the served config.json: the published keys, nested groups
    # whole, and the program's own key; nothing of the harness's
    write_model_dir(tmp_path / "model", config)
    served = json.loads((tmp_path / "model" / "config.json").read_text())
    assert served == hf and served["expert_parallel_size"] == 8
    assert served["linear_attn_config"]["kda_layers"] and served["linear_attn_config"]["full_attn_layers"]
    assert not {"published", "deployment", "reduced", "serving", "limits", "reference", "shapes"} & set(served)
    assert hf["head_dim"] != hf["hidden_size"] // hf["num_attention_heads"]
    assert config["published"]["num_experts"] == hf["num_experts"] * hf["expert_parallel_size"]
    # no max_position_embeddings: the tokenizer takes the served context
    tokenizer = json.loads((tmp_path / "model" / "tokenizer_config.json").read_text())
    assert tokenizer["model_max_length"] == 8192
    # the cache: pages of a latent cache PLUS a recurrent state a lane; the
    # llama-like module would have said 2 x layers x heads x head_dim a token
    latent = len(hf["linear_attn_config"]["full_attn_layers"]) * (512 + 64) * 2
    assert own.kv_bytes_per_token(hf) == latent == 4608
    lanes = serving["args"][serving["args"].index("--max-batch-size") + 1]
    assert serving["kv_bytes"] == serving["kv_tokens"] * latent + lanes * own.state_bytes_per_lane(hf)
    assert own.state_bytes_per_lane(hf) > 12 * 16 * 128 * 128 * 4
    from benchmark import shapes as llama_like

    assert llama_like.kv_bytes_per_token(hf) * serving["kv_tokens"] != serving["kv_bytes"]
    # the reference's stubs return shapes, at the cell's sizes
    reference = modules.load(loaded["reference"])
    rows = reference.hidden(reference.init_weights(hf, 1), hf, list(range(300)))
    assert rows.shape == (300, hf["hidden_size"])
    assert reference.logits(None, hf, jax.ShapeDtypeStruct((256, 2048), "float32")).shape == (256, 16384)


def test_the_new_cell_reports_what_its_entries_say(roots):
    bench = bench_of("next")
    end = [m["name"] for m in runner.metrics_of(bench, CELL, "end_to_end")]
    assert end == ["itl_p50_ms", "itl_p95_ms", "setup_s"]       # appended to the first one's list
    assert [m["name"] for m in runner.metrics_of(bench, "qwen3-4b.chat", "end_to_end")] == end
    assert [m["name"] for m in runner.metrics_of(bench, BLOCKS, "end_to_end")] == end
    specs = runner.metric_specs(bench, CELL, roots["next"] / "benchmark")
    assert [m["name"] for m, _ in specs] == [
        "step_ms_decode.reasoning-toy", "preemptions.reasoning-toy",
        "kda_decode_share.reasoning-toy", "kda_decode_roofline.reasoning-toy"]
    assert runner.op_patterns(specs) == ["^%?kda_recurrent_decode"]
    # the real cells' patterns are the three their own metric files name
    real = runner.metric_specs(bench, "qwen3-4b.long-prompt", roots["next"] / "benchmark")
    assert runner.op_patterns(real) == ["^%?(ragged_)?paged_(window_)?attention", "^%?ragged_paged_attention"]
    assert not {m["name"] for m, _ in real} & {m["name"] for m, _ in specs}
    # a layer the benchmark does not name yet
    layers = {m["layer"] for m in bench_of("real")["per_layer"]}
    assert {m["layer"] for m, _ in specs} - layers == {"recurrent-state kernels"}


def test_a_new_kernels_roofline_is_a_metric_file_and_nothing_else(roots):
    """``kda_decode_roofline.reasoning-toy`` is an entry and a file over the
    reader the real tree has: on a window in which a program counted that
    kernel's work and the trace holds its operations, the cell reports it."""
    bench, bench_dir = bench_of("next"), roots["next"] / "benchmark"
    assert not (bench_dir / "readers" / "kda_decode_roofline.py").exists()
    ops = {"%kda_recurrent_decode.7 = f32[32,16,128,128]": 1.0, "%fusion.3": 2.0, "%copy.1": 1.0}
    trace = {"busy_s": 4.0, "window_s": 4.0, "chips": 1, "ops": ops,
             "op_events": {name: 100 for name in ops}}
    zero = {"kda_decode_flops_total": 0, "kda_decode_state_bytes_total": 0, "num_preemptions_total": 0,
            "engine_step_time_total_s": 0.0, "engine_decode_step_time_total_s": 0.0,
            "engine_decode_steps_total": 0}
    end = {"kda_decode_flops_total": 20e12, "kda_decode_state_bytes_total": 3.276e12,   # 4 s at 819 GB/s
           "num_preemptions_total": 0, "engine_step_time_total_s": 40.0,
           "engine_decode_step_time_total_s": 38.0, "engine_decode_steps_total": 1000}
    ctx = {"records": [], "seconds": 51.0, "e2e": {}, "hf": {}, "trace": trace,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "stats0": {"stats": zero}, "stats1": {"stats": end}}
    got = runner.read_per_layer(bench, CELL, ctx, bench_dir)
    assert got["kda_decode_roofline.reasoning-toy"] == {"value": pytest.approx(40.0), "unit": "%"}
    assert got["kda_decode_share.reasoning-toy"]["value"] == pytest.approx(25.0)
    assert got["step_ms_decode.reasoning-toy"]["value"] == pytest.approx(38.0)
    assert got["preemptions.reasoning-toy"]["value"] == 0.0
    assert kernel_roofline.matched(ctx, "^%?kda_recurrent_decode") == (1.0, 100)
    # a program that does not keep those counters: the roofline is left out
    ctx["stats1"]["stats"].pop("kda_decode_flops_total")
    assert "kda_decode_roofline.reasoning-toy" not in runner.read_per_layer(bench, CELL, ctx, bench_dir)


def test_a_cell_whose_server_answers_a_block_a_chunk_comes_as_files(roots):
    """``blockdiff-toy.blocks-toy``: one reference module with ``decided_by``,
    one shapes module, one configuration, one mix without a backlog, one cell
    file, three metric files over readers the tree has, appended entries."""
    bench, bench_dir = bench_of("next"), roots["next"] / "benchmark"
    mine = [str(rel) for rel in trees.added_files() if "block" in rel.name]
    assert mine == ["benchmark/blockdiff_toy_shapes.py", "benchmark/cells/blockdiff-toy.blocks-toy.json",
                    "benchmark/configs/blockdiff-toy.json", "benchmark/metrics/preemptions.blocks-toy.json",
                    "benchmark/metrics/step_ms_decode.blocks-toy.json",
                    "benchmark/metrics/tokens_per_pass.blocks-toy.json",
                    "benchmark/reference/blockdiff_toy.py", "benchmark/traffic/blocks-toy.json"]
    loaded = runner.load_cell(bench, BLOCKS, bench_dir)
    config, hf = loaded["config"], runner.hf_config(loaded["config"])
    assert "backlog" not in loaded["mix"] and not {"gap_requests", "replay"} & set(loaded["own"])
    # the decoding settings reach the served config.json; the harness's keys do not
    assert (hf["block_length"], hf["tokens_per_pass"]) == (4, 2)
    assert traffic.RESERVED <= hf["mask_token_id"] < hf["vocab_size"]      # a word a prompt may hold
    assert not {"notes", "assumed", "limits", "serving"} & set(hf) and "toy" in config["notes"]
    # a served token costs its block's passes and the committing one
    assert loaded["shapes"].rows_per_token(hf) == 3
    specs = runner.metric_specs(bench, BLOCKS, bench_dir)
    assert [m["name"] for m, _ in specs] == ["step_ms_decode.blocks-toy", "preemptions.blocks-toy",
                                             "tokens_per_pass.blocks-toy"]
    assert not [rel for rel in trees.added_files() if rel.parts[:2] == ("benchmark", "readers")]
    zero = {"block_tokens_fixed_total": 0, "block_lane_passes_total": 0}
    ctx = {"stats0": {"stats": zero}, "stats1": {"stats": {"block_tokens_fixed_total": 900,
                                                           "block_lane_passes_total": 450}}}
    got = runner.read_per_layer(bench, BLOCKS, ctx, bench_dir)
    assert got == {"tokens_per_pass.blocks-toy": {"value": 2.0, "unit": "count"}}
    # the cell says how many tokens a chunk holds
    assert "a chunk is one committed block of 4 tokens" in loaded["cell"]["why"]
