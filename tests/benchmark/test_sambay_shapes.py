"""``phi4-mini-flash``: the benchmark's own arithmetic at the published
sizes (the program's side of it is ``tests/models/test_phi4flash.py``), the
file against the catalog, the reference module under the comparison (the
control rounds what it should and nothing else, the reference's own tokens
pass and altered ones fail), and the new metrics' files over the readers that
exist: a hand-built window, and nothing from a program without the counters."""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from benchmark import run as runner
from benchmark.reference import check

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "phi4-mini-flash.json").read_text())
HF = runner.hf_config(CONFIG)
SHAPES = modules.load(ROOT / "benchmark" / "sambay_shapes.py")
REF = modules.load(ROOT / "benchmark" / "reference" / "sambay.py")
TINY = dict(HF, hidden_size=64, intermediate_size=96, num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=4, sliding_window=8, vocab_size=300, mamba_d_state=4,
            max_position_embeddings=512)
CELL = "phi4-mini-flash.reasoning"


def test_the_file_quotes_the_catalog_and_cuts_nothing():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    entry = next(r for r in map(json.loads, catalog.read_text().splitlines())
                 if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == entry["source_url"] and CONFIG["reduced"] == []
    for key, value in entry["config"].items():
        assert key in CONFIG and CONFIG[key] == value, key
    assert not set(CONFIG["assumed"]) & set(HF)     # none of it reaches the served config.json


def test_the_counts_are_the_issues_to_the_parameter():
    mixers = SHAPES.mixer_params(HF)
    mlp, norms = 3 * 2560 * 10240, 4 * 2560
    layer = {kind: sum(mixers[kind]) + mlp + norms for kind in mixers}
    assert layer == {"ssm": 119_895_040, "window": 98_322_304, "full": 98_322_304,
                     "gmu": 104_867_840, "cross": 91_766_144}
    assert SHAPES.total_params(HF) == 3_852_562_944
    assert SHAPES.flops_per_token(HF) == 2 * SHAPES.matmul_params(HF)
    assert SHAPES.weight_bytes(HF) == 2 * SHAPES.matmul_params(HF) + 4 * SHAPES.float32_params(HF)
    assert 7.70e9 < SHAPES.weight_bytes(HF) < 7.72e9
    # ONE layer's keys and values grow with the context; a lane's state does not
    assert SHAPES.kv_bytes_per_token(HF) == 5120
    assert SHAPES.state_bytes_per_lane(HF) == 9 * (4 * 16 * 5120 + 2 * 3 * 5120) == 3_225_600


def test_the_cache_is_one_full_layer_eight_windows_and_a_state_a_lane():
    serving = CONFIG["serving"]
    page = 16 * 5120
    assert SHAPES.window_pool_blocks(HF, 16, 4096) == 256 + 16 * 34 + 8 == 808
    assert SHAPES.cache_bytes(HF, serving) == serving["kv_bytes"] == (
        4160 * page + 808 * 8 * page + 16 * 3_225_600)
    assert serving["kv_tokens"] == 4160 * 16
    # weights and cache are over a quarter and under 60% of the chip's 16 GB
    assert 0.25 * 16e9 < SHAPES.weight_bytes(HF) + serving["kv_bytes"] < 0.6 * 16e9


def test_a_program_without_the_family_is_told_so_at_once(tmp_path, monkeypatch):
    """A checkout whose program has no models/phi4flash.py (the parent
    commit) is refused when the shapes module is loaded, before a server is
    started that could only die on the model's name."""
    (tmp_path / "dynamo_tpu" / "models").mkdir(parents=True)
    monkeypatch.syspath_prepend(str(tmp_path))
    with pytest.raises(SystemExit, match="phi4flash"):
        modules.load(ROOT / "benchmark" / "sambay_shapes.py")


def test_the_control_rounds_what_a_token_multiplies_and_nothing_else():
    w = REF.init_weights(TINY, 3)
    low = REF.quantize(dict(w), "fp8", TINY)
    kept = {k.split(".")[-1] for k in w
            if np.array_equal(np.asarray(w[k], np.float32), np.asarray(low[k], np.float32))}
    assert kept == {"conv_w", "b_dt", "lq1", "lk1", "lq2", "lk2"}
    assert {k for k in w if "." not in k} == {"embed"}      # the tied head is rounded too
    with pytest.raises(KeyError):
        REF.quantize(w, "int3", TINY)
    ids = list(range(5, 37))
    a, b = REF.forward(w, TINY, ids), REF.forward(low, TINY, ids)
    assert a.shape == (32, 300) and a.dtype == jnp.float32
    spread = float(jnp.std(a))
    assert 0.005 * spread < float(jnp.abs(a - b).mean()) < 0.3 * spread


def _job(served_shift=0, control=None):
    weights = REF.init_weights(TINY, 11)
    rng = np.random.default_rng(3)
    samples = []
    for i, n in enumerate((20, 33)):
        prompt = rng.integers(8, TINY["vocab_size"], n).tolist()
        served = []
        for _ in range(6):  # greedy by the reference itself
            padded = prompt + served + [0] * (64 - n - len(served))
            logits = REF.forward(weights, TINY, padded, rows=[n + len(served) - 1])
            served.append(int(jnp.argmax(logits[0])))
        served = [(t + served_shift) % TINY["vocab_size"] for t in served]
        samples.append({"index": i, "prompt_ids": prompt, "served_ids": served})
    return {"hf": TINY, "reference": REF.__file__, "weights_seed": 11, "samples": samples,
            "control": control}


def test_check_passes_the_references_own_tokens_and_fails_altered_ones_and_reads_the_control():
    sound = check.run(_job(control="fp8"))
    assert sound["tokens"] == 12 and sound["mismatch"] == 0
    assert sound["gap_max"] == 0.0 and sound["gap_mean"] == 0.0
    assert sound["control_gap_max"] >= 0.0 and sound["control_logprob_err_mean"] > 0.0
    broken = check.run(_job(served_shift=1))
    assert broken["mismatch"] > 0 and broken["gap_max"] > 1.0 and broken["gap_mean"] > 0.5


def _ctx(**counters):
    zero = {"engine_step_time_total_s": 10.0, **{k: 0 for k in counters}}
    return {"peaks": {"hbm_bytes_per_s": 819e9}, "stats0": {"stats": zero},
            "stats1": {"stats": {"engine_step_time_total_s": 60.0, **counters}}}


def test_the_new_metrics_read_a_hand_built_window_and_nothing_from_the_parent():
    from benchmark.readers import bytes_floor_share, counter_ratio

    args = lambda name: json.loads(  # noqa: E731
        (ROOT / f"benchmark/metrics/{name}.json").read_text())["args"]
    # 8.19e11 bytes are one second at the peak, of 50 s of steps: 2%
    mine = _ctx(ssm_state_bytes_total=819_000_000_000,
                decode_kv_read_bytes_total=1000, decode_cross_kv_read_bytes_total=640)
    assert bytes_floor_share.read(mine, **args("ssm_floor_share.reasoning")) == pytest.approx(2.0)
    assert counter_ratio.read(mine, **args("cross_kv_read_share.reasoning")) == pytest.approx(64.0)
    parent = _ctx(decode_kv_read_bytes_total=1000)
    assert bytes_floor_share.read(parent, **args("ssm_floor_share.reasoning")) is None
    assert counter_ratio.read(parent, **args("cross_kv_read_share.reasoning")) is None


def test_every_metric_of_the_cell_names_a_reader_that_exists_and_the_cell_alone():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".reasoning")]
    assert len(mine) == 16
    for metric in mine:
        assert metric["workloads"] == [CELL] and metric["moves"] == "itl_p95_ms"
        body = json.loads((ROOT / f"benchmark/metrics/{metric['name']}.json").read_text())
        assert (ROOT / f"benchmark/readers/{body['reader']}.py").is_file()
    # the cell reports the two metrics every cell reports and is in no list
    for metric in bench["end_to_end"]:
        assert CELL not in metric.get("workloads", [])
    cell = json.loads((ROOT / f"benchmark/cells/{CELL}.json").read_text())
    assert set(cell) == {"rate_rps"}        # no backlog: no gap_requests, no replay
    mix = json.loads((ROOT / "benchmark/traffic/reasoning.json").read_text())
    assert "backlog" not in mix and "shared_prefix" not in mix
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] < 4096


def test_every_line_of_a_configuration_and_a_cell_is_at_most_200_printable_characters():
    # the driver refused this PR once for a configuration's `why` of 226
    # characters: test_benchmark_json.py bounds a cell's `why`, not a configuration's
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"] + bench["workloads"]:
        for key in ("why", "source"):
            line = entry.get(key, "x")
            assert 1 <= len(line) <= 200 and line.isprintable(), (entry["name"], key, len(line))


# set from the readings the test prints: at this size (CPU, seed 2**31 + 5)
# the bf16 program read logprob_err_mean 0.00245, topk_err_mean 0.0138 and
# gap_max 0.005, the float8 control in its place 0.0223, 0.132 and 0.379.  The
# first two limits are the geometric means of the two readings; gap_max is
# left wide (a max over ~330 tokens)
SERVED_LIMITS = {"gap_max": 1.0, "logprob_err_mean": 0.0074, "topk_err_mean": 0.043,
                 "min_checked_tokens": 100, "min_probed_tokens": 40}


@pytest.mark.parametrize("seed", [2**31 + 5])
def test_a_tiny_phi4flash_is_served_over_http_and_held_to_the_committed_reference(tmp_path, seed):
    """The harness's whole path on the CPU with the committed reference and
    shapes modules (copied beside a throwaway cell): model directory, the
    server child on the normal path (HTTP frontend, scheduler, unified step,
    the state a lane) drawing its weights from the seed, the window, the
    comparison with its control, and the new counters' metrics."""
    from .helpers import TINY_MIX, tiny_bench

    own = {"reference": (ROOT / "benchmark/reference/sambay.py").read_text(),
           "shapes": (ROOT / "benchmark/sambay_shapes.py").read_text()}
    hf = dict(TINY, vocab_size=2000)
    bench = tiny_bench(tmp_path, hf, SERVED_LIMITS, name="phi", own_modules=own)
    mix = dict(TINY_MIX, output_tokens={"dist": "lognormal", "median": 40, "sigma": 0.3,
                                        "min": 24, "max": 64}, check_requests=6)
    (tmp_path / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    dump = tmp_path / "dump.json"
    argv = ["--workload", "phi.tinychat", "--seed", str(seed), "--seconds", "3",
            "--trace", "0", "--control", "fp8", "--dump", str(dump)]
    rc, result = runner.run(runner.parse(argv), require_platform=None, bench_path=bench,
                            bench_dir=tmp_path, env_overlay={"JAX_PLATFORMS": "cpu"})
    out = json.loads(dump.read_text())
    found = out["check"]
    print({k: round(v, 5) for k, v in found.items() if isinstance(v, float)})
    assert rc == 0 and result["failed"] == 0 and result["correct"] is True
    assert found["tokens"] >= 100 and found["probed_tokens"] >= 40
    assert found["control_logprob_err_mean"] > SERVED_LIMITS["logprob_err_mean"]
    assert found["control_topk_err_mean"] > SERVED_LIMITS["topk_err_mean"]
    served = json.loads((runner.WORK / "phi.tinychat" / "model" / "config.json").read_text())
    assert served["model_type"] == "phi4flash" and "assumed" not in served
    stats0, stats1 = out["stats0"]["stats"], out["stats1"]["stats"]
    assert stats1["ssm_rows_total"] > stats0["ssm_rows_total"]
    assert stats1["ssm_rows_total"] == stats1["gmu_rows_total"]
    assert stats1["decode_windows_unified_total"] > 0


def test_the_references_query_blocks_take_any_length_check_pads_to(monkeypatch):
    """``check.py`` pads a sample to a multiple of 256, not of the reference's
    block of 512 queries (2,816 rows stopped the first run on the chip): the
    blocks adapt, and a row's output does not depend on how they fall."""
    w = REF.init_weights(TINY, 5)
    ids = np.random.default_rng(1).integers(0, TINY["vocab_size"], 768).tolist()
    rows = [0, 255, 256, 511, 512, 767]
    blocked = np.asarray(REF.forward(w, TINY, ids, rows=rows))       # three blocks of 256
    monkeypatch.setattr(REF, "QUERY_BLOCK", 1024)
    REF._attn_jit.clear_cache(), REF._cross_jit.clear_cache()
    whole = np.asarray(REF.forward(w, TINY, ids, rows=rows))          # one block of 768
    np.testing.assert_allclose(blocked, whole, rtol=1e-4, atol=1e-3)
