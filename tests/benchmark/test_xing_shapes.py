"""``xing4-29b-l8``: the benchmark's own arithmetic against the program it
describes (the shapes module is pure Python and imports nothing of the
program: a test holds the two together) and against the numbers the
configuration was chosen by, at the cut and at a tiny size; and the reference
module under the comparison (its draw is the program's, the control rounds
what it should and fails, the reference's own tokens pass), at a tiny size."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from benchmark import run as runner
from benchmark.reference import check

from .helpers import TINY_MIX, tiny_bench

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "xing4-29b-l8.json").read_text())
HF = runner.hf_config(CONFIG)
SHAPES = modules.load(ROOT / "benchmark" / "xing_mhc_shapes.py")
REF = modules.load(ROOT / "benchmark" / "reference" / "xing_mhc.py")
TINY = dict(HF, hidden_size=256, intermediate_size=128, moe_intermediate_size=48, vocab_size=300,
            num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2, max_position_embeddings=512,
            rope_scaling=dict(HF["rope_scaling"], original_max_position_embeddings=64))


def _program(hf):
    from dynamo_tpu.models.registry import get_family

    family = get_family(hf["model_type"])
    return family, family.config_from_hf(hf)


def test_the_file_quotes_the_catalog_but_for_what_it_lists_as_reduced():
    # the catalog lies outside the repository: compared where it is there
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    entry = next(r for r in map(json.loads, catalog.read_text().splitlines())
                 if r["name"] == "Xing4.0-29B-A4B")
    assert CONFIG["source"] == entry["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 40, "num_nextn_predict_layers": 1}
    for key, value in entry["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value != CONFIG[key]
        else:
            assert key in CONFIG and CONFIG[key] == value, key


@pytest.mark.parametrize("hf,blocks", [(HF, 11008), (TINY, 64)], ids=["the_cut", "tiny"])
def test_parameters_and_cache_bytes_are_the_programs(hf, blocks):
    family, cfg = _program(hf)
    params = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(params)
    assert SHAPES.total_params(hf) == sum(math.prod(a.shape) for a in leaves)
    assert SHAPES.held_bytes(hf) == sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves)
    cache = jax.eval_shape(lambda: family.cache_init(cfg, blocks, 16, None))
    pages = sum(math.prod(a.shape) * a.dtype.itemsize for k, a in cache.items() if k != "moe_stats")
    serving = {"args": ["--num-blocks", blocks]}
    assert SHAPES.cache_bytes(hf, serving) == pages
    assert SHAPES.kv_bytes_per_token(hf) * blocks * 16 == pages


def test_the_counts_are_the_issues_to_the_parameter():
    """ISSUE 51's arithmetic: attention 28,411,136 a layer (its two inner
    norms counted), a dense MLP 99,090,432, an expert 11,010,048, the router
    and its bias 229,440, the mixing 688,182 a layer, a dense layer
    128,196,918, a sparse one 744,989,046, in all 5,665,855,792; a token
    multiplies against 762,642,432 before the head (4 + 1 experts, both
    ``phi`` of a layer)."""
    assert SHAPES.attention_params(HF) == 28_411_136
    assert 3 * HF["hidden_size"] * HF["intermediate_size"] == 99_090_432
    assert SHAPES.expert_params(HF) == 11_010_048
    assert SHAPES.router_params(HF) + HF["n_routed_experts"] == 229_440
    assert SHAPES.stream_params(HF) == 2 * (14_336 * 24 + 27) == 688_182
    norms = 2 * HF["hidden_size"]
    assert SHAPES.layer_params(HF, False) + norms == 128_196_918
    assert SHAPES.layer_params(HF, True) + norms + HF["n_routed_experts"] == 744_989_046
    assert SHAPES.total_params(HF) == 5_665_855_792
    head = HF["vocab_size"] * HF["hidden_size"]
    assert head == 469_762_048
    assert SHAPES.matmul_params(HF) - head == 762_642_432
    assert SHAPES.flops_per_token(HF) == 2 * SHAPES.matmul_params(HF)


def test_the_cut_is_stage_0_of_five_and_fills_the_chip():
    """11.33 GB at 2 B a parameter (11.34 as held: the mixing leaves and the
    selection bias are float32); 1,280 B a token-layer as stored, 10,240 B a
    token; the file's pool is what its arguments reserve, the largest
    multiple of 256 blocks under the sizing rule (weights + two caches under
    15e9); the five stages' bytes are the deployment's."""
    assert 2 * SHAPES.total_params(HF) == 11_331_711_584
    assert SHAPES.held_bytes(HF) == 11_342_723_264
    assert SHAPES.page_row(HF) * 2 == 1280
    assert SHAPES.kv_bytes_per_token(HF) == 8 * 1280
    serving = CONFIG["serving"]
    assert SHAPES.cache_bytes(HF, serving) == serving["kv_bytes"] == 1_803_550_720
    blocks = serving["args"][serving["args"].index("--num-blocks") + 1]
    assert serving["kv_tokens"] == blocks * 16 == 176_128
    size = lambda b: SHAPES.held_bytes(HF) + 2 * b * 16 * SHAPES.kv_bytes_per_token(HF)  # noqa: E731
    assert blocks % 256 == 0 and size(blocks) < 15e9 <= size(blocks + 256)
    assert SHAPES.held_bytes(HF) + serving["kv_bytes"] > 0.8 * 16e9
    # the stages: 40 = 5 x 8, layers 0-1 dense; embedding with the first, head with the last
    whole = dict(HF, num_hidden_layers=40)
    sparse = SHAPES.layer_params(HF, True) + 2 * HF["hidden_size"] + HF["n_routed_experts"]
    table = HF["vocab_size"] * HF["hidden_size"]
    stage0 = SHAPES.total_params(HF) - HF["hidden_size"] - table        # less final norm and head
    assert 2 * stage0 == pytest.approx(10.39e9, rel=1e-3)
    assert 2 * 8 * sparse == pytest.approx(11.92e9, rel=1e-3)
    assert 2 * (8 * sparse + table) == pytest.approx(12.86e9, rel=1e-3)
    assert SHAPES.total_params(whole) == stage0 + 32 * sparse + table + HF["hidden_size"]
    assert 2 * SHAPES.total_params(whole) > 3 * 16e9


def test_a_program_without_residual_streams_is_told_so_at_once(tmp_path, monkeypatch):
    """A checkout whose program has no ops/hyper_connections.py (the parent
    commit) is refused when the shapes module is loaded, before a server is
    started that could only die on the model's name."""
    (tmp_path / "dynamo_tpu" / "ops").mkdir(parents=True)
    monkeypatch.syspath_prepend(str(tmp_path))
    with pytest.raises(SystemExit, match="xing4_0"):
        modules.load(ROOT / "benchmark" / "xing_mhc_shapes.py")


@pytest.mark.parametrize("seed", [0, 2147483646])
def test_the_references_draw_is_the_programs_leaf_for_leaf(seed):
    family, cfg = _program(TINY)
    params = family.init_params(cfg, jax.random.PRNGKey(seed))
    weights = REF.init_weights(TINY, seed)
    seen = {"embed", "lm_head"}
    for leaf in seen:
        assert params[leaf].dtype == weights[leaf].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(params[leaf], np.float32), np.asarray(weights[leaf], np.float32))
    for group, name in (("dense_layers", "dense"), ("moe_layers", "sparse")):
        for leaf, stack in params[group].items():
            if leaf.endswith("norm"):
                assert bool(jnp.all(stack == 1))
                continue
            for layer in range(stack.shape[0]):
                mine = weights[f"{name}{layer}.{leaf}"]
                assert mine.dtype == stack.dtype, leaf
                np.testing.assert_array_equal(
                    np.asarray(stack[layer], np.float32), np.asarray(mine, np.float32), err_msg=leaf)
                seen.add(f"{name}{layer}.{leaf}")
    assert seen == set(weights)
    assert weights["sparse0.hc_phi"].dtype == weights["dense1.hc_bias"].dtype == jnp.float32


def test_the_control_rounds_what_a_token_multiplies_and_nothing_else():
    w = REF.init_weights(TINY, 3)
    low = REF.quantize(dict(w), "fp8", TINY)
    kept = {k for k in w if np.array_equal(np.asarray(w[k], np.float32), np.asarray(low[k], np.float32))}
    mixing = {f"{g}{l}.{n}" for g, count in (("dense", 2), ("sparse", 3)) for l in range(count)
              for n in ("hc_phi", "hc_alpha", "hc_bias")}
    assert kept == {"embed", *mixing,
                    *(f"sparse{l}.{n}" for l in range(3) for n in ("w_router", "router_bias"))}
    assert all(low[k].dtype == jnp.float32 for k in mixing)
    with pytest.raises(KeyError):
        REF.quantize(w, "int3", TINY)
    ids = list(range(5, 25))
    a, b = REF.forward(w, TINY, ids), REF.forward(low, TINY, ids)
    assert a.shape == (20, 300) and a.dtype == jnp.float32
    spread = float(jnp.std(a))
    assert 0.005 * spread < float(jnp.abs(a - b).mean()) < 0.3 * spread


def test_other_scalings_and_group_limits_are_refused():
    for change in ({"rope_scaling": {"type": "linear", "factor": 2}}, {"n_group": 2},
                   {"rope_scaling": dict(HF["rope_scaling"], mscale=0.7)}):
        with pytest.raises(NotImplementedError):
            REF.dims(dict(HF, **change))
    # no scaling at all is the plain rotation
    plain = REF.dims({k: v for k, v in HF.items() if k != "rope_scaling"})
    np.testing.assert_allclose(REF._frequencies(plain), 10000.0 ** (-np.arange(32) / 32), rtol=1e-6)
    assert REF._mscale(plain) == 1.0


def _job(served_shift=0, control=None):
    weights = REF.init_weights(TINY, 11)
    rng = np.random.default_rng(3)
    samples = []
    for i, n in enumerate((20, 33)):
        prompt = rng.integers(8, TINY["vocab_size"], n).tolist()
        served = []
        for _ in range(6):  # greedy by the reference itself
            logits = REF.forward(weights, TINY, prompt + served, rows=[len(prompt) + len(served) - 1])
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


def test_the_new_reader_reads_a_hand_built_window_and_nothing_from_the_parent():
    from benchmark.readers import bytes_floor_share, counter_ratio

    def ctx(**counters):
        zero = {"engine_step_time_total_s": 10.0, **{k: 0 for k in counters}}
        return {"peaks": {"hbm_bytes_per_s": 819e9}, "stats0": {"stats": zero},
                "stats1": {"stats": {"engine_step_time_total_s": 60.0, **counters}}}

    # 8.19e11 bytes are one second at the peak, of 50 s of steps: 2%
    mine = ctx(mhc_stream_bytes_total=819_000_000_000, mhc_rows_total=819_000_000_000 // 71_680)
    assert bytes_floor_share.read(mine, bytes="mhc_stream_bytes_total") == pytest.approx(2.0)
    parent = ctx()
    assert bytes_floor_share.read(parent, bytes="mhc_stream_bytes_total") is None
    assert bytes_floor_share.read(dict(mine, peaks=None), bytes="mhc_stream_bytes_total") is None
    args = json.loads((ROOT / "benchmark/metrics/mhc_bytes_per_row.long-doc.json").read_text())["args"]
    assert counter_ratio.read(mine, **args) == pytest.approx(71_680, rel=1e-6)
    assert counter_ratio.read(parent, **args) is None


# set from the readings the test prints: at this size (CPU) the bf16 program
# read logprob_err_mean 0.0287-0.0522, topk_err_mean 0.0191-0.0556 and
# gap_max 1.02-1.96 over seeds 2**31 + 5, 11, 12 and 987654321; the float8
# control in its place 0.239-0.270, 0.275-0.295 and 1.96-3.39.  The first two
# limits are the geometric means of the program's largest and the control's
# smallest; gap_max does not separate (a max over ~330 tokens) and is wide
SERVED_LIMITS = {"gap_max": 4.0, "logprob_err_mean": 0.112, "topk_err_mean": 0.124,
                 "min_checked_tokens": 100, "min_probed_tokens": 40}


@pytest.mark.parametrize("seed", [2**31 + 5])
def test_a_tiny_xing_is_served_over_http_and_held_to_the_committed_reference(tmp_path, seed):
    """The harness's whole path on the CPU with the committed reference and
    shapes modules (copied beside a throwaway cell): model directory, the
    server child on the normal path (HTTP frontend, scheduler, unified step)
    drawing its weights from the seed (no checkpoint: the loader's refusal is
    not in the way), the window, the comparison with its control, and the two
    counters' metrics from the window's ends."""
    own = {"reference": (ROOT / "benchmark/reference/xing_mhc.py").read_text(),
           "shapes": (ROOT / "benchmark/xing_mhc_shapes.py").read_text()}
    hf = dict(TINY, vocab_size=2000)
    bench = tiny_bench(tmp_path, hf, SERVED_LIMITS, name="xing", own_modules=own)
    mix = dict(TINY_MIX, output_tokens={"dist": "lognormal", "median": 40, "sigma": 0.3,
                                        "min": 24, "max": 64}, check_requests=6)
    (tmp_path / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    dump = tmp_path / "dump.json"
    argv = ["--workload", "xing.tinychat", "--seed", str(seed), "--seconds", "3",
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
    served = json.loads((runner.WORK / "xing.tinychat" / "model" / "config.json").read_text())
    assert served["model_type"] == "xing4_0" and served["hc_mult"] == 4
    ctx = {"stats0": out["stats0"], "stats1": out["stats1"], "peaks": {"hbm_bytes_per_s": 819e9}}
    only = {"per_layer": [m for m in json.loads(bench.read_text())["per_layer"]
                          if m["layer"] == "residual streams"]}
    read = runner.read_per_layer(only, "xing.tinychat", ctx, tmp_path)
    assert read["mhc_bytes_per_row.long-doc"] == {"value": (2 * 4 + 2) * 256 * 2, "unit": "bytes/row"}
    assert read["mhc_floor_share.long-doc"]["value"] > 0
    moved = out["stats1"]["stats"]["mhc_rows_total"] - out["stats0"]["stats"]["mhc_rows_total"]
    assert moved > 0 and moved % (2 * 5) == 0
