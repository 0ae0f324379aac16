"""``k-exaone-236b-l8``: the benchmark's own arithmetic against the program
it describes (the shapes module is pure Python and imports nothing of the
program: a test holds the two together), and the reference's control."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from benchmark import run as runner

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "k-exaone-236b-l8.json").read_text())
HF = runner.hf_config(CONFIG)
SHAPES = modules.load(ROOT / "benchmark" / "exaone_moe_shapes.py")
REF = modules.load(ROOT / "benchmark" / "reference" / "exaone_moe.py")


@pytest.fixture(scope="module")
def program():
    from dynamo_tpu.models.registry import get_family

    family = get_family(HF["model_type"])
    return family, family.config_from_hf(HF)


def test_the_file_quotes_the_catalog_but_for_what_it_lists_as_reduced():
    # the catalog lies outside the repository: compared where it is there
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    entry = next(r for r in map(json.loads, catalog.read_text().splitlines())
                 if r["name"] == "K-EXAONE-236B-A23B")
    assert CONFIG["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value != CONFIG[key]
        else:
            assert CONFIG[key] == value, key


def test_parameters_bytes_and_pools_are_the_programs(program):
    family, cfg = program
    params = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(params)
    assert SHAPES.total_params(HF) == sum(math.prod(a.shape) for a in leaves)
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) == pytest.approx(10.38e9, rel=2e-3)
    lanes, context = 16, 8192
    blocks = family.window_pool_blocks(cfg, lanes, context, 16)
    assert SHAPES.window_pool_blocks(HF, lanes, context) == blocks == 678
    cache = jax.eval_shape(lambda: family.cache_init(cfg, 8320, 16, None, window_blocks=blocks))
    pages = sum(math.prod(a.shape) * a.dtype.itemsize for k, a in cache.items() if k != "moe_stats")
    assert SHAPES.cache_bytes(HF, CONFIG["serving"]) == pages == CONFIG["serving"]["kv_bytes"]
    assert SHAPES.kv_bytes_per_token(HF) == 1024


def test_a_token_meets_the_experts_it_is_routed_to_that_are_held():
    """1 of its 8 on average at 16 of 128 held: 1.10 B parameters a token,
    2.2 GFLOP; not the 16 held (1.67 B)."""
    h, mi = HF["hidden_size"], HF["moe_intermediate_size"]
    sparse = SHAPES.layer_params(HF, "sparse", met=True)
    assert sparse == SHAPES.attention_params(HF) + h * 128 + 2 * 3 * h * mi
    assert SHAPES.matmul_params(HF) == pytest.approx(1.105e9, rel=1e-3)
    assert SHAPES.flops_per_token(HF) == 2 * SHAPES.matmul_params(HF)
    assert SHAPES.matmul_params(HF) < SHAPES.total_params(HF) / 4


def test_the_control_rounds_what_a_token_multiplies_and_nothing_else():
    hf = dict(HF, hidden_size=32, intermediate_size=48, moe_intermediate_size=16, vocab_size=64,
              num_hidden_layers=2, head_dim=8, num_attention_heads=2, num_key_value_heads=1,
              num_experts=2)
    w = REF.init_weights(hf, 3)
    low = REF.quantize(dict(w), "fp8", hf)
    kept = {k for k in w if np.array_equal(np.asarray(w[k], np.float32), np.asarray(low[k], np.float32))}
    assert kept == {"embed", "sparse0.w_router", "sparse0.router_bias"}
    with pytest.raises(KeyError):
        REF.quantize(w, "int3", hf)
    ids = list(range(5, 25))
    with jax.default_matmul_precision("highest"):
        a, b = REF.forward(w, hf, ids), REF.forward(low, hf, ids)
    assert a.shape == (20, 64) and a.dtype == jnp.float32
    spread = float(jnp.std(a))
    assert 0.01 * spread < float(jnp.abs(a - b).max()) < spread


def test_a_gauge_is_read_as_the_mean_of_its_readings():
    from benchmark.readers import stat_mean

    ends = {"stats0": {"stats": {"window_pool_blocks_in_use": 100}},
            "stats1": {"stats": {"window_pool_blocks_in_use": 140}}}
    assert stat_mean.read(ends, key="window_pool_blocks_in_use") == 120
    more = dict(ends, samples=[{"window_pool_blocks_in_use": 120}, {"other": 1}])
    assert stat_mean.read(more, key="window_pool_blocks_in_use") == 120
    assert stat_mean.read({"stats0": {"stats": {}}, "stats1": None}, key="window_pool_blocks_in_use") is None
