"""``moonlight-16b-l9``: the benchmark's own arithmetic against the program it
describes (the shapes module is pure Python and imports nothing of the
program: a test holds the two together), at the cut and at a tiny size; and
the reference module under the comparison (the control fails, the program's
own tokens pass), at a tiny size."""

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

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "moonlight-16b-l9.json").read_text())
HF = runner.hf_config(CONFIG)
SHAPES = modules.load(ROOT / "benchmark" / "deepseek_mla_shapes.py")
REF = modules.load(ROOT / "benchmark" / "reference" / "deepseek_mla.py")
TINY = dict(HF, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, vocab_size=300,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
            num_experts_per_tok=3, max_position_embeddings=256)


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
                 if r["name"] == "Moonlight-16B-A3B")
    assert CONFIG["source"] == entry["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
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
    cache = jax.eval_shape(lambda: family.cache_init(cfg, blocks, 16, None))
    pages = sum(math.prod(a.shape) * a.dtype.itemsize for k, a in cache.items() if k != "moe_stats")
    serving = {"args": ["--num-blocks", blocks]}
    assert SHAPES.cache_bytes(hf, serving) == pages
    assert SHAPES.kv_bytes_per_token(hf) * blocks * 16 == pages


def test_the_cut_is_stage_0_of_three_and_fills_the_chip():
    """5,432.8 M parameters = 10.87 GB; a token meets 6 of 64 experts, the two
    shared and attention: 83.1 M a sparse layer; 1,280 B a token-layer as
    stored; the file's pool is what its arguments reserve, the largest
    multiple of 256 blocks under the sizing rule."""
    assert SHAPES.total_params(HF) == pytest.approx(5432.8e6, rel=1e-4)
    assert SHAPES.layer_params(HF, True, met=True) == pytest.approx(83.1e6, rel=1e-3)
    assert SHAPES.matmul_params(HF) < SHAPES.total_params(HF) / 4
    assert SHAPES.flops_per_token(HF) == 2 * SHAPES.matmul_params(HF)
    assert SHAPES.page_row(HF) * 2 == 1280
    assert SHAPES.kv_bytes_per_token(HF) == 9 * 1280
    serving = CONFIG["serving"]
    assert SHAPES.cache_bytes(HF, serving) == serving["kv_bytes"]
    blocks = serving["args"][serving["args"].index("--num-blocks") + 1]
    assert serving["kv_tokens"] == blocks * 16
    size = lambda b: 2 * SHAPES.total_params(HF) + 2 * b * 16 * SHAPES.kv_bytes_per_token(HF)  # noqa: E731
    assert blocks % 256 == 0 and size(blocks) < 15e9 <= size(blocks + 256)
    assert 2 * SHAPES.total_params(HF) + serving["kv_bytes"] > 0.75 * 16e9
    # whole: the published 27 layers are two chips' worth
    assert 2 * SHAPES.total_params(dict(HF, num_hidden_layers=27)) > 31e9


def test_a_program_without_the_page_layout_is_told_so_at_once(tmp_path, monkeypatch):
    """A checkout whose deepseek family stores the key 64 wide behind a unit
    axis (the parent commit) is refused when the shapes module is loaded,
    before anything starts."""
    family = tmp_path / "dynamo_tpu" / "models"
    family.mkdir(parents=True)
    (family / "deepseek.py").write_text("def init_kv_cache(cfg, num_blocks, block_size):\n    ...\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    with pytest.raises(SystemExit, match="rope_page_width"):
        modules.load(ROOT / "benchmark" / "deepseek_mla_shapes.py")


def test_the_control_rounds_what_a_token_multiplies_and_nothing_else():
    w = REF.init_weights(TINY, 3)
    low = REF.quantize(dict(w), "fp8", TINY)
    kept = {k for k in w if np.array_equal(np.asarray(w[k], np.float32), np.asarray(low[k], np.float32))}
    assert kept == {"embed", *(f"sparse{l}.{n}" for l in range(2) for n in ("w_router", "router_bias"))}
    with pytest.raises(KeyError):
        REF.quantize(w, "int3", TINY)
    ids = list(range(5, 25))
    a, b = REF.forward(w, TINY, ids), REF.forward(low, TINY, ids)
    assert a.shape == (20, 300) and a.dtype == jnp.float32
    # (a rounding that flips one token's choice of expert moves single
    # logits by more than a spread: held on the mean)
    spread = float(jnp.std(a))
    assert 0.005 * spread < float(jnp.abs(a - b).mean()) < 0.3 * spread


def test_an_expert_over_its_own_rows_is_every_expert_over_every_row():
    """The reference's one departure from the plain sum: routing first, an
    expert over the rows that chose it.  Held against the dense sum (every
    expert over every token, times its weight or zero)."""
    w = REF.init_weights(TINY, 5)
    c = REF.dims(TINY)
    ids = jnp.asarray(np.random.default_rng(0).integers(8, 300, 50), jnp.int32)
    layer = {k[len("sparse0."):]: v for k, v in w.items() if k.startswith("sparse0.")}
    x = w["embed"][ids].astype(jnp.float32)
    cos = jnp.ones((50, c["rope"] // 2))
    x, u, chosen, g = REF._route(x, layer, cos, 0 * cos, c)
    busiest = int(np.bincount(np.asarray(chosen).ravel(), minlength=8).max())
    got = REF._experts(x, u, chosen, g, layer, -(-busiest // 8) * 8, c)
    want = x + REF._gated(u, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    for e in range(8):
        weight = jnp.sum(jnp.where(chosen == e, g, 0.0), axis=-1, keepdims=True)
        want = want + weight * REF._gated(u, layer["w_gate"][e], layer["w_up"][e], layer["w_down"][e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(got - x).max()) > 0.1


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
