"""models/phi4flash.py against the benchmark's plain reference at a small
size: prefill then decode through the state and both pools, the unified
step with a prompt span and decode rows in one launch, and five programs
each broken in ONE mechanism, each of which must miss the reference."""

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules, sambay_shapes
from dynamo_tpu.models import phi4flash as pf
from dynamo_tpu.models.llama import KvPools, LayerKind, LayerRun
from dynamo_tpu.models.registry import get_family

ROOT = Path(__file__).resolve().parents[2]
REF = modules.load(ROOT / "benchmark" / "reference" / "sambay.py")
HF = {
    "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 8, "num_key_value_heads": 4,
    "vocab_size": 512, "layer_norm_eps": 1e-5, "mb_per_layer": 2, "sliding_window": 8,
    "max_position_embeddings": 256, "tie_word_embeddings": True, "mamba_d_state": 4,
}
BS, BLOCKS, LANES, LANE = 4, 32, 3, 1
N_PROMPT, N_ALL = 24, 40        # three windows of prompt, two more decoded
LIMIT = 0.05                    # the cell's logprob_err_mean is of this order


def config(**changes):
    return dataclasses.replace(pf.Phi4FlashConfig.from_hf_config(HF), dtype=jnp.float32, **changes)


@pytest.fixture(scope="module")
def model():
    cfg = config()
    params = pf.init_params(cfg, jax.random.PRNGKey(3))
    weights = {"embed": params["embed"]}
    for group in ("ssm", "attn", "gmu", "cross"):
        for leaf, stack in params[group].items():
            for layer in range(stack.shape[0]):
                weights[f"{group}{layer}.{leaf}"] = stack[layer]
    ids = np.random.default_rng(0).integers(0, 512, size=N_ALL)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(REF.forward(weights, HF, ids))
    return params, ids, ref


def served_rows(cfg, params, ids, *, between_steps=None, unified=False):
    """Logits of rows ``N_PROMPT - 1 ..`` as the program serves them: the
    prompt in one launch into lane ``LANE``, then a token a step."""
    cache = pf.init_kv_cache(cfg, BLOCKS, BS, window_blocks=BLOCKS, lanes=LANES)
    cache = {k: v + 3.0 if k in pf.LANE_LEAVES else v for k, v in cache.items()}   # a used lane
    blocks = np.arange(1, 17, dtype=np.int32)
    tables = np.zeros((2, LANES, 16), np.int32)
    tables[0, LANE], tables[1, LANE] = blocks, blocks + 1
    pools = KvPools(jnp.asarray(tables[0]), jnp.asarray(tables[1]))
    cos, sin = pf.make_rope_tables(cfg)
    out = []
    with jax.default_matmul_precision("highest"):
        if unified:
            t, z = 32, jnp.zeros(32, jnp.int32)
            tok, pos = np.zeros(t, np.int32), np.full(t, -1, np.int32)
            lane, slot = np.full(t, LANES, np.int32), np.full(t, BLOCKS * BS, np.int32)
            at = np.arange(N_PROMPT)
            tok[at], pos[at], lane[at] = ids[:N_PROMPT], at, LANE
            slot[at] = blocks[at // BS] * BS + at % BS
            lens = np.zeros(LANES, np.int32)
            lens[LANE] = N_PROMPT
            rows = np.zeros(LANES, np.int32)
            rows[LANE] = N_PROMPT - 1
            both = KvPools(z, z)
            logits, cache = jax.jit(lambda p, c: pf.phi4flash_forward_unified(
                p, cfg, jnp.asarray(tok), c, pools, jnp.asarray(lens), jnp.asarray(pos),
                jnp.asarray(slot), jnp.asarray(lane), both, both, both,
                KvPools(z[:4], z[:4]), jnp.asarray(rows), cos, sin))(params, cache)
            out.append(np.asarray(logits)[LANE])
        else:
            padded = np.zeros(32, np.int32)
            padded[:N_PROMPT] = ids[:N_PROMPT]
            logits, cache = jax.jit(lambda p, c: pf.phi4flash_forward_prefill(
                p, cfg, jnp.asarray(padded), c, KvPools(jnp.asarray(blocks), jnp.asarray(blocks + 1)),
                jnp.int32(N_PROMPT), jnp.int32(0), cos, sin, lane=jnp.int32(LANE)))(params, cache)
            out.append(np.asarray(logits))
        decode = jax.jit(lambda p, c, tok, lens, slots: pf.phi4flash_forward_decode(
            p, cfg, tok, c, pools, lens, slots, cos, sin))
        for t in range(N_PROMPT, N_ALL):
            if between_steps is not None:
                cache = between_steps(cache)
            tok, lens = np.zeros(LANES, np.int32), np.zeros(LANES, np.int32)
            slots = np.full(LANES, BLOCKS * BS, np.int32)
            tok[LANE], lens[LANE], slots[LANE] = ids[t], t + 1, blocks[t // BS] * BS + t % BS
            logits, cache = decode(params, cache, *(jnp.asarray(a) for a in (tok, lens, slots)))
            out.append(np.asarray(logits)[LANE])
    return np.array(out)


def logprob_err(rows, ref):
    """Mean |difference| of the log-probability of the reference's first
    choice, over the served rows."""
    ref = ref[N_PROMPT - 1:]
    first = ref.argmax(-1)
    lsm = lambda x: x - np.asarray(jax.nn.logsumexp(x, axis=-1))[:, None]  # noqa: E731
    take = lambda x: lsm(x)[np.arange(len(first)), first]  # noqa: E731
    return float(np.abs(take(rows) - take(ref)).mean())


@pytest.mark.parametrize("unified", [False, True], ids=["prefill", "unified"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(model, unified):
    params, ids, ref = model
    rows = served_rows(config(), params, ids, unified=unified)
    np.testing.assert_allclose(rows, ref[N_PROMPT - 1:], atol=2e-4)
    assert logprob_err(rows, ref) < 1e-4


def _no_state(cache):
    return {k: jnp.zeros_like(v) if k in pf.LANE_LEAVES else v for k, v in cache.items()}


def _memory_of_ones(monkeypatch):
    real = pf._ssm_mixer

    def mixer(*args):
        out, m, ssm, conv = real(*args)
        return out, jnp.ones_like(m), ssm, conv

    monkeypatch.setattr(pf, "_ssm_mixer", mixer)


def _no_lambda(monkeypatch):
    monkeypatch.setattr(pf, "lambda_init", lambda depth: jnp.float32(0.0))
    real = pf._diff_merge

    def merge(cfg, out, w, depth):
        zero = {**{k: w[k] for k in ("gamma",)}, "lq1": jnp.full_like(w["lq1"], -1e3),
                "lk1": jnp.ones_like(w["lk1"]), "lq2": jnp.full_like(w["lq2"], -1e3),
                "lk2": jnp.ones_like(w["lk2"])}
        return real(cfg, out, zero, depth)     # lambda = exp(-inf) - exp(-inf) + 0

    monkeypatch.setattr(pf, "_diff_merge", merge)


def _cross_reads_another_layer(monkeypatch):
    """The cross layers read the LAST WINDOW layer's pages, not layer L/2 + 1's."""
    def runs(self):
        first, second, third = pf.Phi4FlashConfig.__dict__["_runs"](self)
        gmu, cross = third.kind
        wrong = dataclasses.replace(cross, pool="window")
        return first, second, LayerRun((gmu, wrong), third.start, third.count,
                                       (0, self.window_layers - 1))

    monkeypatch.setattr(pf.Phi4FlashConfig, "_runs", pf.Phi4FlashConfig.layer_runs, raising=False)
    monkeypatch.setattr(pf.Phi4FlashConfig, "layer_runs", runs)


BROKEN = {
    "the carried state zeroed each step": dict(between_steps=_no_state),
    "the memory units fed ones": dict(patch=_memory_of_ones),
    "the lambda term dropped": dict(patch=_no_lambda),
    "the window ignored": dict(cfg=dict(window=10 ** 6)),
    "cross layers on another layer's keys": dict(patch=_cross_reads_another_layer),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_each_mechanism_bites_under_the_seeded_draw(model, monkeypatch, how):
    """A program broken in one mechanism misses the reference by more than
    the comparison's limit: none of them is decoration under random weights."""
    params, ids, ref = model
    broken = BROKEN[how]
    if "patch" in broken:
        broken["patch"](monkeypatch)
    rows = served_rows(config(**broken.get("cfg", {})), params, ids,
                       between_steps=broken.get("between_steps"))
    assert logprob_err(rows, ref) > LIMIT


def test_the_layer_pattern_is_three_scans_of_pairs():
    cfg = pf.Phi4FlashConfig.from_hf_config(
        {**HF, "num_hidden_layers": 32, "sliding_window": 512})
    runs = cfg.layer_runs()
    assert [run.count for run in runs] == [8, 1, 7]
    mixers = [kind.mixer for run in runs for _ in range(run.count) for kind, _, _ in run.period]
    assert mixers == ["ssm", "attn"] * 9 + ["gmu", "cross"] * 7
    depth = [int(cfg.depth(kind.group, start + i))
             for run in runs for i in range(run.count) for kind, start, _ in run.period]
    assert depth == list(range(32))
    *_, (_, cross) = (run.kind for run in runs)
    assert not cross.writes and cross.pool == "kv" and runs[2].pool_start == (0, 0)
    # the other families' runs are one kind each, written pools, the one mixer
    assert LayerKind(None, True, "kv", "layers") == LayerKind(
        None, True, "kv", "layers", mixer="attn", writes=True)
    assert LayerRun(LayerKind(None, True, "kv", "layers"), 2, 3, 1).period == (
        (LayerKind(None, True, "kv", "layers"), 2, 1),)


def test_program_shapes_module_and_issue_agree_to_the_parameter():
    hf = {k: v for k, v in __import__("json").loads(
        (ROOT / "benchmark/configs/phi4-mini-flash.json").read_text()).items()}
    cfg = pf.Phi4FlashConfig.from_hf_config(hf)
    counts = pf.param_counts(cfg)
    assert counts["params"] == sambay_shapes.total_params(hf) == 3_852_562_944
    assert counts["matrix"] == sambay_shapes.matmul_params(hf)
    assert counts["float32"] == sambay_shapes.float32_params(hf)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.qk_dim) == (40, 10, 128, 64)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (5120, 16, 4, 160)
    assert pf.window_pool_blocks(cfg, 16, 4096, 16) == sambay_shapes.window_pool_blocks(
        hf, 16, 4096) == 808
    cache = jax.eval_shape(lambda: pf.init_kv_cache(cfg, 4160, 16, window_blocks=808, lanes=16))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert held == sambay_shapes.cache_bytes(hf, hf["serving"]) == hf["serving"]["kv_bytes"]
    assert cache["ssm"].shape == (9, 16, 16, 5120) and cache["conv"].shape == (9, 16, 3, 5120)
    assert cache["k"].shape[0] == 1 and cache["wk"].shape[0] == 8
    family = get_family("phi4flash")
    assert family.lane_state and family.forward_unified is not None
