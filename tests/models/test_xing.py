"""The ``xing4_0`` family (Xing4.0-29B-A4B: four residual streams a token mixed
token by token, around latent attention with a compressed query and YaRN, and
sigmoid-routed experts beside a shared one) at a tiny size on the CPU.

Every forward of models/deepseek.py is held on LOGITS against the full
forward of the benchmark's plain reference (benchmark/reference/xing_mhc.py:
float32, nothing cached, nothing absorbed, the streams as ``[T, n, C]``):
prefill then decode through the cache, a unified mixed window, a prefix
continuation, a verify window.  Then the controls that show the comparison
bites, and the mixing's own properties (ops/hyper_connections.py)."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from dynamo_tpu.models import deepseek
from dynamo_tpu.models.deepseek import DeepseekConfig, init_params
from dynamo_tpu.models.registry import get_family
from dynamo_tpu.ops import hyper_connections as hc
from dynamo_tpu.ops.pallas.ragged_attention import pack_spans

ROOT = Path(__file__).resolve().parents[2]
REF = modules.load(ROOT / "benchmark" / "reference" / "xing_mhc.py")
CFG = DeepseekConfig.tiny_xing()
# the keys a served config.json of this geometry holds (what ``tiny_xing`` is)
HF = {
    "model_type": "xing4_0", "vocab_size": 512, "hidden_size": 256, "num_hidden_layers": 5,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "first_k_dense_replace": 2, "moe_intermediate_size": 48, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "routed_scaling_factor": 2.0,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "max_position_embeddings": 2048, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "rope_scaling": dict(CFG.rope_scaling), "tie_word_embeddings": False,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
}
SEED = 5
BS, BLOCKS, LANES, MAX_BLOCKS, TB = 4, 48, 3, 12, 8
OOB = BLOCKS * BS
IDS = [int(t) for t in np.random.default_rng(3).integers(2, 500, size=40)]
# float32 against float32 over five layers of one recipe: the two differ by
# the order of their sums (absorbed against decompressed attention, the
# statistic's scale after ``phi`` against before it) and read 1e-5 of a
# logit's spread; a coefficient path rounded to bfloat16 reads 6e-3
TOL = 2e-4


def i32(x):
    return jnp.asarray(x, jnp.int32)


@pytest.fixture(scope="module")
def served():
    """ONE set of weights: the recipe's values (bfloat16 matrices, float32
    mixing leaves) served in float32, and the reference's own draw."""
    assert dataclasses.replace(DeepseekConfig.from_hf_config(HF), dtype=jnp.float32) == CFG
    params = init_params(dataclasses.replace(CFG, dtype=jnp.bfloat16), jax.random.PRNGKey(SEED))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return params, REF.init_weights(HF, SEED)


def _want(weights, ids, rows):
    return np.asarray(REF.forward(weights, HF, ids, rows=rows))


def _slots(blocks, positions):
    return [int(blocks[p // BS]) * BS + p % BS for p in positions]


def _prefill(params, cfg, ids, blocks):
    cos, sin = deepseek.make_rope_tables(cfg)
    cache = deepseek.init_kv_cache(cfg, BLOCKS, BS)
    pad = -len(ids) % 8
    return deepseek.deepseek_forward_prefill(
        params, cfg, i32(ids + [0] * pad), cache, i32(blocks), i32(len(ids)), i32(0), cos, sin)


def _run(path, params, cfg):
    """``(logits [rows, vocab], the sequence, the reference rows they are)``
    of one path through the program."""
    cos, sin = deepseek.make_rope_tables(cfg)
    blocks = list(range(5, 5 + MAX_BLOCKS))
    tables = np.zeros((LANES, MAX_BLOCKS), np.int32)
    tables[0] = blocks
    if path == "prefill_then_decode":
        n = 21
        first, cache = _prefill(params, cfg, IDS[:n], blocks)
        got = [first]
        for step in range(3):
            ctx = n + step + 1
            out, cache = deepseek.deepseek_forward_decode(
                params, cfg, i32([IDS[ctx - 1], 0, 0]), cache, i32(tables), i32([ctx, 0, 0]),
                i32(_slots(blocks, [ctx - 1]) + [OOB, OOB]), cos, sin)
            got.append(out[0])
        return jnp.stack(got), IDS[: n + 3], list(range(n - 1, n + 3))
    if path == "unified":
        # lane 0 decodes at position 19 behind a resident prompt; lane 1
        # prefills a whole 12-token prompt in the same window
        n, m = 19, 12
        _, cache = _prefill(params, cfg, IDS[:n], blocks)
        other = IDS[20:20 + m]
        tables[1, :4] = [30, 31, 32, 33]
        t = 2 * TB
        lane, pos, slot = np.full(t, LANES, np.int32), np.full(t, -1, np.int32), np.full(t, OOB, np.int32)
        lane[0], pos[0], slot[0] = 0, n, _slots(blocks, [n])[0]
        lane[1:1 + m], pos[1:1 + m] = 1, np.arange(m)
        slot[1:1 + m] = _slots(tables[1], range(m))
        tokens = np.zeros(t, np.int32)
        tokens[0], tokens[1:1 + m] = IDS[n], other
        spans = pack_spans(lane, pos, lanes=LANES, tb_tokens=TB, block_size=BS)
        out, _ = deepseek.deepseek_forward_unified(
            params, cfg, i32(tokens), cache, i32(tables), i32([n + 1, m, 0]), i32(pos), i32(slot),
            i32(lane), *(i32(s) for s in spans), i32([0, m, 0]), cos, sin, tb_tokens=TB)
        return out[:2], (IDS[: n + 1], other), ([n], [m - 1])
    if path == "prefix":
        n, m = 16, 9       # four whole blocks resident, a nine-token tail
        _, cache = _prefill(params, cfg, IDS[:n], blocks)
        out, _ = deepseek.deepseek_forward_prefill_with_prefix(
            params, cfg, i32(IDS[n:n + m] + [0] * 7), cache, i32(blocks[:4]), i32(blocks[4:8]),
            i32(m), i32(n), cos, sin)
        return out[None], IDS[: n + m], [n + m - 1]
    if path == "verify":
        n, w = 22, 4       # the window's four tokens behind 22 resident ones
        _, cache = _prefill(params, cfg, IDS[:n], blocks)
        slot = np.full((LANES, w), OOB, np.int32)
        slot[0] = _slots(blocks, range(n, n + w))
        window = np.zeros((LANES, w), np.int32)
        window[0] = IDS[n:n + w]
        out, _ = deepseek.deepseek_forward_verify(
            params, cfg, i32(window), cache, i32(tables), i32([n + w, 0, 0]), i32(slot), cos, sin)
        return out[0], IDS[: n + w], list(range(n, n + w))
    raise AssertionError(path)


def _error(path, params, weights, cfg=CFG):
    """The largest difference of a logit from the reference's, in units of
    the row's spread."""
    got, ids, rows = _run(path, params, cfg)
    if isinstance(ids, tuple):      # two sequences, a row each
        want = np.concatenate([_want(weights, s, r) for s, r in zip(ids, rows)])
    else:
        want = _want(weights, ids, rows)
    return float(np.max(np.abs(np.asarray(got) - want) / want.std(-1, keepdims=True)))


PATHS = ("prefill_then_decode", "unified", "prefix", "verify")


@pytest.mark.parametrize("path", PATHS)
def test_every_forward_agrees_with_the_references_full_forward(served, path):
    assert _error(path, *served) < TOL


@pytest.mark.parametrize("path", ["prefill_then_decode", "unified"])
def test_the_mixing_skipped_is_a_different_model(served, path):
    """The same weights through a plain residual (``hc_mult`` 1: the mixing
    leaves lie unused) miss the reference by whole spreads of a row."""
    assert _error(path, *served, cfg=dataclasses.replace(CFG, hc_mult=1)) > 0.5


def test_coefficients_through_bfloat16_fail_the_comparison(served, monkeypatch):
    """The streams' statistic and ``phi`` rounded to bfloat16 before their
    product (what a bf16 coefficient path computes) move the logits twenty
    times the tolerance and more (read: 6e-3 of a spread)."""
    exact = hc.coefficients

    def rounded(x, phi, *args, **kwargs):
        return exact(x.astype(jnp.bfloat16), phi.astype(jnp.bfloat16).astype(jnp.float32),
                     *args, **kwargs)

    monkeypatch.setattr(hc, "coefficients", rounded)
    assert _error("prefill_then_decode", *served) > 20 * TOL


@pytest.mark.parametrize("iters,doubly_stochastic", [(20, True), (2, False)])
def test_the_stream_matrix_is_doubly_stochastic_after_twenty_rounds_not_after_two(
        iters, doubly_stochastic):
    """Stream-to-stream logits as a sublayer of the seeded draw meets them (a
    dynamic and a static part, each of order one: variance 2), 4,096 tokens.
    After 20 rounds a token's rows and columns sum to 1 within 1e-4 for more
    than 95 tokens of 100 (the median token within 2e-6, which is ``hc_eps``
    in the last divisor; the rest are matrices with one entry far above the
    others, which Sinkhorn-Knopp leaves slowly); after 2 rounds for none."""
    logits = jnp.sqrt(2.0) * jax.random.normal(jax.random.PRNGKey(11), (4, 4, 4096))
    m = np.asarray(hc.residual_matrix(logits, iters=iters, eps=1e-6, clamp=(-30.0, 30.0)))
    assert (m > 0).all()
    off = np.maximum(np.abs(m.sum(0) - 1).max(0), np.abs(m.sum(1) - 1).max(0))
    if doubly_stochastic:
        assert np.mean(off < 1e-4) > 0.95 and np.median(off) < 2e-6
    else:
        assert not (off < 1e-4).any()


@pytest.mark.parametrize("logit", [100.0, -100.0])
def test_the_clamp_holds_at_logits_far_outside_it(logit):
    """One entry (then all but one) at +-100: ``exp`` would overflow float32
    at 89; clamped to +-30 every entry stays finite, and the result equals
    that of the clamped logits."""
    logits = jnp.zeros((4, 4, 3)).at[1, 2, 0].set(logit).at[:, :, 1].set(logit).at[0, 0, 1].set(0.0)
    kw = dict(iters=20, eps=1e-6)
    m = np.asarray(hc.residual_matrix(logits, clamp=(-30.0, 30.0), **kw))
    assert np.isfinite(m).all() and (m >= 0).all()
    np.testing.assert_array_equal(
        m, np.asarray(hc.residual_matrix(jnp.clip(logits, -30, 30), clamp=(-1e9, 1e9), **kw)))
    if logit > 0:       # what the clamp is for: unclamped, exp(100) is inf and inf / inf is nan
        assert not np.isfinite(np.asarray(hc.residual_matrix(logits, clamp=(-1e9, 1e9), **kw))).all()


def test_the_programs_mixing_is_the_references(served):
    """One sublayer's coefficients and both mixes, the program's lane-major
    layout against the reference's ``[T, n, C]``."""
    params, weights = served
    n, c = CFG.hc_mult, CFG.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(2), (13, n, c))
    y = jax.random.normal(jax.random.PRNGKey(3), (13, c))
    phi, alpha, bias = (params["moe_layers"][k][1, 1] for k in ("hc_phi", "hc_alpha", "hc_bias"))
    h_pre, h_post, h_res = hc.coefficients(
        x.reshape(13, n * c), phi, alpha, bias, n, norm_eps=CFG.rms_norm_eps, iters=20,
        eps=1e-6, clamp=(-30.0, 30.0))
    w_pre, w_post, w_res = REF._coefficients(x, phi, alpha, bias, REF.dims(HF))
    np.testing.assert_allclose(h_pre.T, w_pre, atol=1e-6)
    np.testing.assert_allclose(h_post.T, w_post, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(h_res, -1, 0), w_res, atol=1e-6)
    np.testing.assert_allclose(
        hc.pre_mix(x.reshape(13, -1), h_pre), jnp.einsum("tn,tnc->tc", w_pre, x), atol=1e-5)
    np.testing.assert_allclose(
        hc.post_mix(x.reshape(13, -1), y, h_post, h_res).reshape(13, n, c),
        REF._mix_out(x, y, (w_post, w_res)), atol=1e-5)
    np.testing.assert_array_equal(hc.replicate(y, n).reshape(13, n, c), jnp.repeat(y[:, None], n, 1))
    np.testing.assert_allclose(hc.collapse(x.reshape(13, -1), n), x.sum(1), atol=1e-5)


def test_the_recipe_is_the_references_leaf_for_leaf(served):
    params, weights = served
    seen = 0
    for group, name in (("dense_layers", "dense"), ("moe_layers", "sparse")):
        for leaf, stack in params[group].items():
            if leaf.endswith("norm"):
                assert bool(jnp.all(stack == 1))
                continue
            for layer in range(stack.shape[0]):
                mine = weights[f"{name}{layer}.{leaf}"]
                assert bool(jnp.all(stack[layer] == mine.astype(jnp.float32))), (group, leaf)
                seen += 1
    assert seen == len(weights) - 2
    for leaf in ("embed", "lm_head"):
        assert bool(jnp.all(params[leaf] == weights[leaf].astype(jnp.float32)))
    mixing = params["moe_layers"]
    assert mixing["hc_phi"].shape == (3, 2, 4 * 256, 24) and mixing["hc_bias"].shape == (3, 2, 24)
    assert bool(jnp.all(mixing["hc_alpha"] == 1))
    # the mechanism bites: both parts of a coefficient's logit are of order one
    assert 0.7 < float(jnp.std(mixing["hc_bias"])) < 1.3
    assert 0.7 < float(jnp.std(mixing["hc_phi"])) * np.sqrt(4 * 256) < 1.3


def test_one_stream_draws_the_leaves_it_drew():
    """``hc_mult`` 1 draws no mixing leaf and every other leaf from the key
    it had: the streams' keys are taken last in each group."""
    plain = dataclasses.replace(CFG, hc_mult=1)
    mine, theirs = (init_params(c, jax.random.PRNGKey(9)) for c in (CFG, plain))
    assert not any(k.startswith("hc_") for g in ("dense_layers", "moe_layers") for k in theirs[g])
    for leaf, stack in theirs["dense_layers"].items():
        np.testing.assert_array_equal(stack, mine["dense_layers"][leaf])
    assert set(deepseek.param_specs(CFG)["moe_layers"]) == set(mine["moe_layers"])
    assert set(deepseek.param_specs(plain)["moe_layers"]) == set(theirs["moe_layers"])


def test_the_published_yarn_tables_are_the_references():
    """The program's tables (ops/rope.py) against the reference's own words
    at the published scaling (factor 64 over 4,096, beta 32 / 1), at the
    first position, either side of the original context's end and at the
    served context's last: unscaled, equal to float32's last bits of an
    angle of 8,191 radians; and the softmax scale carries (0.1 ln 64 + 1)^2."""
    hf = json.loads((ROOT / "benchmark" / "configs" / "xing4-29b-l8.json").read_text())
    cfg = DeepseekConfig.from_hf_config(hf)
    cos, sin = deepseek.make_rope_tables(dataclasses.replace(cfg, max_position_embeddings=8192))
    want_cos, want_sin = REF.tables(hf, 8192)
    at = np.asarray([0, 4095, 4096, 8191])
    np.testing.assert_allclose(cos[at], want_cos[at], atol=2e-3)
    np.testing.assert_allclose(sin[at], want_sin[at], atol=2e-3)
    assert float(jnp.max(jnp.abs(cos))) <= 1.0
    # pairs that turn 32 times or more over 4,096 tokens keep their
    # frequency, the slowest are slowed by 64
    plain = 10000.0 ** (-np.arange(32) / 32)
    freqs = np.asarray(REF._frequencies(REF.dims(hf)))
    np.testing.assert_allclose(freqs[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-6)
    assert (np.diff(freqs) < 0).all()
    m = 0.1 * np.log(64.0) + 1.0
    assert cfg.attn_scale == pytest.approx(m * m / np.sqrt(192.0), rel=1e-6)
    assert REF._mscale(REF.dims(hf)) == pytest.approx(m)


def test_the_family_is_bound_and_refuses_a_checkpoint_it_cannot_read(tmp_path):
    family = get_family("xing4_0")
    assert family.forward_unified is deepseek.deepseek_forward_unified
    cfg = family.config_from_hf(HF)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max) == (-30, 30)
    # no checkpoint: the server's sign to draw the weights from its seed
    with pytest.raises(FileNotFoundError):
        family.load_weights(cfg, tmp_path)
    from safetensors.numpy import save_file

    save_file({"model.norm.weight": np.ones(4, np.float32)}, str(tmp_path / "model.safetensors"))
    with pytest.raises(NotImplementedError, match="hc_phi.*seeded weights"):
        family.load_weights(cfg, tmp_path)


@pytest.mark.parametrize("cuts", [[], [14], [6, 17]], ids=["whole", "two_windows", "three_windows"])
def test_a_prompt_in_unified_windows_gives_the_split_forwards_logits(served, cuts):
    """``tiny_xing``'s 23-token prompt through the unified step's Pallas route
    (interpreted) whole, in two windows cut at 14 and in three cut at 6 and 17
    (pages of 4: every continued window's resident prefix ends mid-page): the
    window's own keys decompressed, the resident pages absorbed, one softmax.
    The last token's logits are the split prefill's and the reference's."""
    from tests.models.test_deepseek import unified_in_windows

    params, weights = served
    n = 23
    blocks = list(range(5, 5 + MAX_BLOCKS))
    got, _ = unified_in_windows(
        params, CFG, IDS[:n], cuts, attention="pallas_interpret", block_size=BS,
        num_blocks=BLOCKS, lanes=LANES, tb=TB, blocks=blocks[: -(-n // BS)])
    split, _ = _prefill(params, CFG, IDS[:n], blocks)
    want = _want(weights, IDS[:n], [n - 1])[0]
    spread = want.std()
    assert float(np.max(np.abs(np.asarray(got) - np.asarray(split)))) / spread < TOL
    assert float(np.max(np.abs(np.asarray(got) - want))) / spread < TOL
