"""exaone_moe: the expert layer that is told what it holds, and the shares
of a layer adding up to the whole (benchmark/reference/exaone_moe.py is the
uncut layer's arbiter)."""

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import modules
from dynamo_tpu.models.exaone_moe import ExaoneMoeConfig, init_params
from dynamo_tpu.ops.moe import MOE_STATS, moe_experts, moe_ffn

REF = modules.load(
    Path(__file__).resolve().parents[2] / "benchmark" / "reference" / "exaone_moe.py"
)


def _banks(key, e, h, i):
    kg, ku, kd = jax.random.split(key, 3)
    return (jax.random.normal(kg, (e, h, i)) / 4, jax.random.normal(ku, (e, h, i)) / 4,
            jax.random.normal(kd, (e, i, h)) / 4)


def _per_token(x, ids, probs, banks, first=0, valid=None):
    """Each token (of the ``valid`` rows) through each of its chosen experts
    that is held."""
    gate, up, down = (np.asarray(b) for b in banks)
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]) if valid is None else np.flatnonzero(valid):
        for e, p in zip(np.asarray(ids[t]) - first, np.asarray(probs[t])):
            if 0 <= e < gate.shape[0]:
                hid = np.asarray(jax.nn.silu(x[t] @ gate[e])) * np.asarray(x[t] @ up[e])
                out[t] += p * (hid @ down[e])
    return out


@pytest.mark.parametrize("skew", ["even", "all_to_one", "all_to_absent"])
def test_no_assignment_dropped_at_any_skew(skew):
    """512 tokens x 4 choices over 32 experts, 4 held (an eighth, so about
    256 of the 2,048 assignments when routed evenly).  With every token
    sent to ONE held expert (2,048 rows for it, eight times its even share)
    each token still gets that expert's full result; with none held, zeros."""
    t, h, i, e_all, e, k, first = 512, 16, 24, 32, 4, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (t, h))
    banks = _banks(keys[1], e, h, i)
    if skew == "even":
        ids = jnp.stack([jax.random.permutation(kk, e_all)[:k]
                         for kk in jax.random.split(keys[2], t)])
    else:
        # one expert for every choice of every token (a router would pick k
        # different ones; the layer must not care)
        ids = jnp.full((t, k), first + 1 if skew == "all_to_one" else 0, jnp.int32)
    probs = jax.nn.softmax(jax.random.normal(keys[3], (t, k)), axis=-1)
    out, stats = jax.jit(lambda *a: moe_experts(*a, first_expert=first))(x, ids, probs, *banks)
    np.testing.assert_allclose(
        np.asarray(out), _per_token(x, ids, probs, banks, first), rtol=2e-4, atol=2e-4)
    stats = dict(zip(MOE_STATS, stats.tolist()))
    assert stats["assignments_routed"] == t * k
    want_held = {"all_to_one": t * k, "all_to_absent": 0}.get(
        skew, int(((np.asarray(ids) >= first) & (np.asarray(ids) < first + e)).sum()))
    assert stats["assignments_held"] == want_held
    if skew == "all_to_one":
        assert (stats["experts_touched"], stats["expert_rows_max"]) == (1, t * k)


def test_rows_that_are_no_token_are_not_routed():
    t, h, i, e, k = 12, 8, 8, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (t, h))
    banks = _banks(keys[1], e, h, i)
    router = jax.random.normal(keys[2], (h, e))
    valid = jnp.arange(t) < 7
    out, stats = moe_ffn(x, router, *banks, top_k=k, valid=valid, with_stats=True)
    whole = moe_ffn(x, router, *banks, top_k=k)
    np.testing.assert_allclose(np.asarray(out[:7]), np.asarray(whole[:7]), rtol=1e-5, atol=1e-5)
    assert not np.asarray(out[7:]).any()
    assert stats.tolist()[:2] == [7 * k, 7 * k]


def test_stacked_banks_and_a_layer_index_are_that_layers_banks():
    """The layer loop hands the expert layer its group's whole stack and the
    layer's index (a kernel reads its layer where it lies): same sum."""
    t, h, i, e, k, layers = 10, 8, 8, 3, 2, 4
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(keys[0], (t, h))
    stacks = [jnp.stack(b) for b in zip(*[_banks(kk, e, h, i) for kk in jax.random.split(keys[1], layers)])]
    ids = jax.random.randint(keys[2], (t, k), 0, e)
    probs = jnp.ones((t, k)) / k
    for impl in ("xla", "pallas_interpret"):
        got, _ = moe_experts(x, ids, probs, *[(s, jnp.int32(2)) for s in stacks], impl=impl)
        want, _ = moe_experts(x, ids, probs, *[s[2] for s in stacks], impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


HF = {
    "model_type": "exaone_moe", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 8, "num_key_value_heads": 4,
    "head_dim": 16, "vocab_size": 128, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "sliding_window": 6, "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse"], "num_experts": 8,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "max_position_embeddings": 64, "tie_word_embeddings": False,
}


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """One sparse layer of the uncut model (8 heads over 4 KV heads, 8
    experts) against its 4 shares (2 heads over 1 KV head, 2 experts each):
    the attention parts and the routed parts of all ranks, the shared expert
    counted once, sum to what the reference gives for the whole layer."""
    ranks, t = 4, 20
    w = REF.init_weights(HF, 9)
    c = REF.dims(HF)
    layer = {n: w[f"sparse0.{n}"] for n in REF.SPARSE}
    x = jax.random.normal(jax.random.PRNGKey(1), (t, 64))
    pos = jnp.arange(t)
    cos = sin = jnp.zeros((t, 1, 8))        # a full layer: not rotated
    diff = pos[:, None] - pos[None, :]
    with jax.default_matmul_precision("highest"):
        whole = REF._sparse_layer(x, layer, cos, sin, diff, 0, c)
        attn_parts, routed_parts = [], []
        for r in range(ranks):
            share = dict(HF, num_attention_heads=2, num_key_value_heads=1, num_experts=2,
                         expert_parallel_size=ranks, expert_parallel_rank=r, num_shared_experts=0)
            cr = REF.dims(share)
            qs, ks = slice(r * 32, (r + 1) * 32), slice(r * 16, (r + 1) * 16)
            mine = dict(layer, wq=layer["wq"][:, qs], wk=layer["wk"][:, ks], wv=layer["wv"][:, ks],
                        wo=layer["wo"][qs], w_gate=layer["w_gate"][2 * r:2 * r + 2],
                        w_up=layer["w_up"][2 * r:2 * r + 2], w_down=layer["w_down"][2 * r:2 * r + 2])
            attn_parts.append(REF._attention(x, mine, cos, sin, diff, 0, cr) - x)
            after_attention = REF._attention(x, layer, cos, sin, diff, 0, c)
            # the routed part alone, of the true post-attention stream: the
            # layer run on it with attention and shared expert taken out
            routed_parts.append(_routed_part(after_attention, mine, cr))
        shared = REF._gated(REF._rms(after_attention, c["eps"]), layer["ws_gate"],
                            layer["ws_up"], layer["ws_down"])
    np.testing.assert_allclose(
        np.asarray(x + sum(attn_parts)), np.asarray(after_attention), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(after_attention + shared + sum(routed_parts)), np.asarray(whole),
        rtol=1e-4, atol=1e-4)


def _routed_part(stream, w, c):
    """A rank's routed sum over ``stream``, by the PROGRAM's expert layer
    (the same router over all experts, its own two held)."""
    m = REF._rms(stream, c["eps"])
    out = moe_ffn(
        m, w["w_router"], *(w[n].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down")),
        top_k=c["k"], router_bias=w["router_bias"], scoring="sigmoid_noaux",
        first_expert=c["first"],
    )
    return c["scale"] * out


def test_config_reads_the_layer_kinds_and_the_share():
    cfg = ExaoneMoeConfig.from_hf_config(dict(
        HF, num_hidden_layers=8, layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 12,
        mlp_layer_types=["dense"] + ["sparse"] * 47, num_experts=16, expert_parallel_size=8,
        expert_parallel_rank=3))
    assert (cfg.window_layers, cfg.full_layers, cfg.dense_layers) == (6, 2, 1)
    assert (cfg.num_experts_total, cfg.first_expert, cfg.sliding_window) == (128, 48, None)
    runs = cfg.layer_runs()
    assert [(r.kind.group, r.kind.pool, r.start, r.count, r.pool_start) for r in runs] == [
        ("dense_layers", "window", 0, 1, 0), ("layers", "window", 0, 2, 1),
        ("layers", "kv", 2, 1, 0), ("layers", "window", 3, 3, 3), ("layers", "kv", 6, 1, 1)]
    assert all(r.kind.rope == (r.kind.pool == "window") for r in runs)
    params = jax.eval_shape(lambda: init_params(dataclasses.replace(cfg), jax.random.PRNGKey(0)))
    assert params["layers"]["w_router"].shape == (7, 64, 128)
    assert params["layers"]["w_gate"].shape == (7, 16, 64, 32)
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 96)
