"""The expert layer's walk (``ops/moe.py:moe_experts``): the rows its experts
hold, a chunk at a time.  Against a plain loop over tokens and their chosen
experts, at the skews a router can produce, with chunk ends that fall inside
an expert's rows and row counts that are no multiple of a chunk; and the
lowered program holds no array of ``tokens x k`` rows of the hidden or the
expert width, so the buffer the walk replaced cannot come back unseen."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from dynamo_tpu.ops import moe
from dynamo_tpu.ops.moe import MOE_STATS, moe_experts
from tests.models.test_exaone_moe import _banks, _per_token

E_ALL, E_HELD, FIRST = 32, 4, 8


def _route(skew, key, t, k, e_held=E_HELD):
    if skew == "even_an_eighth_held":
        return jnp.stack([jax.random.permutation(kk, E_ALL)[:k] for kk in jax.random.split(key, t)])
    if skew == "all_held":
        # what a chip that holds all its experts sees (mixtral): every row live
        return FIRST + jnp.stack([jax.random.permutation(kk, e_held)[:k] for kk in jax.random.split(key, t)])
    # one expert for every choice of every token (a router would pick k
    # different ones; the layer must not care): held, or not here
    return jnp.full((t, k), FIRST + 1 if skew == "all_to_one_held" else 0, jnp.int32)


SKEWS = ("even_an_eighth_held", "all_to_one_held", "none_held", "all_held")


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("masked", [False, True], ids=["every_row_a_token", "valid_rows"])
@pytest.mark.parametrize("skew", SKEWS)
def test_the_walk_is_the_per_token_sum_at_any_skew(monkeypatch, skew, masked, impl):
    """75 tokens x 4 choices = 300 rows, in chunks of 128: no multiple of a
    chunk; with every assignment on ONE expert its rows straddle three
    chunks (two when 61 of the rows are tokens), with none held the walk
    makes no trip, and ``rows_walked`` is the chunks that hold a live row."""
    chunk = 128
    monkeypatch.setattr(moe, "CHUNK_ROWS", chunk)
    t, h, i, k = 75, 16, 24, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (t, h))
    banks = _banks(keys[1], E_HELD, h, i)
    ids = _route(skew, keys[2], t, k).astype(jnp.int32)
    probs = jax.nn.softmax(jax.random.normal(keys[3], (t, k)), axis=-1)
    valid = np.arange(t) < 61 if masked else np.ones(t, bool)
    out, stats = moe_experts(
        x, ids, probs, *banks, first_expert=FIRST,
        valid=jnp.asarray(valid) if masked else None, impl=impl,
    )
    np.testing.assert_allclose(
        np.asarray(out), _per_token(x, ids, probs, banks, FIRST, valid), rtol=2e-4, atol=2e-4)
    assert not np.asarray(out)[~valid].any()
    stats = dict(zip(MOE_STATS, stats.tolist()))
    local = np.asarray(ids)[valid] - FIRST
    held = int(((local >= 0) & (local < E_HELD)).sum())
    assert stats["assignments_routed"] == int(valid.sum()) * k
    assert stats["assignments_held"] == held
    assert stats["rows_walked"] == -(-held // chunk) * chunk
    # an expert counts once a chunk that visits it: its banks are read in each
    sizes = np.bincount(local[(local >= 0) & (local < E_HELD)], minlength=E_HELD)
    ends = np.cumsum(sizes)
    assert stats["experts_touched"] == sum(
        (end - 1) // chunk - (end - n) // chunk + 1 for end, n in zip(ends, sizes) if n)
    if skew == "all_to_one_held":
        assert stats["experts_touched"] == -(-held // chunk)
        assert stats["expert_rows_max"] == held


def test_a_prompt_buckets_rows_at_the_chunk_the_tree_ships():
    """``CHUNK_ROWS`` as it is: 520 tokens x 4 = 2,080 rows on one expert are
    two chunks; 16 lanes x 8 = 128 rows (a decode step) are one chunk of
    their own size, walked once whatever share of them is held."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    t, h, i, k = 520, 8, 8, 4
    x = jax.random.normal(keys[0], (t, h))
    banks = _banks(keys[1], E_HELD, h, i)
    probs = jax.nn.softmax(jax.random.normal(keys[3], (t, k)), axis=-1)
    ids = _route("all_to_one_held", keys[2], t, k)
    out, stats = jax.jit(lambda *a: moe_experts(*a, first_expert=FIRST, impl="xla"))(x, ids, probs, *banks)
    np.testing.assert_allclose(
        np.asarray(out), _per_token(x, ids, probs, banks, FIRST), rtol=2e-4, atol=2e-4)
    assert dict(zip(MOE_STATS, stats.tolist()))["rows_walked"] == 2 * moe.CHUNK_ROWS
    lanes, k = 16, 8
    ids = _route("even_an_eighth_held", keys[2], lanes, k)
    _, stats = moe_experts(x[:lanes], ids, jnp.ones((lanes, k)) / k, *banks, first_expert=FIRST, impl="xla")
    stats = dict(zip(MOE_STATS, stats.tolist()))
    assert 0 < stats["assignments_held"] < lanes * k and stats["rows_walked"] == lanes * k


def _shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(var.aval.shape), eqn.primitive.name
        for sub in jax_core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


def test_no_array_of_tokens_x_k_rows_of_the_hidden_or_the_expert_width():
    """Traced at 2,048 tokens x 8 choices (the program an ``impl`` of "xla"
    is lowered from): only indices, masks and the count by expert have 16,384
    rows; nothing of 16,384 rows is as wide as the hidden or the expert width,
    and the walk's loop is there with the rows of ONE chunk."""
    t, k, h, i, e = 2048, 8, 256, 128, 16
    f32 = jnp.float32
    args = (
        jax.ShapeDtypeStruct((t, h), f32), jax.ShapeDtypeStruct((t, k), jnp.int32),
        jax.ShapeDtypeStruct((t, k), f32), jax.ShapeDtypeStruct((e, h, i), f32),
        jax.ShapeDtypeStruct((e, h, i), f32), jax.ShapeDtypeStruct((e, i, h), f32),
    )
    jaxpr = jax.make_jaxpr(lambda *a: moe_experts(*a, first_expert=32, impl="xla"))(*args)
    shapes = list(_shapes(jaxpr.jaxpr))
    rows = t * k
    wide = [(s, p) for s, p in shapes if rows in s and np.prod(s) >= rows * min(h, i)]
    assert not wide, wide
    assert any(p == "while" for _, p in shapes)
    assert (moe.CHUNK_ROWS, i) in [s for s, _ in shapes]
    assert (moe.CHUNK_ROWS, h) in [s for s, _ in shapes]
