"""The expert layer's walk (``ops/moe.py:moe_experts``): the rows its experts
hold, a chunk at a time, summed back per token by a scatter-add (a share of
the router's experts held) or by one gather after the loop (every expert
held).  Against a plain loop over tokens and their chosen experts, on both
combines, at the skews a router can produce, with chunk ends that fall inside
an expert's rows and row counts that are no multiple of a chunk; and the
lowered program holds no array of ``tokens x k`` rows of the expert width, and
of the hidden width only the one buffer of the gather's path, so the buffers
the walk replaced cannot come back unseen."""

import json
from pathlib import Path

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

# what the layer is told of its holding: experts [FIRST, FIRST + E_HELD) of a
# router E_ALL wide (the walk's scatter-add), or all of a router E_HELD wide
# (the gather after the loop; ids outside it stand for what a router cannot
# produce and the layer must not care about)
HOLDINGS = {"a_share": (FIRST, E_ALL), "every_expert": (0, E_HELD)}


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("masked", [False, True], ids=["every_row_a_token", "valid_rows"])
@pytest.mark.parametrize("skew", SKEWS)
@pytest.mark.parametrize("holding", sorted(HOLDINGS))
def test_the_walk_is_the_per_token_sum_at_any_skew(monkeypatch, holding, skew, masked, impl):
    """75 tokens x 4 choices = 300 rows, in chunks of 128: no multiple of a
    chunk; with every assignment on ONE expert its rows straddle three
    chunks (two when 61 of the rows are tokens), with none held the walk
    makes no trip, and ``rows_walked`` is the chunks that hold a live row.
    On both combines: ``rows_gathered`` is every held row or none."""
    chunk = 128
    monkeypatch.setattr(moe, "CHUNK_ROWS", chunk)
    first, routed = HOLDINGS[holding]
    t, h, i, k = 75, 16, 24, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (t, h))
    banks = _banks(keys[1], E_HELD, h, i)
    ids = (_route(skew, keys[2], t, k) - FIRST + first).astype(jnp.int32)
    probs = jax.nn.softmax(jax.random.normal(keys[3], (t, k)), axis=-1)
    valid = np.arange(t) < 61 if masked else np.ones(t, bool)
    out, stats = moe_experts(
        x, ids, probs, *banks, first_expert=first, experts_routed=routed,
        valid=jnp.asarray(valid) if masked else None, impl=impl,
    )
    np.testing.assert_allclose(
        np.asarray(out), _per_token(x, ids, probs, banks, first, valid), rtol=2e-4, atol=2e-4)
    assert not np.asarray(out)[~valid].any()
    stats = dict(zip(MOE_STATS, stats.tolist()))
    local = np.asarray(ids)[valid] - first
    held = int(((local >= 0) & (local < E_HELD)).sum())
    assert stats["assignments_routed"] == int(valid.sum()) * k
    assert stats["assignments_held"] == held
    assert stats["rows_gathered"] == (held if holding == "every_expert" else 0)
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


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax_core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _outvars(jaxpr):
    return ((eqn.primitive.name, var) for eqn in _eqns(jaxpr) for var in eqn.outvars)


def _shapes(jaxpr):
    return ((tuple(var.aval.shape), name) for name, var in _outvars(jaxpr))


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


def test_with_every_expert_held_the_program_has_one_buffer_of_rows_and_no_scatter_add():
    """Traced at 2,048 tokens x 8 choices with all 16 of the router's experts
    held: the walk's loop carries ONE ``[16,384, hidden]`` buffer in ``x``'s
    dtype and writes a chunk's rows into it where they lie; nothing of 16,384
    rows is as wide as the EXPERT width, of the hidden width there is the
    buffer and nothing else (the gather reads a choice's 2,048 rows at a
    time), no float32 ``[tokens, hidden]`` sum rides the loop and no
    ``scatter-add`` is in the program at all."""
    t, k, h, i, e = 2048, 8, 256, 128, 16
    bf16 = jnp.bfloat16
    args = (
        jax.ShapeDtypeStruct((t, h), bf16), jax.ShapeDtypeStruct((t, k), jnp.int32),
        jax.ShapeDtypeStruct((t, k), jnp.float32), jax.ShapeDtypeStruct((e, h, i), bf16),
        jax.ShapeDtypeStruct((e, h, i), bf16), jax.ShapeDtypeStruct((e, i, h), bf16),
    )
    jaxpr = jax.make_jaxpr(lambda *a: moe_experts(*a, experts_routed=e, impl="xla"))(*args)
    rows = t * k
    outs = [(tuple(v.aval.shape), v.aval.dtype, p) for p, v in _outvars(jaxpr.jaxpr)]
    wide = {(s, str(d)) for s, d, _ in outs if rows in s and np.prod(s) >= rows * min(h, i)}
    assert wide == {((rows, h), "bfloat16")}, wide
    assert not [p for _, _, p in outs if p.startswith("scatter")]
    loops = [eqn for eqn in _eqns(jaxpr.jaxpr) if eqn.primitive.name == "while"]
    assert len(loops) == 1
    carried = [(tuple(v.aval.shape), str(v.aval.dtype)) for v in loops[0].outvars]
    assert ((rows, h), "bfloat16") in carried
    assert not [c for c in carried if c[1] == "float32" and np.prod(c[0]) >= t * h], carried
    body = [p for p, _ in _outvars(loops[0].params["body_jaxpr"].jaxpr)]
    assert "dynamic_update_slice" in body and "ragged_dot_general" in body


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_padding_rows_that_point_at_unwritten_chunks_leave_the_sum_finite_and_exact(monkeypatch, impl):
    """A bucket a quarter full, every expert held: 64 of 256 rows are tokens,
    so 192 of the 768 assignments are live and the walk writes two chunks of
    the buffer's six.  The other 576 point past them, at rows no trip wrote:
    what they read is zeros (not whatever the memory held), a padding row's
    sum is exactly 0 and a token's is the per-token reference's."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 128)
    t, h, i, k = 256, 16, 24, 3
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    x = jax.random.normal(keys[0], (t, h))
    banks = _banks(keys[1], E_HELD, h, i)
    ids = jnp.stack([jax.random.permutation(kk, E_HELD)[:k] for kk in jax.random.split(keys[2], t)])
    probs = jax.nn.softmax(jax.random.normal(keys[3], (t, k)), axis=-1)
    valid = np.arange(t) < t // 4
    out, stats = jax.jit(lambda valid, *a: moe_experts(
        *a, experts_routed=E_HELD, valid=valid, impl=impl))(
            jnp.asarray(valid), x, ids.astype(jnp.int32), probs, *banks)
    out = np.asarray(out)
    assert np.isfinite(out).all() and not out[~valid].any()
    np.testing.assert_allclose(out, _per_token(x, ids, probs, banks, 0, valid), rtol=2e-4, atol=2e-4)
    stats = dict(zip(MOE_STATS, stats.tolist()))
    assert stats["rows_gathered"] == stats["assignments_held"] == 192
    assert stats["rows_walked"] == 2 * 128


def _routed_widths():
    """(hidden, expert width, choices a token, lanes) of every routed
    configuration under ``benchmark/configs/`` and of ``mixtral-8x7b-l4``
    (``tests/ops/test_chip_compile.py``: no cell serves it)."""
    out = {"mixtral-8x7b-l4": (4096, 14336, 2, 8)}
    for path in sorted((Path(__file__).parents[2] / "benchmark" / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        if "moe_intermediate_size" in cfg:
            args = cfg["serving"]["args"]
            out[path.stem] = (
                cfg["hidden_size"], cfg["moe_intermediate_size"],
                cfg["num_experts_per_tok"], args[args.index("--max-batch-size") + 1])
    return out


ROUTED = _routed_widths()


def test_the_benchmarks_routed_configurations_are_all_here():
    assert sorted(ROUTED) == ["k-exaone-236b-l8", "mixtral-8x7b-l4", "moonlight-16b-l9", "xing4-29b-l8"]


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("rows", ["decode", "prompt"])
@pytest.mark.parametrize("config", sorted(ROUTED))
def test_the_tiling_divides_the_widths_and_fits_fast_memory(config, rows, product):
    """``gmm_tiling`` at a decode step's rows (lanes x choices) and at a
    prompt's 2,048-row chunk: no masked column (``tk | k``, ``tn | n``, whole
    128-lane tiles), the row tile a multiple of the bf16 sublane tile, the
    blocks within the budget the rule states, a decode step's bank blocks at
    least 1,024 columns wide, and at 6,144 x 2,048 the tilings the chip kept
    (PERF.md sections 5 and 6, PR 53)."""
    h, width, choices, lanes = ROUTED[config]
    m = min((lanes if rows == "decode" else 8192) * choices, moe.CHUNK_ROWS)
    k, n = (h, width) if product == "up" else (width, h)
    tm, tk, tn = moe.gmm_tiling(m, k, n, 2)
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0, (tm, tk, tn)
    assert tm % 16 == 0 and tm == moe.tile_rows(m)
    assert moe.gmm_block_bytes(tm, tk, tn, 2) <= moe.GMM_BLOCK_BYTES < 16 * 2**20
    assert rows == "prompt" or tn >= 1024
    if config == "k-exaone-236b-l8":
        assert (tm, tk, tn) == {
            ("decode", "up"): (128, 1024, 2048), ("decode", "down"): (128, 2048, 1024),
            ("prompt", "up"): (256, 6144, 256), ("prompt", "down"): (256, 2048, 1024),
        }[rows, product]


def test_a_width_no_tile_divides_keeps_the_old_tiling():
    """The CPU tests' widths (16 x 24) and any width that is no multiple of
    128: correctness never depends on the rule."""
    assert moe.gmm_tiling(128, 16, 24, 4) == (128, 16, 24)
    assert moe.gmm_tiling(2048, 2048, 1400, 2) == (256, 1024, 1024)


@pytest.mark.parametrize("widths", [(2048, 1408), (3584, 1024)], ids=["2048x1408", "3584x1024"])
def test_the_products_at_the_cells_widths_are_xlas(monkeypatch, widths):
    """The kernel at the tiling the rule gives ``moonlight-16b-l9``'s and
    ``xing4-29b-l8``'s widths (bf16, interpreted) against ``ragged_dot``: 75
    tokens x 3 choices over 4 experts of which one gets nothing, in chunks of
    128 rows: groups of 75 rows straddle row tiles and chunks, and the last
    chunk is part empty."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 128)
    h, i = widths
    t, k = 75, 3
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(keys[0], (t, h), jnp.bfloat16)
    banks = [(b * h ** -0.5).astype(jnp.bfloat16) for b in _banks(keys[1], E_HELD, h, i)]
    ids = FIRST + jnp.asarray([0, 1, 3], jnp.int32)[
        jnp.stack([jax.random.permutation(kk, 3) for kk in jax.random.split(keys[2], t)])]
    probs = jax.nn.softmax(jax.random.normal(keys[3], (t, k)), axis=-1)
    tm = moe.tile_rows(128)
    assert moe.gmm_tiling(128, i, h, 2)[1] == i and h % moe.gmm_tiling(128, h, i, 2)[1] == 0
    got, stats = moe_experts(x, ids, probs, *banks, first_expert=FIRST, impl="pallas_interpret")
    want, _ = moe_experts(x, ids, probs, *banks, first_expert=FIRST, impl="xla")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)
    stats = dict(zip(MOE_STATS, stats.tolist()))
    assert stats["expert_rows_max"] == 75 and stats["rows_walked"] == 2 * 128
    # [0, 75) and [75, 128) of the first chunk, [0, 22) and [22, 97) of the second
    assert stats["rows_multiplied"] == tm * sum(
        -(-end // tm) - start // tm for start, end in ((0, 75), (75, 128), (0, 22), (22, 97)))


def test_rows_multiplied_is_the_row_tiles_the_kernel_visits(monkeypatch):
    """Ten tokens x 2 choices over four held experts: 5, 0, 9 and 6 rows, in
    chunks of 16 at row tiles of 8.  Chunk 0 holds rows [0, 5) of expert 0 (one
    tile), [5, 14) of expert 2 (tiles 0 and 1) and [14, 16) of expert 3 (tile
    1): four visits; chunk 1 the last four rows of expert 3: one.  Five visits
    x 8 rows for 20 held."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 16)
    monkeypatch.setattr(moe, "tile_rows", lambda m: 8)
    experts = np.repeat([0, 2, 3], [5, 9, 6])
    ids = jnp.asarray(FIRST + np.random.default_rng(3).permutation(experts).reshape(10, 2), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    x = jax.random.normal(keys[0], (10, 16))
    _, stats = moe_experts(
        x, ids, jnp.full((10, 2), 0.5), *_banks(keys[1], E_HELD, 16, 24), first_expert=FIRST, impl="xla")
    stats = dict(zip(MOE_STATS, stats.tolist()))
    assert stats["assignments_held"] == 20 and stats["rows_walked"] == 32
    assert stats["rows_multiplied"] == 5 * 8
