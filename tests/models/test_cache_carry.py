"""The KV cache rides the layer loop as a carry of flat pages
(models/llama.py ``_scan_layers``): each layer writes and reads the ONE
buffer at its own page offset.  Held here, for every forward that takes the
stacked cache:

(a) a pad token, an inactive lane or an out-of-range slot writes nowhere (an
    offset added to the layer's own out-of-range sentinel would land in the
    next layer's first page), and a live token's K/V lands in its own
    layer's page only;
(b) logits and the returned cache equal, bit for bit, the loop that slices
    layer ``l`` out, runs the same layer body on it and stacks the layers
    back (what the forwards did before);
(c) the returned cache has the stored shape and, under a ``tp`` mesh, the
    stored sharding.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from dynamo_tpu.models import llama
from dynamo_tpu.models.llama import (
    LlamaConfig,
    init_params,
    kv_cache_spec,
    make_rope_tables,
    param_specs,
)
from dynamo_tpu.ops.pallas.ragged_attention import pack_spans
from dynamo_tpu.parallel import MeshConfig, make_mesh, shard_pytree

# three layers: the middle one has a neighbour on both sides to spill into
CFG = dataclasses.replace(LlamaConfig.tiny(), num_layers=3)
N, BS = 12, 4               # pages of one layer, tokens a page
OOB = N * BS                # the engine's sentinel slot: one past a layer
LANES, MAX_BLOCKS, TB = 4, 6, 8

FORWARDS = ("prefill", "prefill_with_prefix", "decode", "unified", "verify")
# the two prefill forwards attend densely: no attention implementation to
# pick.  The Pallas decode kernel reads a slice of the layer where K and V of
# one layer fit the chip's fast memory (they do at this size) and the flat
# pages where they do not: "pallas_interpret-flat" takes the budget away.
CASES = [
    (name, attention)
    for name in FORWARDS
    for attention in (
        ("jax",) if name.startswith("prefill") else ("jax", "pallas_interpret")
    )
] + [("decode", "pallas_interpret-flat")]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _random_cache(seed=1):
    """A cache full of random values: a write that strays shows anywhere."""
    shape = (CFG.num_layers, N, BS, CFG.num_kv_heads, CFG.head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "k": jax.random.normal(kk, shape, CFG.dtype),
        "v": jax.random.normal(kv, shape, CFG.dtype),
    }


def _slots(block_ids, positions):
    return [int(block_ids[p // BS]) * BS + p % BS for p in positions]


def _case(name, attention, monkeypatch=None, **decode_kwargs):
    """(forward with params/cache left open, the slots live tokens own).
    Every batch carries pads, inactive lanes or out-of-range slots."""
    if attention.endswith("-flat"):
        attention = attention.removesuffix("-flat")
        monkeypatch.setattr(llama, "_ON_CHIP_PAGES_BYTES", 0)
    cos, sin = make_rope_tables(CFG)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    if name == "prefill":
        ids, seq_len, seq_pad = [4, 2, 8, 0, 0, 0], 10, 16
        tokens = list(range(2, 2 + seq_len)) + [0] * (seq_pad - seq_len)
        fwd = lambda p, c: llama.llama_forward_prefill(  # noqa: E731
            p, CFG, i32(tokens), c, i32(ids), i32(seq_len), i32(0), cos, sin
        )
        return fwd, _slots(ids, range(seq_len))
    if name == "prefill_with_prefix":
        full, tail = [4, 2, 8, 6, 0, 0], [8, 6, 0, 0, 0, 0]
        tail_len, tail_pad, start = 5, 8, 8
        tokens = list(range(20, 20 + tail_len)) + [0] * (tail_pad - tail_len)
        fwd = lambda p, c: llama.llama_forward_prefill_with_prefix(  # noqa: E731
            p, CFG, i32(tokens), c, i32(full), i32(tail), i32(tail_len),
            i32(start), cos, sin,
        )
        return fwd, _slots(tail, range(tail_len))
    tables = np.zeros((LANES, MAX_BLOCKS), np.int32)
    tables[0, :3], tables[1, :3] = [3, 5, 11], [7, 1, 9]
    if name == "decode":
        # lanes 2 and 3 idle: the sentinel, and a slot further out of range
        lens = [6, 9, 0, 0]
        live = [_slots(tables[0], [5])[0], _slots(tables[1], [8])[0]]
        fwd = lambda p, c: llama.llama_forward_decode(  # noqa: E731
            p, CFG, i32([5, 6, 0, 0]), c, i32(tables), i32(lens),
            i32(live + [OOB, OOB + 5]), cos, sin, attention=attention,
            **decode_kwargs,
        )
        return fwd, live
    if name == "unified":
        # lane 0 decodes at position 5; lane 1 prefills 4..9 behind a
        # resident prefix 0..3; the rest of the flat axis is pad
        t = 2 * TB
        lane = np.full((t,), LANES, np.int32)
        pos = np.full((t,), -1, np.int32)
        slot = np.full((t,), OOB, np.int32)
        lane[0], pos[0], slot[0] = 0, 5, _slots(tables[0], [5])[0]
        lane[1:7], pos[1:7] = 1, np.arange(4, 10)
        slot[1:7] = _slots(tables[1], range(4, 10))
        slot[-1] = OOB + 7  # a pad further out of range than the sentinel
        spans = pack_spans(lane, pos, lanes=LANES, tb_tokens=TB, block_size=BS)
        fwd = lambda p, c: llama.llama_forward_unified(  # noqa: E731
            p, CFG, i32(np.arange(t) % 50 + 3), c, i32(tables),
            i32([6, 10, 0, 0]), i32(pos), i32(slot), i32(lane),
            *(i32(s) for s in spans), i32([0, 6, 0, 0]), cos, sin,
            attention=attention, tb_tokens=TB,
        )
        return fwd, [int(s) for s in slot[:7]]
    if name == "verify":
        w, lens = 3, [7, 10, 0, 0]
        slot = np.full((LANES, w), OOB, np.int32)
        slot[0] = _slots(tables[0], range(7 - w, 7))
        slot[1] = _slots(tables[1], range(10 - w, 10))
        slot[3, 1] = OOB + 3
        fwd = lambda p, c: llama.llama_forward_verify(  # noqa: E731
            p, CFG, i32(np.arange(LANES * w).reshape(LANES, w) + 9), c,
            i32(tables), i32(lens), i32(slot), cos, sin, attention=attention,
        )
        return fwd, [int(s) for s in slot[:2].reshape(-1)]
    raise AssertionError(name)


def _scan_layers_sliced(layer, x, layers, kv_cache):
    """The loop the forwards ran before: the stacked cache as per-layer scan
    INPUTS, fresh stacked outputs back.  Layer ``l`` sees its own
    ``[N, bs, kvh, d]`` slice, so it sits at page 0 of a one-layer cache."""
    num_blocks, block_size = kv_cache["k"].shape[1:3]

    def body(x, layer_in):
        w, k_layer, v_layer = layer_in
        at = llama._LayerPages(jnp.int32(0), num_blocks, block_size, 1)
        x, k_layer, v_layer = layer(x, w, k_layer, v_layer, at)
        return x, (k_layer, v_layer)

    x, (k, v) = jax.lax.scan(body, x, (layers, kv_cache["k"], kv_cache["v"]))
    return x, {"k": k, "v": v}


@pytest.mark.parametrize("name,attention", CASES)
def test_only_live_slots_of_each_layer_change(params, monkeypatch, name, attention):
    fwd, live = _case(name, attention, monkeypatch)
    old = _random_cache()
    _, new = jax.jit(fwd)(params, old)
    want = np.zeros((N * BS,), bool)
    want[live] = True
    for leaf in ("k", "v"):
        assert new[leaf].shape == old[leaf].shape
        changed = np.asarray(new[leaf] != old[leaf]).reshape(
            CFG.num_layers, N * BS, -1
        )
        # every element of a live slot is rewritten, in EVERY layer; nothing
        # else moves: no pad lands in a neighbour layer's first page
        assert (changed.all(-1) == want[None]).all(), (name, leaf)
        assert (changed.any(-1) == want[None]).all(), (name, leaf)
    # the layers hold different K/V for one token (its own, not a copy)
    k_live = np.asarray(new["k"]).reshape(CFG.num_layers, N * BS, -1)[:, live]
    assert not np.array_equal(k_live[0], k_live[1])
    assert not np.array_equal(k_live[1], k_live[2])


@pytest.mark.parametrize("name,attention", CASES)
def test_bit_equal_to_slicing_each_layer_out(params, monkeypatch, name, attention):
    fwd, _ = _case(name, attention, monkeypatch)
    cache = _random_cache()
    logits, new = jax.jit(fwd)(params, cache)
    monkeypatch.setattr(llama, "_scan_layers", _scan_layers_sliced)
    want_logits, want = jax.jit(lambda p, c: fwd(p, c))(params, cache)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[leaf]), np.asarray(want[leaf]))


@pytest.mark.parametrize("name", FORWARDS)
def test_stored_shape_and_sharding_under_tp(params, name):
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    fwd, _ = _case(name, "jax")
    cache = _random_cache()
    want_logits, want = jax.jit(fwd)(params, cache)
    stored = NamedSharding(mesh, kv_cache_spec())
    sharded_params = shard_pytree(params, param_specs(CFG), mesh)
    sharded_cache = shard_pytree(cache, {"k": kv_cache_spec(), "v": kv_cache_spec()}, mesh)
    with mesh:
        # no out_shardings pinned: the sharding has to SURVIVE the flat view
        logits, new = jax.jit(fwd)(sharded_params, sharded_cache)
    for leaf in ("k", "v"):
        assert new[leaf].shape == cache[leaf].shape
        assert new[leaf].sharding.is_equivalent_to(stored, new[leaf].ndim), (
            name, new[leaf].sharding,
        )
        np.testing.assert_allclose(new[leaf], want[leaf], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("attention", ["pallas_interpret", "pallas_interpret-flat"])
def test_decode_kernel_under_tp_shard_map(params, monkeypatch, attention):
    """The ``tp`` shard_map around the Pallas decode kernel takes a slice of
    the layer, or the flat pages, with the spec it took a layer's pages with."""
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    cache = _random_cache()
    want_logits, want = jax.jit(_case("decode", "jax")[0])(params, cache)
    fwd, _ = _case("decode", attention, monkeypatch, tp_mesh=mesh)
    sharded_params = shard_pytree(params, param_specs(CFG), mesh)
    sharded_cache = shard_pytree(cache, {"k": kv_cache_spec(), "v": kv_cache_spec()}, mesh)
    with mesh:
        logits, new = jax.jit(fwd)(sharded_params, sharded_cache)
    np.testing.assert_allclose(logits[:2], want_logits[:2], rtol=2e-3, atol=2e-3)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(new[leaf], want[leaf], rtol=2e-5, atol=2e-5)
