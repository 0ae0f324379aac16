"""Pallas kernels vs pure-JAX references (interpret mode on the CPU mesh;
the same kernels compile for TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_decode_attention, write_prefill_kv
from dynamo_tpu.ops.pallas import gather_blocks, paged_attention_decode, scatter_blocks


def build_cache(rng, num_blocks=16, bs=8, kvh=2, d=128, batch=3, maxb=4):
    keys = jax.random.split(rng, 3)
    k_cache = jnp.zeros((num_blocks, bs, kvh, d), jnp.float32)
    v_cache = jnp.zeros((num_blocks, bs, kvh, d), jnp.float32)
    ctx = [5, 17, 29]
    tables = jnp.asarray(
        [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], jnp.int32
    )
    for i in range(batch):
        n = ctx[i]
        pad = maxb * bs
        k_seq = jax.random.normal(jax.random.fold_in(keys[0], i), (pad, kvh, d))
        v_seq = jax.random.normal(jax.random.fold_in(keys[1], i), (pad, kvh, d))
        k_cache, v_cache = write_prefill_kv(
            k_cache, v_cache, k_seq, v_seq, tables[i], jnp.int32(n)
        )
    return k_cache, v_cache, tables, jnp.asarray(ctx, jnp.int32)


def test_paged_attention_matches_reference():
    rng = jax.random.PRNGKey(0)
    k_cache, v_cache, tables, ctx = build_cache(rng)
    q = jax.random.normal(jax.random.fold_in(rng, 9), (3, 4, 128), jnp.float32)

    ref = paged_decode_attention(q, k_cache, v_cache, tables, ctx)
    out = paged_attention_decode(q, k_cache, v_cache, tables, ctx, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_attention_gqa_groups():
    rng = jax.random.PRNGKey(1)
    k_cache, v_cache, tables, ctx = build_cache(rng, kvh=2)
    q = jax.random.normal(rng, (3, 8, 128), jnp.float32)  # 4 groups per kv head
    ref = paged_decode_attention(q, k_cache, v_cache, tables, ctx)
    out = paged_attention_decode(q, k_cache, v_cache, tables, ctx, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_gather_scatter_blocks_roundtrip():
    rng = jax.random.PRNGKey(2)
    pool = jax.random.normal(rng, (10, 8, 2, 128), jnp.float32)
    src_ids = jnp.asarray([7, 2, 5], jnp.int32)

    gathered = gather_blocks(pool, src_ids, interpret=True)
    np.testing.assert_allclose(gathered, pool[src_ids])

    dst_pool = jnp.zeros_like(pool)
    dst_ids = jnp.asarray([1, 3, 9], jnp.int32)
    out = scatter_blocks(dst_pool, gathered, dst_ids, interpret=True)
    np.testing.assert_allclose(out[dst_ids], pool[src_ids])
    # untouched slots stay zero
    np.testing.assert_allclose(out[0], jnp.zeros_like(pool[0]))


def test_mla_paged_attention_matches_reference():
    """MLA kernel vs a dense latent-space softmax reference."""
    from dynamo_tpu.ops.pallas.mla_attention import mla_paged_attention_decode

    rng = jax.random.PRNGKey(3)
    b, h, r, p, bs, maxb, nblocks = 3, 4, 32, 16, 8, 4, 16
    keys = jax.random.split(rng, 4)
    q_lat = jax.random.normal(keys[0], (b, h, r), jnp.float32)
    q_rope = jax.random.normal(keys[1], (b, h, p), jnp.float32)
    ck = jax.random.normal(keys[2], (nblocks, bs, r), jnp.float32)
    kr = jax.random.normal(keys[3], (nblocks, bs, p), jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], jnp.int32)
    ctx = jnp.asarray([5, 17, 29], jnp.int32)
    scale = 0.17

    out = mla_paged_attention_decode(
        q_lat, q_rope, ck, kr, tables, ctx, scale=scale, interpret=True
    )

    # dense reference
    length = maxb * bs
    ck_g = ck[tables].reshape(b, length, r)
    kr_g = kr[tables].reshape(b, length, p)
    logits = (
        jnp.einsum("bhr,btr->bht", q_lat, ck_g)
        + jnp.einsum("bhp,btp->bht", q_rope, kr_g)
    ) * scale
    valid = jnp.arange(length)[None, :] < ctx[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bht,btr->bhr", weights, ck_g)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_rope_scaling_llama3_and_yarn():
    """rope_table scaling: llama3 divides long-wavelength freqs by the
    factor and keeps short ones; yarn interpolates low-frequency dims and
    extrapolates high-frequency ones; mscale follows 0.1*m*ln(s)+1."""
    import math

    from dynamo_tpu.ops.rope import rope_table, yarn_mscale

    head_dim, theta = 64, 500000.0
    base_cos, _ = rope_table(64, head_dim, theta)

    l3 = {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
          "high_freq_factor": 4.0, "original_max_position_embeddings": 8192}
    cos3, sin3 = rope_table(64, head_dim, theta, scaling=l3)
    # dim 0 is the highest frequency (shortest wavelength): unscaled
    np.testing.assert_allclose(cos3[:, 0], base_cos[:, 0], rtol=1e-6)
    # the last dim is lowest frequency: angle divided by exactly the factor
    # (small angles: assert via sin, which preserves them in float32)
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(half) / half))
    np.testing.assert_allclose(
        float(sin3[63, -1]), math.sin(63 * freqs[-1] / 8.0), rtol=1e-4
    )

    yarn = {"rope_type": "yarn", "factor": 4.0,
            "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale_all_dim": 1.0}
    m = 0.1 * math.log(4.0) + 1.0  # HF attention_factor baked into tables
    cosy, siny = rope_table(64, head_dim, theta, scaling=yarn)
    # highest-frequency dim extrapolates (angle unscaled, amplitude * m)
    np.testing.assert_allclose(cosy[:, 0], base_cos[:, 0] * m, rtol=1e-6)
    # lowest-frequency dim interpolates (angle / factor)
    np.testing.assert_allclose(
        float(siny[63, -1]), m * math.sin(63 * freqs[-1] / 4.0), rtol=1e-4
    )
    # DeepSeek convention: tables unscaled (temperature rides attn_scale)
    cosd, _ = rope_table(
        64, head_dim, theta, scaling=yarn, yarn_apply_attention_factor=False
    )
    np.testing.assert_allclose(cosd[:, 0], base_cos[:, 0], rtol=1e-6)
    assert abs(yarn_mscale(yarn) - (0.1 * math.log(4.0) + 1.0)) < 1e-9
    assert yarn_mscale(None) == 1.0
    assert yarn_mscale({"rope_type": "llama3"}) == 1.0


def test_paged_attention_fp8_cache():
    """fp8 (e4m3) KV pages through the Pallas kernel — the dtype TPU
    serving/bench defaults feed it (engine 'auto' → pallas + fp8 cache)."""
    rng = jax.random.PRNGKey(2)
    k_cache, v_cache, tables, ctx = build_cache(rng)
    fp8 = jnp.dtype("float8_e4m3fn")
    k8, v8 = k_cache.astype(fp8), v_cache.astype(fp8)
    q = jax.random.normal(jax.random.fold_in(rng, 9), (3, 4, 128), jnp.float32)

    ref = paged_decode_attention(q, k8, v8, tables, ctx)  # XLA path, fp8
    out = paged_attention_decode(q, k8, v8, tables, ctx, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
    # and the fp8 result tracks the full-precision one within e4m3 error
    exact = paged_decode_attention(q, k_cache, v_cache, tables, ctx)
    rel = np.linalg.norm(np.asarray(out) - np.asarray(exact)) / np.linalg.norm(
        np.asarray(exact)
    )
    assert rel < 0.08


def test_mla_paged_attention_fp8_cache():
    from dynamo_tpu.ops.pallas.mla_attention import mla_paged_attention_decode

    rng = np.random.default_rng(3)
    b, h, r, p, nb, bs, maxb = 2, 4, 32, 16, 8, 4, 3
    fp8 = jnp.dtype("float8_e4m3fn")
    ck = jnp.asarray(rng.standard_normal((nb, bs, r)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((nb, bs, p)), jnp.float32)
    q_lat = jnp.asarray(rng.standard_normal((b, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, h, p)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nb, (b, maxb)), jnp.int32)
    ctx = jnp.asarray([7, 10], jnp.int32)
    scale = 1.0 / np.sqrt(r + p)

    exact = mla_paged_attention_decode(
        q_lat, q_rope, ck, kr, tables, ctx, scale=scale, interpret=True
    )
    out = mla_paged_attention_decode(
        q_lat, q_rope, ck.astype(fp8), kr.astype(fp8), tables, ctx,
        scale=scale, interpret=True,
    )
    rel = np.linalg.norm(np.asarray(out) - np.asarray(exact)) / np.linalg.norm(
        np.asarray(exact)
    )
    assert rel < 0.1


def test_window_attention_kernel_matches_reference():
    """Speculative-verification multi-query kernel vs the pure-JAX twin."""
    from dynamo_tpu.ops.attention import paged_window_attention
    from dynamo_tpu.ops.pallas import paged_window_attention_decode

    rng = jax.random.PRNGKey(5)
    k_cache, v_cache, tables, ctx = build_cache(rng)
    w = 3
    # window's last token included in ctx (mirror the engine's convention)
    ctx_w = ctx + (w - 1)
    q = jax.random.normal(jax.random.fold_in(rng, 7), (3, w, 8, 128), jnp.float32)

    ref = paged_window_attention(q, k_cache, v_cache, tables, ctx_w)
    out = paged_window_attention_decode(
        q, k_cache, v_cache, tables, ctx_w, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_window_attention_kernel_fp8_cache():
    from dynamo_tpu.ops.attention import paged_window_attention
    from dynamo_tpu.ops.pallas import paged_window_attention_decode

    rng = jax.random.PRNGKey(6)
    k_cache, v_cache, tables, ctx = build_cache(rng)
    fp8 = jnp.dtype("float8_e4m3fn")
    q = jax.random.normal(jax.random.fold_in(rng, 8), (3, 2, 4, 128), jnp.float32)
    ctx_w = ctx + 1
    ref = paged_window_attention(q, k_cache.astype(fp8), v_cache.astype(fp8), tables, ctx_w)
    out = paged_window_attention_decode(
        q, k_cache.astype(fp8), v_cache.astype(fp8), tables, ctx_w, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_mla_window_attention_kernel_matches_reference():
    from dynamo_tpu.ops.pallas.mla_attention import (
        mla_paged_attention_decode,
        mla_paged_window_attention_decode,
    )

    rng = np.random.default_rng(9)
    b, w, h, r, p, nb, bs, maxb = 2, 3, 4, 32, 16, 8, 4, 3
    ck = jnp.asarray(rng.standard_normal((nb, bs, r)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((nb, bs, p)), jnp.float32)
    q_lat = jnp.asarray(rng.standard_normal((b, w, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, w, h, p)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nb, (b, maxb)), jnp.int32)
    ctx_w = jnp.asarray([9, 6], jnp.int32)  # including window's last token
    scale = 1.0 / np.sqrt(r + p)

    out = mla_paged_window_attention_decode(
        q_lat, q_rope, ck, kr, tables, ctx_w, scale=scale, interpret=True
    )
    # each window position must equal a single-query call at that length
    for i in range(w):
        ref = mla_paged_attention_decode(
            q_lat[:, i], q_rope[:, i], ck, kr, tables, ctx_w - (w - 1 - i),
            scale=scale, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out[:, i]), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def ragged_meta(spans, lanes, tb=8, t_pad=None):
    """Pack (lane, start_pos, q_len) spans DENSELY into the ragged
    per-token metadata the unified kernel consumes: spans and decode
    tokens share token blocks (packed lanes); only the flat axis tail
    pads to whole blocks, with fully-masked rows."""
    total = sum(l for _, _, l in spans)
    t_pad = t_pad or -(-total // tb) * tb
    token_lane = np.full((t_pad,), lanes, np.int32)
    token_pos = np.full((t_pad,), -1, np.int32)
    ctx = np.zeros((lanes,), np.int32)
    cur = 0
    for lane, start, l in spans:
        token_lane[cur : cur + l] = lane
        token_pos[cur : cur + l] = np.arange(start, start + l)
        ctx[lane] = start + l
        cur += l
    return (
        jnp.asarray(token_lane), jnp.asarray(token_pos), jnp.asarray(ctx)
    )


def span_args(token_lane, token_pos, tables, tb, bs, sliding_window=None):
    """The ragged kernels' routing arguments after q and the caches:
    per-token lane/pos, the block tables and pack_spans' four arrays."""
    from dynamo_tpu.ops.pallas import pack_spans

    meta = pack_spans(
        token_lane, token_pos, lanes=tables.shape[0], tb_tokens=tb,
        block_size=bs, sliding_window=sliding_window,
    )
    return (
        jnp.asarray(token_lane), jnp.asarray(token_pos), jnp.asarray(tables),
        *(jnp.asarray(a) for a in meta),
    )


def run_ragged(spans, q_key=9, lanes=3, tb=8, t_pad=None, sliding_window=None,
               cache_dtype=None):
    """Kernel + pure-JAX twin over the shared test cache; returns
    (kernel_out, ref_out, token_pos host array, q)."""
    from dynamo_tpu.ops.attention import ragged_paged_attention as ragged_ref
    from dynamo_tpu.ops.pallas import ragged_paged_attention as ragged_kernel

    rng = jax.random.PRNGKey(0)
    k_cache, v_cache, tables, _ = build_cache(rng)
    if cache_dtype is not None:
        k_cache = k_cache.astype(cache_dtype)
        v_cache = v_cache.astype(cache_dtype)
    token_lane, token_pos, ctx = ragged_meta(spans, lanes, tb=tb, t_pad=t_pad)
    t = token_lane.shape[0]
    q = jax.random.normal(jax.random.fold_in(rng, q_key), (t, 4, 128), jnp.float32)
    ref = ragged_ref(
        q, k_cache, v_cache, tables, ctx, token_lane, token_pos,
        sliding_window=sliding_window,
    )
    out = ragged_kernel(
        q, k_cache, v_cache,
        *span_args(token_lane, token_pos, tables, tb, k_cache.shape[1],
                   sliding_window),
        tb_tokens=tb, interpret=True, sliding_window=sliding_window,
    )
    return np.asarray(out), np.asarray(ref), np.asarray(token_pos), q


def test_ragged_attention_decode_only_matches_decode_kernel():
    """A decode-only ragged batch (one token per lane) must equal both the
    pure-JAX twin and the plain paged decode path row-for-row — and with
    packed lanes all three decode tokens share ONE token block."""
    spans = [(0, 4, 1), (1, 16, 1), (2, 28, 1)]
    out, ref, token_pos, q = run_ragged(spans)
    assert out.shape[0] == 8  # 3 lanes packed into a single 8-token block
    valid = token_pos >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)
    rng = jax.random.PRNGKey(0)
    k_cache, v_cache, tables, _ = build_cache(rng)
    rows = np.asarray([0, 1, 2])
    dec = paged_decode_attention(
        q[jnp.asarray(rows)], k_cache, v_cache, tables,
        jnp.asarray([5, 17, 29], jnp.int32),
    )
    np.testing.assert_allclose(out[rows], np.asarray(dec), rtol=2e-5, atol=2e-5)


def test_ragged_attention_prefill_span_matches_reference():
    """A prefill-only ragged batch: one 13-token span attending its own
    in-cache prefix causally (positions 16..28 of lane 2's 29-long ctx)."""
    out, ref, token_pos, _ = run_ragged([(2, 16, 13)])
    valid = token_pos >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)


def test_ragged_attention_mixed_and_single_token_tail():
    """Mixed batch: decode token + a mid-prompt chunk + a single-token
    prefill tail (span length 1 — the chunk-boundary edge case)."""
    spans = [(0, 4, 1), (1, 8, 9), (2, 28, 1)]
    out, ref, token_pos, _ = run_ragged(spans)
    valid = token_pos >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)


def test_ragged_attention_lane_holes_and_padding():
    """Lane 1 is a hole (contributes no tokens) and the token axis pads
    past the spans: every live row still matches, junk rows stay
    NaN-free."""
    spans = [(0, 4, 1), (2, 20, 9)]
    out, ref, token_pos, _ = run_ragged(spans, t_pad=32)
    valid = token_pos >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)
    assert np.isfinite(out).all()


def test_ragged_attention_single_lane_degenerate():
    """A single lane owning the whole window (the degenerate packing) is
    just chunked prefill — packed metadata must not perturb it."""
    out, ref, token_pos, _ = run_ragged([(1, 0, 17)])
    valid = token_pos >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)


def test_ragged_attention_packed_block_reduction_16_lanes():
    """The acceptance geometry: a 16-lane decode-heavy window.  Packed
    lanes fit it in ceil(16/8) = 2 kernel token blocks — >= 4x fewer than
    the one-lane-per-block layout's 16 — while every row still matches
    the twin byte-for-row."""
    from dynamo_tpu.ops.attention import (
        ragged_paged_attention as ragged_ref,
        write_prefill_kv,
    )
    from dynamo_tpu.ops.pallas import ragged_paged_attention as ragged_kernel

    lanes, bs, kvh, d, maxb, tb = 16, 8, 2, 128, 4, 8
    rng = jax.random.PRNGKey(3)
    keys = jax.random.split(rng, 3)
    k_cache = jnp.zeros((lanes * maxb, bs, kvh, d), jnp.float32)
    v_cache = jnp.zeros((lanes * maxb, bs, kvh, d), jnp.float32)
    tables = jnp.arange(lanes * maxb, dtype=jnp.int32).reshape(lanes, maxb)
    ctx = [(5 + 3 * i) % (maxb * bs - 1) + 1 for i in range(lanes)]
    for i in range(lanes):
        k_seq = jax.random.normal(jax.random.fold_in(keys[0], i), (maxb * bs, kvh, d))
        v_seq = jax.random.normal(jax.random.fold_in(keys[1], i), (maxb * bs, kvh, d))
        k_cache, v_cache = write_prefill_kv(
            k_cache, v_cache, k_seq, v_seq, tables[i], jnp.int32(ctx[i])
        )
    spans = [(i, ctx[i] - 1, 1) for i in range(lanes)]
    token_lane, token_pos, ctx_a = ragged_meta(spans, lanes, tb=tb)
    packed_blocks = token_lane.shape[0] // tb
    padded_blocks = lanes  # one-lane-per-block: every decode lane = 1 block
    assert packed_blocks * 4 <= padded_blocks
    q = jax.random.normal(keys[2], (token_lane.shape[0], 4, d), jnp.float32)
    ref = ragged_ref(q, k_cache, v_cache, tables, ctx_a, token_lane, token_pos)
    out = ragged_kernel(
        q, k_cache, v_cache,
        *span_args(token_lane, token_pos, tables, tb, bs),
        tb_tokens=tb, interpret=True,
    )
    valid = np.asarray(token_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(ref)[valid], rtol=2e-5, atol=2e-5
    )


def test_pack_spans_lists_each_lane_once_per_block():
    """One span per lane present in a token block, first-appearance order,
    live spans first; a lane's pages run from the window's floor to its
    highest position's page; empty blocks total zero."""
    from dynamo_tpu.ops.pallas import pack_spans

    token_lane = np.asarray([0, 1, 0, 3, 3, 3, 3, 3], np.int32)
    token_pos = np.asarray([9, 0, 17, -1, -1, -1, -1, -1], np.int32)
    lane, first, count, total = pack_spans(
        token_lane, token_pos, lanes=3, tb_tokens=4, block_size=8,
        pages_per_step=1,
    )
    # block 0: lane 0 sees pages 0..2 (pos 9 and 17), lane 1 page 0; the
    # out-of-range lane 3 is padding
    assert lane.tolist() == [0, 1, -1, -1, -1, -1, -1, -1]
    assert first.tolist() == [0, 0, 0, 0, 0, 0, 0, 0]
    assert count.tolist() == [3, 1, 0, 0, 0, 0, 0, 0]
    assert total.tolist() == [4, 0]
    # KV steps of two pages: lane 0's three pages take two, lane 1's one;
    # at the kernels' own width (16 pages of 8 positions) one step a span
    for pages, steps in ((2, [3, 0]), (None, [2, 0])):
        assert pack_spans(
            token_lane, token_pos, lanes=3, tb_tokens=4, block_size=8,
            pages_per_step=pages,
        )[3].tolist() == steps
    # a window of 8 positions: lane 0's lowest token (pos 9) sees from
    # position 2, so its span still starts at page 0; a window of 2 drops it
    _, first, count, total = pack_spans(
        token_lane, token_pos, lanes=3, tb_tokens=4, block_size=8,
        sliding_window=2, pages_per_step=1,
    )
    assert (first[:2].tolist(), count[:2].tolist()) == ([1, 0], [2, 1])
    assert total.tolist() == [3, 0]
    with pytest.raises(ValueError, match="whole token blocks"):
        pack_spans(token_lane[:6], token_pos[:6], lanes=3, tb_tokens=4,
                   block_size=8)


def _pack_spans_loop(token_lane, token_pos, lanes, tb, bs, window, pages=1):
    """pack_spans as the plain loop it replaced (per block a dict by lane,
    insertion-ordered): the reference for the vectorised packer."""
    t_pad = len(token_lane)
    lane_o = np.full(t_pad, -1, np.int32)
    first_o = np.zeros(t_pad, np.int32)
    count_o = np.zeros(t_pad, np.int32)
    total = np.zeros(t_pad // tb, np.int32)
    for t in range(t_pad // tb):
        span = {}
        for i in range(t * tb, (t + 1) * tb):
            lane, pos = int(token_lane[i]), int(token_pos[i])
            if pos < 0 or not 0 <= lane < lanes:
                continue
            lo, hi = span.get(lane, (pos, pos))
            span[lane] = (min(lo, pos), max(hi, pos))
        for s, (lane, (lo, hi)) in enumerate(span.items()):
            first = 0 if window is None else max(0, lo - (window - 1)) // bs
            lane_o[t * tb + s] = lane
            first_o[t * tb + s] = first
            count_o[t * tb + s] = hi // bs + 1 - first
            total[t] += -(-(hi // bs + 1 - first) // pages)
    return lane_o, first_o, count_o, total


@pytest.mark.parametrize("pages", [1, 3])
@pytest.mark.parametrize("window", [None, 5, 40])
@pytest.mark.parametrize("tb", [1, 4, 8, 32])
def test_pack_spans_equals_the_plain_loop(tb, window, pages):
    """Random windows — interleaved lanes, holes, pad rows, out-of-range
    lanes — pack to exactly what the loop packs, in KV steps of one page
    and of three."""
    from dynamo_tpu.ops.pallas import pack_spans

    rng = np.random.default_rng(tb * 100 + (window or 0))
    for _ in range(20):
        t_pad, lanes, bs = tb * int(rng.integers(1, 9)), 5, 4
        token_lane = rng.integers(-1, lanes + 2, t_pad).astype(np.int32)
        token_pos = rng.integers(-1, 70, t_pad).astype(np.int32)
        got = pack_spans(token_lane, token_pos, lanes=lanes, tb_tokens=tb,
                         block_size=bs, sliding_window=window,
                         pages_per_step=pages)
        want = _pack_spans_loop(token_lane, token_pos, lanes, tb, bs, window,
                                pages)
        for g, w in zip(got, want):
            assert g.dtype == np.int32 and g.tolist() == w.tolist()


def test_ragged_mla_attention_matches_dense_reference():
    """Packed-lane ragged MLA kernel vs a dense latent-space per-token
    reference: mixed span + decode tokens against the latent cache, causal
    per-row masks, pad rows finite."""
    from dynamo_tpu.ops.pallas import ragged_mla_attention

    rng = jax.random.PRNGKey(5)
    h, r, p, bs, maxb, nblocks = 4, 32, 16, 8, 4, 16
    keys = jax.random.split(rng, 4)
    ck = jax.random.normal(keys[2], (nblocks, bs, r), jnp.float32)
    kr = jax.random.normal(keys[3], (nblocks, bs, p), jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], jnp.int32)
    scale = 0.17
    spans = [(0, 2, 3), (1, 16, 1), (2, 24, 5)]
    token_lane, token_pos, _ = ragged_meta(spans, 3)
    t = token_lane.shape[0]
    q_lat = jax.random.normal(keys[0], (t, h, r), jnp.float32)
    q_rope = jax.random.normal(keys[1], (t, h, p), jnp.float32)
    out = np.asarray(ragged_mla_attention(
        q_lat, q_rope, ck, kr, *span_args(token_lane, token_pos, tables, 8, bs),
        scale=scale, tb_tokens=8, interpret=True,
    ))
    assert np.isfinite(out).all()
    length = maxb * bs
    tl, tp = np.asarray(token_lane), np.asarray(token_pos)
    tab = np.asarray(tables)
    for i in range(t):
        if tp[i] < 0:
            continue
        ck_g = np.asarray(ck)[tab[tl[i]]].reshape(length, r)
        kr_g = np.asarray(kr)[tab[tl[i]]].reshape(length, p)
        logits = (
            np.asarray(q_lat)[i] @ ck_g.T + np.asarray(q_rope)[i] @ kr_g.T
        ) * scale
        logits = np.where(np.arange(length)[None, :] <= tp[i], logits, -1e30)
        w = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        np.testing.assert_allclose(out[i], w @ ck_g, rtol=2e-5, atol=2e-5)


def test_ragged_attention_chunked_gather_matches_direct():
    """The fallback's bounded-memory token-chunk path (max_gather_tokens
    exceeded → lax.map over chunks) is numerically identical to the direct
    gather, including a chunk boundary that splits a span."""
    from dynamo_tpu.ops.attention import ragged_paged_attention as ragged_ref

    spans = [(0, 4, 1), (1, 8, 9), (2, 28, 1)]
    rng = jax.random.PRNGKey(0)
    k_cache, v_cache, tables, _ = build_cache(rng)
    token_lane, token_pos, ctx = ragged_meta(spans, 3)
    t = token_lane.shape[0]
    q = jax.random.normal(jax.random.fold_in(rng, 13), (t, 4, 128), jnp.float32)
    direct = ragged_ref(
        q, k_cache, v_cache, tables, ctx, token_lane, token_pos,
        max_gather_tokens=4096,
    )
    chunked = ragged_ref(
        q, k_cache, v_cache, tables, ctx, token_lane, token_pos,
        max_gather_tokens=8,
    )
    np.testing.assert_allclose(
        np.asarray(chunked), np.asarray(direct), rtol=2e-6, atol=2e-6
    )


def test_ragged_attention_sliding_window_matches_fallback():
    """Packed kernel with a sliding window must match the windowed XLA twin;
    page pruning (pack_spans starts a span at the window's floor) must
    not change the result."""
    spans = [(0, 4, 1), (1, 8, 9), (2, 28, 1)]
    for w in (4, 16):
        out, ref, token_pos, _ = run_ragged(spans, q_key=11, sliding_window=w)
        valid = np.asarray(token_pos) >= 0
        np.testing.assert_allclose(
            np.asarray(out)[valid], np.asarray(ref)[valid],
            rtol=2e-5, atol=2e-5,
        )


def test_paged_attention_sliding_window_matches_fallback():
    """Pallas decode kernel with a sliding window (interpret mode) must
    match the XLA gather fallback's windowed mask exactly."""
    rng = np.random.default_rng(11)
    k = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, 8, (2, 4)), jnp.int32)
    ctx = jnp.asarray([29, 13], jnp.int32)
    for w in (4, 16):
        out = np.asarray(paged_attention_decode(
            q, k, v, tables, ctx, interpret=True, sliding_window=w,
        ))
        ref = np.asarray(paged_decode_attention(
            q, k, v, tables, ctx, sliding_window=w,
        ))
        rel = np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-9)
        assert rel < 1e-5, (w, rel)
    # and the windowed result must differ from full attention (mask live)
    full = np.asarray(paged_decode_attention(q, k, v, tables, ctx))
    win = np.asarray(paged_decode_attention(q, k, v, tables, ctx, sliding_window=4))
    assert not np.allclose(full, win)


def test_paged_attention_pages_per_step_parity():
    """Decode kernel: clamped multi-page grid steps match pps=1 exactly,
    including pps values that do not divide (or exceed) max_blocks."""
    rng = jax.random.PRNGKey(0)
    k_cache, v_cache, tables, ctx = build_cache(rng)
    q = jax.random.normal(jax.random.fold_in(rng, 7), (3, 4, 128), jnp.float32)
    base = np.asarray(paged_attention_decode(
        q, k_cache, v_cache, tables, ctx, interpret=True
    ))
    for pps in (3, 8):
        out = np.asarray(paged_attention_decode(
            q, k_cache, v_cache, tables, ctx, interpret=True,
            pages_per_step=pps,
        ))
        np.testing.assert_array_equal(out, base)


def test_mla_attention_pages_per_step_parity():
    """MLA decode kernel under pages_per_step matches its default (a KV
    step's pages are ONE online-softmax update, so a different step width
    regroups float32 sums: equal to rounding, not to the bit); the ragged
    MLA kernel matches its twin on a mixed chunk + decode window."""
    from dynamo_tpu.ops.attention import ragged_mla_paged_attention
    from dynamo_tpu.ops.pallas import ragged_mla_attention
    from dynamo_tpu.ops.pallas.mla_attention import mla_paged_attention_decode

    rng = np.random.default_rng(5)
    nb, bs, R, P, h, maxb = 12, 8, 128, 64, 4, 4
    ck = jnp.asarray(rng.standard_normal((nb, bs, R)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((nb, bs, P)), jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], jnp.int32)
    ctx = jnp.asarray([5, 17, 29], jnp.int32)
    scale = 1.0 / np.sqrt(R + P)
    q_lat = jnp.asarray(rng.standard_normal((3, h, R)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((3, h, P)), jnp.float32)
    base = np.asarray(mla_paged_attention_decode(
        q_lat, q_rope, ck, kr, tables, ctx, scale=scale, interpret=True
    ))
    for pps in (1, 2, 3):
        out = np.asarray(mla_paged_attention_decode(
            q_lat, q_rope, ck, kr, tables, ctx, scale=scale, interpret=True,
            pages_per_step=pps,
        ))
        np.testing.assert_allclose(out, base, rtol=2e-5, atol=2e-6)

    # ragged MLA: mixed chunk + decode spans
    lanes, tb = 3, 8
    token_lane, token_pos, _ = ragged_meta(
        [(0, 4, 1), (1, 8, 9), (2, 28, 1)], lanes, tb=tb
    )
    t = token_lane.shape[0]
    ql = jnp.asarray(rng.standard_normal((t, h, R)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((t, h, P)), jnp.float32)
    rbase = np.asarray(ragged_mla_attention(
        ql, qr, ck, kr, *span_args(token_lane, token_pos, tables, tb, bs),
        scale=scale, tb_tokens=tb, interpret=True,
    ))
    valid = np.asarray(token_pos) >= 0
    rref = np.asarray(ragged_mla_paged_attention(
        ql, qr, ck, kr, tables, token_lane, token_pos, scale=scale,
    ))
    np.testing.assert_allclose(rbase[valid], rref[valid], rtol=2e-5, atol=2e-5)


def test_ragged_attention_fp8_cache():
    """fp8 KV read inside the packed ragged kernel: the kernel upcasts
    page reads to f32, so it must agree with the XLA twin reading the SAME
    fp8 cache (tight tolerance — identical quantized inputs), and sit
    within quantization error of the f32 result."""
    fp8 = jnp.float8_e4m3fn
    spans = [(0, 4, 1), (1, 8, 9), (2, 28, 1)]
    out8, ref8, token_pos, _ = run_ragged(spans, cache_dtype=fp8)
    valid = token_pos >= 0
    np.testing.assert_allclose(out8[valid], ref8[valid], rtol=2e-5, atol=2e-5)
    out32, _, _, _ = run_ragged(spans)
    rel = np.linalg.norm(out8[valid] - out32[valid]) / max(
        np.linalg.norm(out32[valid]), 1e-9
    )
    assert 0 < rel < 0.12, rel  # quantized but sane


def test_ragged_mla_attention_fp8_cache():
    """fp8 latent+rope cache through the ragged MLA kernel vs its twin."""
    from dynamo_tpu.ops.attention import ragged_mla_paged_attention
    from dynamo_tpu.ops.pallas import ragged_mla_attention

    fp8 = jnp.float8_e4m3fn
    rng = np.random.default_rng(6)
    nb, bs, R, P, h = 12, 8, 128, 64, 4
    ck = jnp.asarray(rng.standard_normal((nb, bs, R)), jnp.float32).astype(fp8)
    kr = jnp.asarray(rng.standard_normal((nb, bs, P)), jnp.float32).astype(fp8)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], jnp.int32)
    lanes, tb = 3, 8
    token_lane, token_pos, _ = ragged_meta(
        [(0, 4, 1), (1, 8, 9), (2, 28, 1)], lanes, tb=tb
    )
    t = token_lane.shape[0]
    scale = 1.0 / np.sqrt(R + P)
    ql = jnp.asarray(rng.standard_normal((t, h, R)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((t, h, P)), jnp.float32)
    out = np.asarray(ragged_mla_attention(
        ql, qr, ck, kr, *span_args(token_lane, token_pos, tables, tb, bs),
        scale=scale, tb_tokens=tb, interpret=True,
    ))
    ref = np.asarray(ragged_mla_paged_attention(
        ql, qr, ck, kr, tables, token_lane, token_pos, scale=scale,
    ))
    valid = np.asarray(token_pos) >= 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)


# The benchmark cells' attention geometry (Qwen3-4B, Mistral-7B: 32 query
# heads over 8 KV heads of 128, pages of 16) at the token blocks the engine
# packs there (64 tokens; 32 for the 32-token bucket) and at the small ones
# PR 28's kernel ran (8, 4).
CELL_WINDOWS = {
    # name: (spans [(lane, start, len)], t_pad, sliding_window, cache dtype)
    "decode_lanes_only": ([(i, 11 + 29 * i, 1) for i in range(8)], None, None, None),
    "one_long_span": ([(2, 0, 150)], None, None, None),
    "span_beside_8_packed_decodes": (
        [(i, 20 + 27 * i, 1) for i in range(8)] + [(8, 64, 40)], None, None, None,
    ),
    # 23 pages under the span's last token: two KV steps of 16 pages, the
    # second partly filled, and decode lanes of 18 and 2 pages beside it
    "span_across_kv_steps_beside_decodes": (
        [(0, 280, 1), (1, 20, 1), (8, 300, 60)], None, None, None,
    ),
    "lane_holes_and_padding_rows": ([(0, 36, 1), (3, 90, 1), (6, 17, 21)], 64, None, None),
    "sliding_window_that_cuts": (
        [(1, 200, 1), (4, 130, 1), (5, 100, 30)], None, 48, None,
    ),
    "fp8_kv": ([(0, 77, 1), (1, 8, 25), (2, 140, 1)], None, None, jnp.float8_e4m3fn),
}


def _cell_window(window, tb, q_dtype, h=32, kvh=8):
    """Kernel and XLA twin over one of CELL_WINDOWS at d128, block 16;
    returns (kernel out, twin out over float32 copies of the same operands,
    live-row mask, the routing arguments)."""
    from dynamo_tpu.ops.attention import ragged_paged_attention as ragged_ref
    from dynamo_tpu.ops.pallas import ragged_paged_attention as ragged_kernel

    d, bs, lanes, maxb, nblocks = 128, 16, 9, 24, 40
    spans, t_pad, sw, cache_dtype = CELL_WINDOWS[window]
    rng = np.random.default_rng(17)
    k_cache = jnp.asarray(rng.standard_normal((nblocks, bs, kvh, d)), q_dtype)
    v_cache = jnp.asarray(rng.standard_normal((nblocks, bs, kvh, d)), q_dtype)
    if cache_dtype is not None:
        k_cache, v_cache = k_cache.astype(cache_dtype), v_cache.astype(cache_dtype)
    tables = jnp.asarray(rng.integers(0, nblocks, (lanes, maxb)), jnp.int32)
    token_lane, token_pos, ctx = ragged_meta(spans, lanes, tb=tb, t_pad=t_pad)
    q = jnp.asarray(rng.standard_normal((token_lane.shape[0], h, d)), q_dtype)
    args = span_args(token_lane, token_pos, tables, tb, bs, sw)
    out = ragged_kernel(
        q, k_cache, v_cache, *args, tb_tokens=tb, interpret=True,
        sliding_window=sw,
    )
    assert out.dtype == q_dtype
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    ref = ragged_ref(
        f32(q), f32(k_cache), f32(v_cache), tables, ctx, token_lane, token_pos,
        sliding_window=sw,
    )
    return np.asarray(f32(out)), np.asarray(ref), np.asarray(token_pos) >= 0, args


@pytest.mark.parametrize("tb", [4, 8, 32, 64])
@pytest.mark.parametrize("window", sorted(CELL_WINDOWS))
def test_ragged_attention_cell_geometry_matches_twin(window, tb):
    """The kernel against the XLA twin at h32 kv8 d128, block 16, float32
    operands (fp8 pages widen exactly): every live row agrees to float32's
    rounding, pad rows are finite, the pages it copies are exactly the pages
    the window's lanes can see, and each span's pages take ceil(pages / 16)
    KV steps."""
    out, ref, valid, args = _cell_window(window, tb, jnp.float32)
    np.testing.assert_allclose(out[valid], ref[valid], rtol=2e-5, atol=2e-5)
    assert np.isfinite(out).all()
    # hand count: per token block, per lane in it, the pages from the
    # window's floor under its lowest token to its highest token's page
    _, _, sw, _ = CELL_WINDOWS[window]
    tl, tp = np.asarray(args[0]), np.asarray(args[1])
    want = steps = 0
    for t in range(0, len(tl), tb):
        for lane in {int(x) for x, p in zip(tl[t:t + tb], tp[t:t + tb]) if p >= 0}:
            pos = [p for x, p in zip(tl[t:t + tb], tp[t:t + tb]) if x == lane and p >= 0]
            lo = 0 if sw is None else max(0, min(pos) - sw + 1) // 16
            want += max(pos) // 16 + 1 - lo
            steps += -(-(max(pos) // 16 + 1 - lo) // 16)
    assert int(np.asarray(args[-2]).sum()) == want
    assert int(np.asarray(args[-1]).sum()) == steps


# bfloat16 operands, as every step program hands them over: the products are
# the twin's (a bf16 x bf16 product is exact in float32), so what differs is
# the one rounding the kernel adds (the probabilities to bf16 before P.V, a
# relative 2^-9 on each, against sum(p |v|) <= max |v| < 4.6 here) and the
# output's own rounding to bf16 (half an ulp of 2^-6 under 4): 0.009 + 0.008
BF16_ATOL = 0.02


@pytest.mark.parametrize("tb", [32, 64])
@pytest.mark.parametrize("window", sorted(CELL_WINDOWS))
def test_ragged_attention_cell_geometry_in_bfloat16(window, tb):
    """The same windows with bfloat16 queries (and pages, but the fp8 case):
    the per-head rows are read out of bf16 pages as uint32 words, and the
    MXU is fed bf16."""
    out, ref, valid, _ = _cell_window(window, tb, jnp.bfloat16)
    np.testing.assert_allclose(out[valid], ref[valid], rtol=0, atol=BF16_ATOL)
    assert np.isfinite(out).all()
    # the rounding is there: further than float32's 2e-5 from the twin
    assert np.abs(out[valid] - ref[valid]).max() > 2e-4


@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kvh", [(8, 8), (16, 2), (3, 1), (9, 3)])
def test_ragged_attention_group_sizes(h, kvh, q_dtype):
    """No grouping (G = 1), eight query heads a KV head (G = 8), one KV
    head (no strided read at all) and an odd count of them (bf16 pages that
    cannot be read as uint32 pairs go through a float32 copy)."""
    out, ref, valid, _ = _cell_window(
        "span_beside_8_packed_decodes", 32, q_dtype, h=h, kvh=kvh)
    atol = 2e-5 if q_dtype == jnp.float32 else BF16_ATOL
    np.testing.assert_allclose(out[valid], ref[valid], rtol=0, atol=atol)
    assert np.isfinite(out).all()


# --- the unified step's two-part latent attention (PR 52): the window's own
# keys decompressed (ragged_mla_attention_window), the resident pages absorbed
# (ragged_mla_attention told each lane's last resident position), one softmax


def _dense_window_reference(qn, qr, kn, kr, v, lane, pos, scale):
    """float32, dense: row i attends the live rows of its lane at flat index
    <= i.  Returns (out [t, H, v], lse [t, H]; a pad: zeros, -inf)."""
    lane, pos = np.asarray(lane), np.asarray(pos)
    t = lane.shape[0]
    idx = np.arange(t)
    live = pos >= 0
    mask = ((lane[:, None] == lane[None, :]) & (idx[None, :] <= idx[:, None])
            & live[:, None] & live[None, :])
    s = (jnp.einsum("thn,shn->hts", qn, kn) + jnp.einsum("thp,sp->hts", qr, kr)) * scale
    s = jnp.where(mask[None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    w = jnp.where(mask[None], jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    return np.asarray(jnp.einsum("hts,shv->thv", w, v)), np.asarray(lse.T)


@pytest.mark.parametrize("block,t_pad", [(512, 40), (128, 384), (128, 640)],
                         ids=["one_block", "three_blocks", "five_blocks"])
def test_mla_window_launch_matches_a_dense_float32_reference(block, t_pad):
    """The flash launch over a window's own keys at a 192/128-shaped head
    (128 + a 64-wide rotated part stored 128 wide, values 128), a mixed
    batch as the engine packs it: three decode rows, a span that continues
    at position 5, a whole prompt from 0, pads behind.  One block of rows,
    and three blocks of 128 (the second span crosses a block edge; the first
    key block a query block visits comes from its earliest lane's first
    row), and five (the whole prompt, 450 rows, fills two blocks alone)."""
    from dynamo_tpu.ops.pallas.mla_attention import NEG_INF, ragged_mla_attention_window

    lanes, h, n, p, vd = 6, 2, 128, 128, 128
    long = t_pad > 128
    spans = [(0, 40, 1), (1, 7, 1), (2, 0, 1), (3, 5, 150 if long else 20),
             (4, 0, {40: 13, 384: 200, 640: 450}[t_pad])]
    token_lane, token_pos, _ = ragged_meta(spans, lanes, t_pad=t_pad)
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    qn, kn = (jax.random.normal(k, (t_pad, h, n), jnp.float32) for k in keys[:2])
    v = jax.random.normal(keys[2], (t_pad, h, vd), jnp.float32)
    # the rotated parts: 64 live lanes, zeros behind (the page's layout)
    qr = jnp.pad(jax.random.normal(keys[3], (t_pad, h, 64), jnp.float32), ((0, 0), (0, 0), (0, 64)))
    kr = jnp.pad(jax.random.normal(keys[4], (t_pad, 64), jnp.float32), ((0, 0), (0, 64)))
    out, lse = ragged_mla_attention_window(
        qn, qr, kn, kr, v, token_lane, token_pos, lanes=lanes, scale=0.07,
        interpret=True, block=block)
    out = np.asarray(out).reshape(t_pad, h, vd)     # [T, H x V], head-major columns
    want, want_lse = _dense_window_reference(qn, qr, kn, kr, v, token_lane, token_pos, 0.07)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    live = np.asarray(token_pos) >= 0
    np.testing.assert_allclose(np.asarray(lse)[live], want_lse[live], rtol=2e-5, atol=2e-5)
    assert (out[~live] == 0).all() and (np.asarray(lse)[~live] == NEG_INF).all()
    # a decode row is a window of one: its own value, its own score
    np.testing.assert_allclose(out[0], np.asarray(v)[0], rtol=1e-6, atol=1e-6)


def _latent_batch():
    """A unified window with every kind of row of the contract over a small
    latent cache (pages of 8, lane i's pages 4 i .. 4 i + 3): lane 0 a decode
    row at position 20; lane 1 a whole 11-token prompt; lane 2 a chunk of 9
    that continues a resident prefix of 13 (which ends mid-page); lane 3
    EMPTY (pages, no row); pads behind.  Returns everything both routes
    need and the one-piece absorbed result."""
    from dynamo_tpu.ops.attention import ragged_mla_paged_attention
    from dynamo_tpu.ops.pallas.mla_attention import last_resident_pos

    h, r, p, n, vd, bs, lanes = 4, 32, 128, 16, 16, 8, 4
    spans = [(0, 20, 1), (1, 0, 11), (2, 13, 9)]
    token_lane, token_pos, _ = ragged_meta(spans, lanes, t_pad=24)
    lane, pos = np.asarray(token_lane), np.asarray(token_pos)
    t = lane.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(23), 6)
    tables = jnp.arange(lanes * 4, dtype=jnp.int32).reshape(lanes, 4)
    ck = jax.random.normal(keys[0], (lanes * 4, bs, r), jnp.float32)
    kr = jnp.pad(jax.random.normal(keys[1], (lanes * 4, bs, 8), jnp.float32),
                 ((0, 0), (0, 0), (0, p - 8)))
    w_uk = jax.random.normal(keys[2], (r, h, n), jnp.float32) / np.sqrt(r)
    w_uv = jax.random.normal(keys[3], (r, h, vd), jnp.float32) / np.sqrt(r)
    q_nope = jax.random.normal(keys[4], (t, h, n), jnp.float32)
    q_rope = jnp.pad(jax.random.normal(keys[5], (t, h, 8), jnp.float32), ((0, 0), (0, 0), (0, p - 8)))
    q_lat = jnp.einsum("thn,rhn->thr", q_nope, w_uk)
    # the rows' own latents: what the step wrote at their slots before attention
    live = pos >= 0
    page = np.asarray(tables)[np.clip(lane, 0, lanes - 1), np.maximum(pos, 0) // bs]
    c_kv = jnp.where(live[:, None], ck[page, np.maximum(pos, 0) % bs], 0.0)
    k_rope = jnp.where(live[:, None], kr[page, np.maximum(pos, 0) % bs], 0.0)
    scale = 0.2
    ctx = ragged_mla_paged_attention(q_lat, q_rope, ck, kr, tables, token_lane, token_pos, scale=scale)
    want = np.asarray(jnp.einsum("thr,rhv->thv", ctx, w_uv))
    resident = last_resident_pos(lane, pos, lanes)
    return dict(
        lanes=lanes, bs=bs, scale=scale, lane=lane, pos=pos, resident=resident, tables=tables,
        ck=ck, kr=kr, w_uk=w_uk, w_uv=w_uv, q_nope=q_nope, q_rope=q_rope, q_lat=q_lat,
        c_kv=c_kv, k_rope=k_rope, want=want)


@functools.cache
def _two_parts():
    """``_latent_batch`` through the two launches and the merge."""
    from dynamo_tpu.models.deepseek import _merge_parts
    from dynamo_tpu.ops.pallas.mla_attention import (
        ragged_mla_attention,
        ragged_mla_attention_window,
    )

    b = _latent_batch()
    ctx, lse_resident = ragged_mla_attention(
        b["q_lat"], b["q_rope"], b["ck"], b["kr"],
        *span_args(b["lane"], b["resident"], b["tables"], 8, b["bs"]),
        scale=b["scale"], tb_tokens=8, interpret=True, with_lse=True)
    out, lse_window = ragged_mla_attention_window(
        b["q_nope"], b["q_rope"], jnp.einsum("tr,rhn->thn", b["c_kv"], b["w_uk"]), b["k_rope"],
        jnp.einsum("tr,rhv->thv", b["c_kv"], b["w_uv"]), jnp.asarray(b["lane"]),
        jnp.asarray(b["pos"]), lanes=b["lanes"], scale=b["scale"], interpret=True)
    merged = _merge_parts(ctx, lse_resident, out, lse_window, b["w_uv"])
    by_head = lambda a: a.reshape(a.shape[0], 4, -1)  # noqa: E731 — [T, H x v] -> [T, H, v]
    return b, {k: np.asarray(a) for k, a in dict(
        ctx=ctx, lse_resident=lse_resident, out=by_head(out), lse_window=lse_window,
        merged=by_head(merged)).items()}


@pytest.mark.parametrize("kind,rows,has_resident,has_window", [
    ("decode_row", slice(0, 1), True, True),
    ("whole_prompt", slice(1, 12), False, True),
    ("chunk_after_a_prefix_that_ends_mid_page", slice(12, 21), True, True),
    ("pad", slice(21, 24), False, False),
    ("empty_lane", None, False, False),
])
def test_the_merged_parts_equal_one_piece_absorbed_attention(kind, rows, has_resident, has_window):
    """Window keys decompressed + resident pages absorbed, merged under one
    softmax, against ``ops/attention.py:ragged_mla_paged_attention`` over all
    of a row's keys at once, on each kind of row of the contract; and which
    part was empty where (its log-sum-exp reads ``NEG_INF``, and the merge
    then takes the other unscaled)."""
    from dynamo_tpu.ops.pallas import pack_spans
    from dynamo_tpu.ops.pallas.mla_attention import NEG_INF

    b, got = _two_parts()
    if kind == "empty_lane":
        # lane 3 has pages and no row: no span names it, nothing is walked
        # for it, and every row's result stands as the other cases hold it
        span_lane, _, count, steps = pack_spans(
            b["lane"], b["resident"], lanes=b["lanes"], tb_tokens=8, block_size=b["bs"])
        assert 3 not in span_lane.tolist()
        # the decode row's 3 pages, and the chunk's 2 resident ones for each
        # of the two token blocks its rows lie in; nothing of the whole prompt
        assert sorted(count[count > 0].tolist()) == [2, 2, 3] and steps.tolist() == [1, 1, 1]
        assert b["resident"].tolist() == [19] + [-1] * 11 + [12] * 9 + [-1] * 3
        return
    if kind == "pad":     # (the twin's softmax over nothing is uniform: no reference)
        assert (got["merged"][rows] == 0).all()
    else:
        np.testing.assert_allclose(got["merged"][rows], b["want"][rows], rtol=2e-5, atol=2e-5)
    assert ((got["lse_resident"][rows] > NEG_INF).all() if has_resident
            else (got["lse_resident"][rows] == NEG_INF).all())
    assert ((got["lse_window"][rows] > NEG_INF).all() if has_window
            else (got["lse_window"][rows] == NEG_INF).all())
    if not has_resident and has_window:
        decompressed = got["out"][rows]
        np.testing.assert_array_equal(got["merged"][rows], decompressed)   # unscaled


def test_the_ragged_mla_launchs_log_sum_exp_is_the_xla_twins():
    """``with_lse``: the rows' log-sum-exp from the running max and sum the
    kernel keeps, against the gathered scores of the XLA twin's arithmetic
    (every key of the lane up to the row's position), on the mixed batch; the
    context is the launch's without the flag, to the bit."""
    from dynamo_tpu.ops.pallas.mla_attention import ragged_mla_attention

    b = _latent_batch()
    args = (b["q_lat"], b["q_rope"], b["ck"], b["kr"],
            *span_args(b["lane"], b["pos"], b["tables"], 8, b["bs"]))
    kw = dict(scale=b["scale"], tb_tokens=8, interpret=True)
    ctx, lse = ragged_mla_attention(*args, **kw, with_lse=True)
    np.testing.assert_array_equal(np.asarray(ctx), np.asarray(ragged_mla_attention(*args, **kw)))
    length = 4 * b["bs"]
    for i in np.flatnonzero(b["pos"] >= 0):
        pages = np.asarray(b["tables"])[b["lane"][i]]
        ck = np.asarray(b["ck"])[pages].reshape(length, -1)
        kr = np.asarray(b["kr"])[pages].reshape(length, -1)
        s = (np.asarray(b["q_lat"])[i] @ ck.T + np.asarray(b["q_rope"])[i] @ kr.T) * b["scale"]
        s = s[:, : b["pos"][i] + 1]
        want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
        np.testing.assert_allclose(np.asarray(lse)[i], want, rtol=2e-5, atol=2e-5)


# sha256 of ``str(jax.make_jaxpr(launch))`` (the kernel's body included, no
# source locations in it) at the two latent cells' shapes, read on the commit
# before PR 52 (dee43df) and again after it, jax 0.9.0
LANE_LAUNCH_PROGRAMS = {
    ("decode", 16): "eb2e781dd1714fd67bd3c86ec901add6beb6d07ef01cb62a2df982192db44bb5",
    ("decode", 32): "b4e4377054992970b6fea3af29c2b4590d79e310a32fb7ae8ae902244846f820",
    ("verify_w5", 16): "9bbe1d7eb5f4625617ecc7c33951971196b03a45e76e2cb4a54e19a8cf14e493",
    ("verify_w5", 32): "b1fc3b9b4f21d19423201e179251aefde6ab06ccd72baded49105ef9112c02a6",
}


@pytest.mark.parametrize("launch,heads", sorted(LANE_LAUNCH_PROGRAMS))
def test_the_decode_and_verify_launches_trace_to_the_program_they_were(launch, heads):
    """The ragged launch alone learned to return a log-sum-exp and to pass by a
    token block with nothing resident (a static flag of ``_launch``): the
    decode and verify launches trace, kernel body and all, to the text they
    traced to before (so ``step_ms_decode`` and ``mla_decode_roofline`` have
    nothing to move by)."""
    import hashlib

    from dynamo_tpu.ops.pallas.mla_attention import (
        mla_paged_attention_decode,
        mla_paged_window_attention_decode,
    )

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's printer")
    fn, lead = ((mla_paged_attention_decode, (24,)) if launch == "decode"
                else (mla_paged_window_attention_decode, (24, 5)))
    s = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    text = str(jax.make_jaxpr(lambda *a: fn(*a, scale=0.07))(
        s((*lead, heads, 512)), s((*lead, heads, 128)), s((9 * 11008, 16, 512)),
        s((9 * 11008, 16, 128)), s((24, 512), jnp.int32), s((24,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() == LANE_LAUNCH_PROGRAMS[launch, heads]
