"""DeepSeek MLA: absorbed-decode vs decompressed-prefill consistency, cache
compactness, q-lora path, ep+tp sharded equivalence, engine integration.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.deepseek import (
    DeepseekConfig,
    deepseek_forward_decode,
    deepseek_forward_prefill,
    init_kv_cache,
    init_params,
    kv_cache_specs,
    make_rope_tables,
    param_specs,
)
from dynamo_tpu.parallel import MeshConfig, make_mesh, shard_pytree

CFG = DeepseekConfig.tiny_mla()
BLOCK_SIZE = 4
NUM_BLOCKS = 32


def test_latent_cache_is_compact():
    """The MLA cache stores the latent and the one rotated key a token, the
    key in whole 128-lane tiles (the layout the kernels copy in place): 640
    values at the published 512 + 64, a sixth of the 4,096 + 2,048 that 16
    heads' decompressed keys and values would take."""
    from dynamo_tpu.models.deepseek import rope_page_width

    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    assert cache["k"].shape == (CFG.num_layers, NUM_BLOCKS, BLOCK_SIZE, CFG.kv_lora_rank)
    assert cache["v"].shape[-1] == rope_page_width(CFG) == 128
    lite = DeepseekConfig.deepseek_v2_lite()
    per_token = lite.kv_lora_rank + rope_page_width(lite)
    assert per_token == 512 + 128
    assert per_token * 6 < lite.num_heads * (lite.qk_head_dim + lite.v_head_dim)


def test_prefill_decode_consistency():
    """Absorbed-latent decode of token t+1 after prefill(1..t) must match a
    fresh decompressed prefill over (1..t+1)."""
    params = init_params(CFG, jax.random.PRNGKey(2))
    cos, sin = make_rope_tables(CFG)
    tokens = list(range(3, 12))
    block_ids = jnp.asarray([0, 1, 2], jnp.int32)

    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_a, cache = deepseek_forward_prefill(
        params, CFG, jnp.asarray(tokens, jnp.int32), cache, block_ids,
        jnp.int32(len(tokens)), jnp.int32(0), cos, sin,
    )
    nxt = int(jnp.argmax(logits_a))

    context = len(tokens) + 1
    slot = jnp.asarray(
        [(context - 1) // BLOCK_SIZE * BLOCK_SIZE + (context - 1) % BLOCK_SIZE],
        jnp.int32,
    )
    tables = jnp.pad(block_ids, (0, 1))[None, :]
    logits_dec, _ = deepseek_forward_decode(
        params, CFG, jnp.asarray([nxt], jnp.int32), cache, tables,
        jnp.asarray([context], jnp.int32), slot, cos, sin,
    )

    cache2 = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_b, _ = deepseek_forward_prefill(
        params, CFG, jnp.asarray(tokens + [nxt], jnp.int32), cache2, block_ids,
        jnp.int32(context), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits_dec[0]), np.asarray(logits_b), rtol=2e-3, atol=2e-3
    )


def test_direct_q_projection_path():
    """q_lora_rank=0 switches to the direct q projection (V2-Lite style)."""
    cfg = DeepseekConfig.tiny_mla().__class__(
        **{**DeepseekConfig.tiny_mla().__dict__, "q_lora_rank": 0}
    )
    params = init_params(cfg, jax.random.PRNGKey(4))
    assert "wq" in params["moe_layers"] and "w_uq" not in params["moe_layers"]
    cos, sin = make_rope_tables(cfg)
    cache = init_kv_cache(cfg, NUM_BLOCKS, BLOCK_SIZE)
    logits, _ = deepseek_forward_prefill(
        params, cfg, jnp.asarray([5, 6, 7], jnp.int32), cache,
        jnp.asarray([0], jnp.int32), jnp.int32(3), jnp.int32(0), cos, sin,
    )
    assert logits.shape == (cfg.vocab_size,)
    assert np.isfinite(np.asarray(logits)).all()


def test_ep_tp_sharded_matches_single():
    params = init_params(CFG, jax.random.PRNGKey(3))
    cos, sin = make_rope_tables(CFG)
    tokens = jnp.asarray(list(range(3, 11)), jnp.int32)
    block_ids = jnp.asarray([0, 1], jnp.int32)

    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_single, _ = deepseek_forward_prefill(
        params, CFG, tokens, cache, block_ids, jnp.int32(8), jnp.int32(0), cos, sin
    )

    mesh = make_mesh(MeshConfig(ep=2, tp=2), devices=jax.devices()[:4])
    sharded_params = shard_pytree(params, param_specs(CFG), mesh)
    specs = kv_cache_specs(CFG)
    sharded_cache = shard_pytree(init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE), specs, mesh)
    out_shardings = (
        NamedSharding(mesh, P()),
        jax.tree.map(lambda s: NamedSharding(mesh, s), specs),
    )

    run = jax.jit(
        lambda p, c, ids: deepseek_forward_prefill(
            p, CFG, ids, c, block_ids, jnp.int32(8), jnp.int32(0), cos, sin
        ),
        out_shardings=out_shardings,
    )
    logits_ep, _ = run(sharded_params, sharded_cache, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_ep), np.asarray(logits_single), rtol=2e-3, atol=2e-3
    )


def test_v3_geometry_params_shape():
    """The V3/R1 geometry builds a parameter tree with the expected expert
    stack (config shape only — tiny init not materialized at full size)."""
    cfg = DeepseekConfig.deepseek_v3()
    assert cfg.num_moe_layers == 58
    assert cfg.qk_head_dim == 192
    specs = param_specs(cfg)
    assert specs["moe_layers"]["w_gate"] == P(None, "ep", None, "tp")
    assert specs["moe_layers"]["w_uk"] == P(None, None, "tp")


def test_decode_pallas_kernel_matches_gather_path():
    """MLA paged-attention kernel (interpret mode) produces the same decode
    logits as the XLA gather fallback."""
    import numpy as np

    from dynamo_tpu.models.deepseek import init_kv_cache, make_rope_tables

    cfg = CFG
    params = init_params(cfg, jax.random.PRNGKey(0))
    cos, sin = make_rope_tables(cfg)
    num_blocks, bs = 16, 8
    cache = init_kv_cache(cfg, num_blocks, bs)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    ctx = jnp.asarray([9, 21], jnp.int32)
    slots = jnp.asarray([8, 20], jnp.int32)  # next slot per sequence
    tokens = jnp.asarray([3, 7], jnp.int32)

    # write some prefix content so attention sees a real context
    key = jax.random.PRNGKey(1)
    # (the pages; the expert layers' counters stay as they are.  The lanes
    # behind the rotated key get noise too: the queries are zero there)
    cache = {
        k: v if k == "moe_stats"
        else jax.random.normal(jax.random.fold_in(key, i), v.shape, v.dtype)
        for i, (k, v) in enumerate(cache.items())
    }

    logits_jax, cache_jax = deepseek_forward_decode(
        params, cfg, tokens, dict(cache), tables, ctx, slots, cos, sin,
        attention="jax",
    )
    logits_pl, cache_pl = deepseek_forward_decode(
        params, cfg, tokens, dict(cache), tables, ctx, slots, cos, sin,
        attention="pallas_interpret",
    )
    np.testing.assert_allclose(logits_pl, logits_jax, rtol=2e-4, atol=2e-4)
    for k in cache_jax:
        np.testing.assert_allclose(cache_pl[k], cache_jax[k], rtol=1e-6, atol=1e-6)


def test_prefix_prefill_matches_plain_prefill():
    """MLA continued prefill: prefilling [prefix] then [tail] over the
    resident prefix latents must equal one whole-prompt prefill (logits and
    cache)."""
    import numpy as np

    from dynamo_tpu.models.deepseek import (
        deepseek_forward_prefill_with_prefix,
        init_kv_cache,
        make_rope_tables,
    )

    cfg = CFG
    params = init_params(cfg, jax.random.PRNGKey(0))
    cos, sin = make_rope_tables(cfg)
    num_blocks, bs = 16, 4
    prompt = list(range(3, 19))  # 16 tokens = 4 blocks
    split = 8                    # block-aligned prefix

    # reference: whole-prompt prefill
    blocks = jnp.arange(8, dtype=jnp.int32)
    ref_logits, ref_cache = deepseek_forward_prefill(
        params, cfg, jnp.asarray(prompt, jnp.int32),
        init_kv_cache(cfg, num_blocks, bs), blocks,
        jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
    )

    # two-step: prefix prefill, then continued prefill over it
    _, cache = deepseek_forward_prefill(
        params, cfg, jnp.asarray(prompt[:split], jnp.int32),
        init_kv_cache(cfg, num_blocks, bs), blocks[: split // bs],
        jnp.int32(split), jnp.int32(0), cos, sin,
    )
    tail = prompt[split:]
    tail_blocks = blocks[split // bs :]
    logits2, cache2 = deepseek_forward_prefill_with_prefix(
        params, cfg, jnp.asarray(tail, jnp.int32), cache,
        blocks[: split // bs], tail_blocks, jnp.int32(len(tail)),
        jnp.int32(split), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits2), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    for key in ("k", "v"):  # the pages (the counters count two steps here, one there)
        np.testing.assert_allclose(
            np.asarray(cache2[key]), np.asarray(ref_cache[key]), rtol=1e-5, atol=1e-5
        )


def test_v3_sigmoid_noaux_routing():
    """V3/R1 routing semantics: the e_score_correction_bias steers SELECTION
    but never the combine weights, and group-limited top-k keeps experts
    within the chosen groups (reference: HF modeling_deepseek noaux_tc /
    vLLM grouped_topk sigmoid)."""
    import numpy as np

    from dynamo_tpu.ops.moe import moe_router_sigmoid_noaux

    rng = jax.random.PRNGKey(0)
    t, h, e = 6, 16, 8
    x = jax.random.normal(rng, (t, h), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (h, e), jnp.float32) * 0.3

    # a huge bias on expert 5 forces selection, but the combine weight must
    # come from the unbiased sigmoid score (renormalized)
    bias = jnp.zeros((e,)).at[5].set(100.0)
    ids, probs = moe_router_sigmoid_noaux(x, w, bias, top_k=2)
    assert bool(jnp.all(jnp.any(ids == 5, axis=-1)))
    scores = jax.nn.sigmoid(x @ w)
    for row in range(t):
        chosen = scores[row, ids[row]]
        np.testing.assert_allclose(
            np.asarray(probs[row]), np.asarray(chosen / chosen.sum()),
            rtol=1e-5, atol=1e-6,
        )

    # group limiting: 4 groups of 2, keep 1 group → both experts same group
    ids, _ = moe_router_sigmoid_noaux(
        x, w, jnp.zeros((e,)), top_k=2, n_group=4, topk_group=1
    )
    assert bool(jnp.all(ids[:, 0] // 2 == ids[:, 1] // 2))


def test_v3_config_roundtrip_and_forward():
    """A sigmoid-routing config initializes router_bias, loads the HF
    e_score_correction_bias, and the forward pass runs."""
    import dataclasses

    cfg = dataclasses.replace(
        CFG, scoring_func="sigmoid", n_group=2, topk_group=1
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert params["moe_layers"]["router_bias"].shape == (cfg.num_moe_layers, cfg.num_experts)

    from dynamo_tpu.models.deepseek import init_kv_cache, make_rope_tables

    cos, sin = make_rope_tables(cfg)
    logits, _ = deepseek_forward_prefill(
        params, cfg, jnp.arange(3, 11, dtype=jnp.int32),
        init_kv_cache(cfg, 8, 4), jnp.asarray([0, 1], jnp.int32),
        jnp.int32(8), jnp.int32(0), cos, sin,
    )
    assert bool(jnp.all(jnp.isfinite(logits)))


def unified_in_windows(params, cfg, ids, cuts, *, attention, block_size=4, num_blocks=32,
                       lanes=3, tb=8, blocks=None):
    """A prompt served through ``deepseek_forward_unified`` in the windows
    ``[0, cuts[0]), [cuts[0], cuts[1]), ...`` on lane 1 of ``lanes``, the
    spans packed as the engine packs them for a family that attends its own
    window (the walk told each lane's last RESIDENT position).  Returns the
    last window's logits of the prompt's last token and the cache."""
    from dynamo_tpu.models.deepseek import deepseek_forward_unified
    from dynamo_tpu.ops.pallas.mla_attention import last_resident_pos
    from dynamo_tpu.ops.pallas.ragged_attention import pack_spans

    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    cos, sin = make_rope_tables(cfg)
    cache = init_kv_cache(cfg, num_blocks, block_size)
    max_blocks = -(-len(ids) // block_size)
    blocks = np.asarray(blocks if blocks is not None else range(3, 3 + max_blocks), np.int32)
    tables = np.zeros((lanes, max_blocks), np.int32)
    tables[1] = blocks
    oob = num_blocks * block_size
    logits = None
    for start, end in zip([0, *cuts], [*cuts, len(ids)]):
        n = end - start
        t = -(-n // tb) * tb
        lane, pos, slot = np.full(t, lanes, np.int32), np.full(t, -1, np.int32), np.full(t, oob, np.int32)
        tokens = np.zeros(t, np.int32)
        lane[:n], pos[:n], tokens[:n] = 1, np.arange(start, end), ids[start:end]
        slot[:n] = blocks[pos[:n] // block_size] * block_size + pos[:n] % block_size
        walk = last_resident_pos(lane, pos, lanes) if attention.startswith("pallas") else pos
        spans = pack_spans(lane, walk, lanes=lanes, tb_tokens=tb, block_size=block_size)
        ctx = np.zeros(lanes, np.int32)
        ctx[1] = end
        rows = np.zeros(lanes, np.int32)
        rows[1] = n - 1
        logits, cache = deepseek_forward_unified(
            params, cfg, i32(tokens), cache, i32(tables), i32(ctx), i32(pos), i32(slot),
            i32(lane), *(i32(s) for s in spans), i32(rows), cos, sin,
            attention=attention, tb_tokens=tb)
    return logits[1], cache


def test_a_prompt_in_two_unified_windows_gives_the_split_forwards_logits():
    """A 23-token prompt served in two windows of the unified step (Pallas
    kernels, interpreted), cut at 14, so that the second window continues a
    resident prefix that ends mid-page (pages of 4): the first window's rows
    attend nothing but their own window, decompressed; the second's attend
    the resident 14 absorbed and their own 9 decompressed, merged under one
    softmax.  The logits are the whole-prompt split prefill's, and the XLA
    route's (absorbed in one piece) in the same two windows; the pages
    written are the same."""
    params = init_params(CFG, jax.random.PRNGKey(4))
    cos, sin = make_rope_tables(CFG)
    ids = [int(t) for t in np.random.default_rng(7).integers(2, 500, size=23)]
    blocks = jnp.arange(3, 9, dtype=jnp.int32)
    want, want_cache = deepseek_forward_prefill(
        params, CFG, jnp.asarray(ids + [0], jnp.int32), init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE),
        blocks, jnp.int32(len(ids)), jnp.int32(0), cos, sin)
    got, cache = unified_in_windows(params, CFG, ids, [14], attention="pallas_interpret")
    twin, _ = unified_in_windows(params, CFG, ids, [14], attention="jax")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin), rtol=2e-4, atol=2e-4)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache[leaf]), np.asarray(want_cache[leaf]), rtol=1e-5, atol=1e-5)
