"""Model correctness: paged prefill+decode must match dense full-sequence
recomputation, and TP-sharded execution must match single-device execution.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import (
    LlamaConfig,
    init_kv_cache,
    init_params,
    kv_cache_spec,
    llama_forward_decode,
    llama_forward_prefill,
    make_rope_tables,
    param_specs,
)
from dynamo_tpu.ops.attention import (
    dense_causal_attention,
    paged_decode_attention,
    write_prefill_kv,
)
from dynamo_tpu.parallel import MeshConfig, make_mesh, shard_pytree

CFG = LlamaConfig.tiny()
BLOCK_SIZE = 4
NUM_BLOCKS = 64


def dense_reference_logits(params, cfg, token_ids):
    """Recompute logits for every position with a plain dense forward."""
    from dynamo_tpu.ops.norms import rms_norm
    from dynamo_tpu.ops.rope import apply_rope

    cos, sin = make_rope_tables(cfg)
    s = len(token_ids)
    ids = jnp.asarray(token_ids, jnp.int32)
    x = params["embed"][ids].astype(cfg.dtype)
    positions = jnp.arange(s, dtype=jnp.int32)
    for i in range(cfg.num_layers):
        w = jax.tree.map(lambda a: a[i], params["layers"])
        attn_in = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
        qp, kp, vp = attn_in @ w["wq"], attn_in @ w["wk"], attn_in @ w["wv"]
        if cfg.attention_bias:
            qp, kp, vp = qp + w["bq"], kp + w["bk"], vp + w["bv"]
        q = qp.reshape(s, cfg.num_heads, cfg.head_dim)
        k = kp.reshape(s, cfg.num_kv_heads, cfg.head_dim)
        v = vp.reshape(s, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        attn = dense_causal_attention(q[None], k[None], v[None])[0]
        x = x + attn.reshape(s, -1) @ w["wo"]
        mlp_in = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
        x = x + jax.nn.silu(mlp_in @ w["w_gate"]) * (mlp_in @ w["w_up"]) @ w["w_down"]
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def test_paged_decode_matches_dense_attention():
    rng = jax.random.PRNGKey(1)
    b, h, kvh, d, bs = 2, 4, 2, 16, 4
    ctx = [7, 13]
    max_blocks = 4
    keys = jax.random.split(rng, 4)
    q = jax.random.normal(keys[0], (b, h, d), jnp.float32)
    k_cache = jnp.zeros((8, bs, kvh, d))
    v_cache = jnp.zeros((8, bs, kvh, d))
    block_tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)

    dense_outs = []
    for i in range(b):
        k_seq = jax.random.normal(jax.random.fold_in(keys[1], i), (ctx[i], kvh, d))
        v_seq = jax.random.normal(jax.random.fold_in(keys[2], i), (ctx[i], kvh, d))
        k_cache, v_cache = write_prefill_kv(
            k_cache, v_cache,
            jnp.pad(k_seq, ((0, 16 - ctx[i]), (0, 0), (0, 0))),
            jnp.pad(v_seq, ((0, 16 - ctx[i]), (0, 0), (0, 0))),
            block_tables[i], jnp.int32(ctx[i]),
        )
        # dense reference: single query attending over the full context
        groups = h // kvh
        qg = q[i].reshape(kvh, groups, d)
        logits = jnp.einsum("kgd,lkd->kgl", qg, k_seq) / jnp.sqrt(jnp.float32(d))
        weights = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("kgl,lkd->kgd", weights, v_seq).reshape(h, d)
        dense_outs.append(out)

    paged = paged_decode_attention(
        q, k_cache, v_cache, block_tables, jnp.asarray(ctx, jnp.int32)
    )
    np.testing.assert_allclose(paged, jnp.stack(dense_outs), rtol=2e-4, atol=2e-4)


def test_prefill_then_decode_matches_dense(params):
    cos, sin = make_rope_tables(CFG)
    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    token_ids = list(range(2, 12))  # 10 prompt tokens
    seq_pad = 16
    block_ids = jnp.asarray([0, 1, 2, 3, 4, 5], jnp.int32)

    padded = jnp.asarray(token_ids + [0] * (seq_pad - len(token_ids)), jnp.int32)
    logits, cache = llama_forward_prefill(
        params, CFG, padded, cache, block_ids, jnp.int32(len(token_ids)),
        jnp.int32(0), cos, sin,
    )
    ref = dense_reference_logits(params, CFG, token_ids)
    np.testing.assert_allclose(logits, ref[len(token_ids) - 1], rtol=2e-3, atol=2e-3)

    # decode three more greedy tokens; compare each against dense recompute
    current = list(token_ids)
    for _ in range(3):
        next_id = int(jnp.argmax(ref[len(current) - 1]))
        current.append(next_id)
        context_len = len(current)
        slot = jnp.asarray([block_ids[(context_len - 1) // BLOCK_SIZE] * BLOCK_SIZE
                            + (context_len - 1) % BLOCK_SIZE], jnp.int32)
        block_tables = jnp.pad(block_ids, (0, 2))[None, :]
        logits, cache = llama_forward_decode(
            params, CFG, jnp.asarray([next_id], jnp.int32), cache,
            block_tables, jnp.asarray([context_len], jnp.int32), slot, cos, sin,
        )
        ref = dense_reference_logits(params, CFG, current)
        np.testing.assert_allclose(
            logits[0], ref[context_len - 1], rtol=2e-3, atol=2e-3
        )


def test_tp_sharded_matches_single_device(params):
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    cos, sin = make_rope_tables(CFG)
    token_ids = list(range(2, 10))
    seq_pad = 8
    block_ids = jnp.asarray([0, 1], jnp.int32)
    padded = jnp.asarray(token_ids, jnp.int32)

    cache = init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE)
    logits_single, _ = llama_forward_prefill(
        params, CFG, padded, cache, block_ids, jnp.int32(len(token_ids)),
        jnp.int32(0), cos, sin,
    )

    sharded_params = shard_pytree(params, param_specs(CFG), mesh)
    cache_specs = {"k": kv_cache_spec(), "v": kv_cache_spec()}
    sharded_cache = shard_pytree(init_kv_cache(CFG, NUM_BLOCKS, BLOCK_SIZE), cache_specs, mesh)

    # pin output shardings (the engine does the same): logits replicated,
    # cache kept kv-head-sharded
    out_shardings = (
        NamedSharding(mesh, P()),
        {"k": NamedSharding(mesh, kv_cache_spec()), "v": NamedSharding(mesh, kv_cache_spec())},
    )

    @partial(jax.jit, out_shardings=out_shardings)
    def run(p, c, ids):
        return llama_forward_prefill(
            p, CFG, ids, c, block_ids, jnp.int32(len(token_ids)), jnp.int32(0), cos, sin
        )

    with mesh:
        logits_tp, new_cache = run(sharded_params, sharded_cache, padded)
    np.testing.assert_allclose(logits_tp, logits_single, rtol=2e-3, atol=2e-3)
    # cache must remain sharded over kv heads
    assert isinstance(new_cache["k"].sharding, NamedSharding)
    assert new_cache["k"].sharding.spec == P("pp", None, None, "tp", None)


def test_qwen3_qk_norm_matches_dense_reference():
    """Qwen3 geometry (per-head q/k RMSNorm, pre-rope): paged prefill +
    decode must match the dense recompute with the norm applied."""
    import dataclasses

    cfg = dataclasses.replace(LlamaConfig.tiny(), qk_norm=True)
    params = init_params(cfg, jax.random.PRNGKey(7))
    # non-trivial norm weights so the test actually exercises the op
    params["layers"]["q_norm"] = (
        1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(8),
                                      params["layers"]["q_norm"].shape)
    ).astype(cfg.dtype)
    params["layers"]["k_norm"] = (
        1.0 - 0.2 * jax.random.normal(jax.random.PRNGKey(9),
                                      params["layers"]["k_norm"].shape)
    ).astype(cfg.dtype)

    prompt = list(range(3, 15))
    ref = dense_reference_logits(params, cfg, prompt)

    cos, sin = make_rope_tables(cfg)
    num_blocks, bs = 16, 4
    cache = init_kv_cache(cfg, num_blocks, bs)
    block_ids = jnp.arange(4, dtype=jnp.int32)
    logits, cache = llama_forward_prefill(
        params, cfg, jnp.asarray(prompt, jnp.int32), cache, block_ids,
        jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref[len(prompt) - 1]), rtol=2e-4, atol=2e-4
    )

    # one decode step on the next token must match the dense recompute too
    nxt = int(jnp.argmax(ref[len(prompt) - 1]))
    full = prompt + [nxt]
    ref2 = dense_reference_logits(params, cfg, full)
    tables = jnp.arange(4, dtype=jnp.int32)[None, :]
    lens = jnp.asarray([len(full)], jnp.int32)
    slots = jnp.asarray([len(prompt)], jnp.int32)
    logits2, _ = llama_forward_decode(
        params, cfg, jnp.asarray([nxt], jnp.int32), cache, tables, lens, slots,
        cos, sin,
    )
    np.testing.assert_allclose(
        np.asarray(logits2[0]), np.asarray(ref2[len(full) - 1]), rtol=2e-4, atol=2e-4
    )


def test_qwen3_registry_config():
    from dynamo_tpu.models.registry import get_family

    fam = get_family("qwen3")
    cfg = fam.config_from_hf(
        {
            "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
        }
    )
    assert cfg.qk_norm and not cfg.attention_bias
    params = fam.init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["q_norm"].shape == (2, 16)


QKV_KINDS = {"plain": {}, "qk_norm": {"qk_norm": True}, "attention_bias": {"attention_bias": True}}


@pytest.mark.parametrize("rows", [1, 16, 2048])
@pytest.mark.parametrize("kind", sorted(QKV_KINDS))
def test_qkv_is_three_plain_products_on_both_sides_of_the_row_rule(kind, rows):
    """``_qkv`` in bf16 on random weights equals the three products computed
    separately in float32 (bias, head split and q/k norm behind them) within
    bf16 rounding, where the head split is held on the activation
    (``_split_on_activation``: 1 and 16 rows of hidden 64) and where the
    compiler is left free (2,048 rows); the traced program holds the barrier
    on the first side only."""
    import dataclasses

    from dynamo_tpu.models.llama import _qkv, _split_on_activation
    from dynamo_tpu.ops.norms import rms_norm

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.bfloat16, **QKV_KINDS[kind])
    keys = jax.random.split(jax.random.PRNGKey(rows), 4)
    w = jax.tree.map(lambda a: a[1], init_params(cfg, keys[0])["layers"])
    for i, name in enumerate(n for n in ("bq", "bk", "bv", "q_norm", "k_norm") if n in w):
        w[name] = (0.5 + jax.random.uniform(jax.random.fold_in(keys[1], i), w[name].shape)
                   ).astype(cfg.dtype)
    x = jax.random.normal(keys[2], (rows, cfg.hidden_size), jnp.float32).astype(cfg.dtype)

    split = _split_on_activation(rows, cfg.hidden_size)
    assert split == (rows < 2048)
    assert ("optimization_barrier" in str(jax.make_jaxpr(lambda x, w: _qkv(x, w, cfg))(x, w))) == split
    got = jax.jit(lambda x, w: _qkv(x, w, cfg))(x, w)

    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    heads = {"q": cfg.num_heads, "k": cfg.num_kv_heads, "v": cfg.num_kv_heads}
    for out, (name, n) in zip(got, heads.items()):
        ref = x.astype(jnp.float32) @ f32[f"w{name}"]
        if cfg.attention_bias:
            ref = ref + f32[f"b{name}"]
        ref = ref.reshape(rows, n, cfg.head_dim)
        if cfg.qk_norm and name != "v":
            ref = rms_norm(ref, f32[f"{name}_norm"], cfg.rms_norm_eps)
        assert out.shape == ref.shape and out.dtype == jnp.bfloat16
        # one rounding of the product to bf16 (2^-9 of its size), a second and
        # a third through the norm
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), rtol=2 ** -6, atol=2 ** -7)


def test_greedy_decode_tokens_of_a_two_layer_model_are_what_they_were():
    """Prefill, then seven greedy ``llama_forward_decode`` steps of the tiny
    two-layer model (its head untied, so that the ids wander): the ids the
    tree gave before ``_qkv`` held its head split on the activation (one row
    is under the row rule: this runs the barrier's side)."""
    import dataclasses

    cfg = dataclasses.replace(CFG, tie_word_embeddings=False)
    params = init_params(cfg, jax.random.PRNGKey(3))
    cos, sin = make_rope_tables(cfg)
    cache = init_kv_cache(cfg, NUM_BLOCKS, BLOCK_SIZE)
    prompt = list(range(2, 12))
    block_ids = jnp.arange(6, dtype=jnp.int32)
    logits, cache = llama_forward_prefill(
        params, cfg, jnp.asarray(prompt + [0] * 6, jnp.int32), cache, block_ids,
        jnp.int32(len(prompt)), jnp.int32(0), cos, sin,
    )
    ids = [int(jnp.argmax(logits))]
    decode = jax.jit(partial(llama_forward_decode, cfg=cfg, cos=cos, sin=sin))
    for _ in range(7):
        length = len(prompt) + len(ids)
        slot = block_ids[(length - 1) // BLOCK_SIZE] * BLOCK_SIZE + (length - 1) % BLOCK_SIZE
        logits, cache = decode(
            params, token_ids=jnp.asarray(ids[-1:], jnp.int32), kv_cache=cache,
            block_tables=block_ids[None, :], context_lens=jnp.asarray([length], jnp.int32),
            slot_ids=slot[None],
        )
        ids.append(int(jnp.argmax(logits[0])))
    assert ids == [478, 11, 206, 26, 430, 412, 464, 383]
