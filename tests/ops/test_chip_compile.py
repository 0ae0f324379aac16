"""Ask the TPU's compiler, with no TPU: the main path's Pallas kernels at
real widths, compiled for a DESCRIBED v5e chip (on-chip-measurement guide,
section 2).  Interpret mode cannot see what Mosaic refuses — scalar-memory
footprints, tilings of 1-byte types, VMEM scratch — and these cases are what
keeps `chip_smoke.py` from finding it out on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
Everything lives in this one file so one worker owns the library.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.mixtral import MixtralConfig
from dynamo_tpu.models.registry import get_family
from dynamo_tpu.ops.pallas.block_copy import gather_blocks, scatter_blocks
from dynamo_tpu.ops.pallas.paged_attention import (
    paged_attention_decode,
    paged_window_attention_decode,
)
from dynamo_tpu.ops.pallas.ragged_attention import (
    bucket_tb_tokens,
    ragged_paged_attention,
)

# head geometries: what chip_smoke.py serves on one chip (Llama-3.2-3B) and
# the repo's headline (Llama-3-8B; also one tp=4 shard's KV width times 4)
GEOMETRY = {"llama32_3b": (24, 8, 128), "llama3_8b": (32, 8, 128),
            # one chip's share of K-EXAONE-236B's attention: 8 of 64 query
            # heads over 1 of 8 KV heads (benchmark/configs/k-exaone-236b-l8.json)
            "k_exaone_share": (8, 1, 128)}
KV_DTYPES = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
BLOCK = 16
NUM_BLOCKS = 1024
MAX_LEN = 4096                      # chip_smoke.py --context-length
MAX_BLOCKS = MAX_LEN // BLOCK
LANES = 8                           # chip_smoke.py --max-batch-size
# every unified bucket that EngineConfig builds: the default prefill buckets
# up to max_len; the engine packs a bucket to blocks of gcd(largest token
# block for the head geometry, bucket) tokens (engine._tb_for)
SMOKE_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)
# the token block of the MLA kernel's cases (16 heads a token: 256 // 16)
TB = 16
# the benchmark's two serving shapes (BENCHMARK.json: both models are h32 kv8
# d128: four query heads a KV head, blocks of up to 64 tokens): lanes
CELL_SHAPES = {"lanes8": 8, "lanes16": 16}


def _tb(model, bucket):
    h, kvh, _ = GEOMETRY[model]
    return bucket_tb_tokens(h // kvh, BLOCK, bucket)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be written to the persistent cache
    # but not read back without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.compilation_cache.reset_cache()


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cache(s, kv_dtype, kvh, d):
    return s((NUM_BLOCKS, BLOCK, kvh, d), KV_DTYPES[kv_dtype])


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp8"])
@pytest.mark.parametrize("model", ["llama32_3b", "llama3_8b"])
def test_paged_decode_compiles(one_chip, model, kv_dtype):
    h, kvh, d = GEOMETRY[model]
    s = _sds(one_chip)
    cache = _cache(s, kv_dtype, kvh, d)
    paged_attention_decode.lower(
        s((LANES, h, d), jnp.bfloat16), cache, cache,
        s((LANES, MAX_BLOCKS), jnp.int32), s((LANES,), jnp.int32),
    ).compile()


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp8"])
def test_window_verify_compiles(one_chip, kv_dtype):
    h, kvh, d = GEOMETRY["llama3_8b"]
    s = _sds(one_chip)
    cache = _cache(s, kv_dtype, kvh, d)
    paged_window_attention_decode.lower(
        s((LANES, 5, h, d), jnp.bfloat16), cache, cache,  # spec_tokens + 1
        s((LANES, MAX_BLOCKS), jnp.int32), s((LANES,), jnp.int32),
    ).compile()


def _ragged_case(s, bucket, model, kv_dtype, lanes=LANES, tb=None,
                 max_blocks=MAX_BLOCKS, sliding_window=None):
    h, kvh, d = GEOMETRY[model]
    tb = tb or _tb(model, bucket)
    cache = _cache(s, kv_dtype, kvh, d)
    tok = s((bucket,), jnp.int32)
    return ragged_paged_attention.lower(
        s((bucket, h, d), jnp.bfloat16), cache, cache, tok, tok,
        s((lanes, max_blocks), jnp.int32), tok, tok, tok,
        s((bucket // tb,), jnp.int32),
        tb_tokens=tb, sliding_window=sliding_window,
    )


@pytest.mark.parametrize("bucket", SMOKE_BUCKETS)
def test_ragged_compiles_at_every_smoke_bucket(one_chip, bucket):
    """Every (bucket, tb_tokens) the smoke's engine can build (24 query
    heads over 8: three a KV head, 192 score rows a product at 64 tokens).
    The scalar memory the kernel needs is seven words a token and the block
    tables."""
    _ragged_case(_sds(one_chip), bucket, "llama32_3b", "bf16").compile()


@pytest.mark.parametrize("bucket", [SMOKE_BUCKETS[0], 528, SMOKE_BUCKETS[-1]])
def test_ragged_fp8_compiles(one_chip, bucket):
    """fp8 KV at the headline geometry (a step's pages go through a float32
    copy, whose rows the per-head read can stride): smallest, a chunk+lanes
    mixed bucket (512 + 16 lanes, packed to blocks of 16), largest."""
    _ragged_case(_sds(one_chip), bucket, "llama3_8b", "fp8").compile()


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp8"])
@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
def test_ragged_compiles_at_every_bucket_of_the_benchmark_cells(
    one_chip, shape, kv_dtype
):
    """The per-head KV step at every unified bucket of both benchmark
    serving shapes (h32 kv8 d128, context 4,096: one block of 32 tokens for
    the smallest bucket, blocks of 64 from there), bf16 and fp8 KV, with
    Mistral's sliding window on the 8-lane shape."""
    lanes = CELL_SHAPES[shape]
    for bucket in SMOKE_BUCKETS:
        _ragged_case(
            _sds(one_chip), bucket, "llama3_8b", kv_dtype, lanes=lanes,
            sliding_window=4096 if lanes == 8 else None,
        ).compile()


@pytest.mark.parametrize("sliding_window", [128, None], ids=["window128", "full"])
@pytest.mark.parametrize("bucket", [32, 4096, 8192])
def test_ragged_compiles_for_window_and_full_layers_of_one_kv_head(
    one_chip, bucket, sliding_window
):
    """``k-exaone-236b-l8``'s two kinds of layer in one step program: eight
    query heads over ONE KV head (blocks of 32 tokens), 16 lanes of 512-page
    tables at context 8,192, a 128 window or none."""
    _ragged_case(
        _sds(one_chip), bucket, "k_exaone_share", "bf16", lanes=16,
        max_blocks=512, sliding_window=sliding_window,
    ).compile()


def test_decode_kernel_walks_a_window_not_the_context(one_chip):
    """A 128 window under 512-page tables: the wrapper cuts each lane's
    table to the nine pages its query can see, so the kernels grid is lanes x
    9 steps whatever the context (it was lanes x 512, the pages behind the
    window fetched once more and skipped)."""
    h, kvh, d = GEOMETRY["k_exaone_share"]
    s = _sds(one_chip)
    cache = s((678, BLOCK, kvh, d), jnp.bfloat16)
    compiled = paged_attention_decode.lower(
        s((16, h, d), jnp.bfloat16), cache, cache,
        s((16, 512), jnp.int32), s((16,), jnp.int32), sliding_window=128,
    ).compile()
    hlo = compiled.as_text()
    assert "paged_window_attention_decode" in hlo
    # the table the kernel is handed (the argument's 512 columns are cut)
    assert "s32[16,9]" in hlo


def test_ragged_compiles_at_a_context_of_32768(one_chip):
    """One whole 32,768-token window against 16 lanes of 2,048-page block
    tables: five words a token and 128 KiB of tables fit scalar memory.
    (The static page worklists refused every context past about 19k.)"""
    _ragged_case(
        _sds(one_chip), 32768, "llama3_8b", "bf16", lanes=16, max_blocks=2048,
    ).compile()


# the latent cache as ``moonlight-16b-l9`` serves it (benchmark/configs/
# moonlight-16b-l9.json; DeepSeek-V2-Lite's attention widths too): 16 heads
# over one latent of 512 and a rotated key stored 128 wide, 9 layers of
# 11,008 blocks as flat pages, 24 lanes of 8,192 tokens
MLA = {"heads": 16, "latent": 512, "rope_page": 128, "pages": 9 * 11008, "lanes": 24,
       "max_blocks": 512}


def _mla_pages(s):
    return (s((MLA["pages"], BLOCK, MLA["latent"]), jnp.bfloat16),
            s((MLA["pages"], BLOCK, MLA["rope_page"]), jnp.bfloat16))


def _kernel_names(compiled):
    return set(re.findall(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text()))


@pytest.mark.parametrize("bucket,max_blocks", [
    (128, 512), (1024, 512), (8192, 512), (32768, 2048)])
def test_ragged_mla_compiles_at_every_bucket_of_the_cell(one_chip, bucket, max_blocks):
    """The ragged MLA kernel at the published widths, bf16 operands, the
    pages read where they lie (flat, both leaves a whole number of 128-lane
    tiles: no pad of the rope cache), a whole window of the bucket on the
    shared walker's KV steps of 16 pages, 512-page tables (and a context of
    32,768: the static worklists ran out of scalar memory from bucket 512
    up).  The kernel keeps the name the trace shows."""
    from dynamo_tpu.ops.pallas.mla_attention import ragged_mla_attention

    s = _sds(one_chip)
    tok = s((bucket,), jnp.int32)
    compiled = ragged_mla_attention.lower(
        s((bucket, MLA["heads"], MLA["latent"]), jnp.bfloat16),
        s((bucket, MLA["heads"], MLA["rope_page"]), jnp.bfloat16),
        *_mla_pages(s), tok, tok, s((MLA["lanes"], max_blocks), jnp.int32), tok, tok, tok,
        s((bucket // TB,), jnp.int32), scale=0.07, tb_tokens=TB,
    ).compile()
    assert _kernel_names(compiled) == {"ragged_mla_attention"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize("launch", ["decode", "verify_w5"])
def test_mla_lane_kernels_compile_at_the_cells_shapes(one_chip, launch):
    """One query a lane (decode) and a verify window of 5: the same body, a
    token block a lane, its spans derived on the device; 24 lanes, 512-page
    tables."""
    from dynamo_tpu.ops.pallas.mla_attention import (
        mla_paged_attention_decode,
        mla_paged_window_attention_decode,
    )

    s = _sds(one_chip)
    lanes = MLA["lanes"]
    fn, lead = ((mla_paged_attention_decode, (lanes,)) if launch == "decode"
                else (mla_paged_window_attention_decode, (lanes, 5)))
    compiled = fn.lower(
        s((*lead, MLA["heads"], MLA["latent"]), jnp.bfloat16),
        s((*lead, MLA["heads"], MLA["rope_page"]), jnp.bfloat16),
        *_mla_pages(s), s((lanes, MLA["max_blocks"]), jnp.int32), s((lanes,), jnp.int32),
        scale=0.07,
    ).compile()
    assert _kernel_names(compiled) == {fn.__name__}


def _moonlight_16b_l9():
    import dataclasses

    from dynamo_tpu.models.deepseek import DeepseekConfig

    cfg = dataclasses.replace(DeepseekConfig.from_hf_config({
        "vocab_size": 163840, "hidden_size": 2048, "num_hidden_layers": 9,
        "num_attention_heads": 16, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 11264, "first_k_dense_replace": 1, "moe_intermediate_size": 1408,
        "n_routed_experts": 64, "num_experts_per_tok": 6, "n_shared_experts": 2,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "rope_theta": 50000,
        "max_position_embeddings": 8192, "rms_norm_eps": 1e-5,
    }), grouped_matmul="pallas")      # what "auto" is on the chip
    # family, config, blocks, lanes, query rows a token in one product, rope width
    return "deepseek_v3", cfg, 11008, MLA["lanes"], cfg.num_heads, cfg.qk_rope_head_dim


def _k_exaone_236b_l8():
    from dynamo_tpu.models.exaone_moe import ExaoneMoeConfig

    cfg = ExaoneMoeConfig(
        vocab_size=19200, hidden_size=6144, intermediate_size=18432,
        num_layers=8, num_heads=8, num_kv_heads=1, head_dim=128,
        max_position_embeddings=8192, rope_theta=1e6,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 2,
        mlp_layer_types=("dense",) + ("sparse",) * 7, window=128,
        num_experts=16, expert_parallel_size=8, experts_per_token=8,
        moe_intermediate_size=2048, grouped_matmul="pallas",
    )
    return "exaone_moe", cfg, 8320, 16, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim


def _xing4_29b_l8():
    import dataclasses
    import json
    from pathlib import Path

    from dynamo_tpu.models.deepseek import DeepseekConfig

    hf = json.loads((Path(__file__).parents[2] / "benchmark/configs/xing4-29b-l8.json").read_text())
    cfg = dataclasses.replace(DeepseekConfig.from_hf_config(hf), grouped_matmul="pallas")
    return "xing4_0", cfg, 11008, MLA["lanes"], cfg.num_heads, cfg.qk_rope_head_dim


def _phi4_mini_flash():
    import json
    from pathlib import Path

    from dynamo_tpu.models.phi4flash import Phi4FlashConfig

    hf = json.loads((Path(__file__).parents[2] / "benchmark/configs/phi4-mini-flash.json").read_text())
    cfg = Phi4FlashConfig.from_hf_config(hf)
    return "phi4flash", cfg, 4160, 16, cfg.num_heads // cfg.num_kv_heads, 2, 4096


EXPERT_CELLS = {"moonlight-16b-l9": _moonlight_16b_l9, "k-exaone-236b-l8": _k_exaone_236b_l8}
# (the cells whose 6,144 bucket PR 45 held between its neighbours; a later
# configuration's programs are built by the same helper from here)
CELL_PROGRAMS = {**EXPERT_CELLS, "xing4-29b-l8": _xing4_29b_l8,
                 "phi4-mini-flash": _phi4_mini_flash}


@functools.cache
def _expert_cell_program(one_chip, config, program):
    """A step forward of a cell at the cell's shapes (``decode``,
    ``unified_t<tokens>``, ``prefill_t<tokens>``; context 8,192 unless the
    cell says another), the cache donated, compiled for the described chip
    (once a module), and the bytes of its cache."""
    from dynamo_tpu.models.llama import KvPools

    name, cfg, blocks, lanes, rows, rope_dim, *context = CELL_PROGRAMS[config]()
    family = get_family(name)
    s = _sds(one_chip)
    context = context[0] if context else 8192
    window = (family.window_pool_blocks(cfg, lanes, context, BLOCK)
              if family.window_pool_blocks else 0)
    pools = (lambda a: KvPools(a, a)) if window else (lambda a: a)
    shaped = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)  # noqa: E731
    params = shaped(jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0))))
    cache = shaped(jax.eval_shape(lambda: family.cache_init(
        cfg, blocks, BLOCK, None, **({"window_blocks": window} if window else {}),
        **({"lanes": lanes} if family.lane_state else {}))))
    i32 = lambda *shape: s(shape, jnp.int32)  # noqa: E731
    rope = s((context, rope_dim // 2), jnp.float32)
    tables = pools(i32(lanes, context // BLOCK))
    kind, _, t = program.partition("_t")
    t = int(t or 0)
    if kind == "decode":
        def fn(p, c, tok, bt, cl, sl, cos, sin):
            return family.forward_decode(p, cfg, tok, c, bt, cl, sl, cos, sin, attention="pallas")
        args = (i32(lanes), tables, i32(lanes), i32(lanes), rope, rope)
    elif kind == "unified":
        tb = bucket_tb_tokens(rows, BLOCK, t)
        def fn(p, c, tok, bt, cl, pos, slot, lane, sl, sf, sc, pt, rows, cos, sin):
            return family.forward_unified(
                p, cfg, tok, c, bt, cl, pos, slot, lane, sl, sf, sc, pt, rows,
                cos, sin, attention="pallas", tb_tokens=tb)
        args = (i32(t), tables, i32(lanes), i32(t), i32(t), i32(t),
                *(pools(i32(t)) for _ in range(3)), pools(i32(t // tb)), i32(lanes), rope, rope)
    else:
        def fn(p, c, tok, ids, n, start, cos, sin):
            return family.forward_prefill(p, cfg, tok, c, ids, n, start, cos, sin)
        args = (i32(t), pools(i32(context // BLOCK)), i32(), i32(), rope, rope)
    full = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, *args).compile()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", full)
    return compiled, sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(cache))


# a latent family's launches by step program: the unified step walks the
# resident pages absorbed and attends its window's own keys decompressed
# (PR 52), both under names the benchmark's ``^%?ragged_mla_attention`` reads
MLA_KERNELS = {"decode": {"mla_paged_attention_decode"},
               "unified_t8192": {"ragged_mla_attention", "ragged_mla_attention_window"}}


@pytest.mark.parametrize("program", ["decode", "unified_t8192"])
def test_moonlight_step_programs_compile_and_write_the_latent_pages_in_place(one_chip, program):
    """``moonlight-16b-l9``'s whole decode and 8,192-token step programs at
    the cell's shapes (10.87 GB of weights, 2.03 GB of latent pages): both
    leaves aliased to the outputs, the kernels named as the benchmark's
    metrics look for them (the MLA launch of the program, the grouped
    products of the walk), less than 1 GB of temporaries (a copy, a pad or a
    relayout of the rope leaf alone would be 0.41 GB, of the latent 1.62)."""
    compiled, pools = _expert_cell_program(one_chip, "moonlight-16b-l9", program)
    assert _kernel_names(compiled) == {*MLA_KERNELS[program], "gmm"}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pools - 64
    assert memory.temp_size_in_bytes < 1e9
    assert 12.8e9 < memory.argument_size_in_bytes < 13.0e9


XING_HEADS = 32     # benchmark/configs/xing4-29b-l8.json: twice moonlight's on one latent


@pytest.mark.parametrize("launch", ["ragged_t8192", "decode", "verify_w5"])
def test_mla_launches_compile_at_32_heads_on_one_latent(one_chip, launch):
    """``xing4-29b-l8``'s attention: the three launches of the one MLA body
    with 32 heads' queries against each latent row (16 until PR 51), 8
    layers of 11,008 blocks as flat pages, 24 lanes, 512-page tables; the
    ragged launch a whole 8,192-token window at the token block the engine
    derives for 32 query rows a token."""
    from dynamo_tpu.ops.pallas.mla_attention import (
        mla_paged_attention_decode,
        mla_paged_window_attention_decode,
        ragged_mla_attention,
    )

    s = _sds(one_chip)
    lanes, pages = MLA["lanes"], 8 * 11008
    cache = (s((pages, BLOCK, MLA["latent"]), jnp.bfloat16),
             s((pages, BLOCK, MLA["rope_page"]), jnp.bfloat16))
    q = lambda *lead: (s((*lead, XING_HEADS, MLA["latent"]), jnp.bfloat16),  # noqa: E731
                       s((*lead, XING_HEADS, MLA["rope_page"]), jnp.bfloat16))
    tables = s((lanes, MLA["max_blocks"]), jnp.int32)
    if launch == "ragged_t8192":
        bucket = 8192
        tb = bucket_tb_tokens(XING_HEADS, BLOCK, bucket)
        tok = s((bucket,), jnp.int32)
        fn = ragged_mla_attention
        compiled = fn.lower(*q(bucket), *cache, tok, tok, tables, tok, tok, tok,
                            s((bucket // tb,), jnp.int32), scale=0.14, tb_tokens=tb).compile()
    else:
        fn, lead = ((mla_paged_attention_decode, (lanes,)) if launch == "decode"
                    else (mla_paged_window_attention_decode, (lanes, 5)))
        compiled = fn.lower(*q(*lead), *cache, tables, s((lanes,), jnp.int32), scale=0.14).compile()
    assert _kernel_names(compiled) == {fn.__name__}
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize("program", ["decode", "unified_t8192"])
def test_xing_step_programs_compile_beside_their_weights(one_chip, program):
    """``xing4-29b-l8``'s whole decode and 8,192-token step programs at the
    cell's shapes (11.34 GB of weights, 1.80 GB of latent pages, four
    residual streams a row carried through the layer loop): both cache
    leaves aliased to the outputs, the kernels the benchmark's metrics look
    for and no other, and the temporaries (the streams of 8,192 rows are
    235 MB in bf16, 470 MB in float32) small enough that arguments and
    temporaries together leave the chip's 16 GB a margin."""
    compiled, pools = _expert_cell_program(one_chip, "xing4-29b-l8", program)
    assert _kernel_names(compiled) == {*MLA_KERNELS[program], "gmm"}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pools - 64
    assert 13.1e9 < memory.argument_size_in_bytes < 13.3e9
    assert memory.temp_size_in_bytes < 2.0e9


@pytest.mark.parametrize("program", ["decode", "unified_t4096"])
def test_phi4_flash_step_programs_compile_with_the_state_beside_the_pools(one_chip, program):
    """``phi4-mini-flash``'s whole decode and 4,096-token step programs at
    the cell's shapes (7.71 GB of weights, two pools and the lanes' state):
    every cache leaf aliased to the outputs (no copy of a pool, none of the
    state), the two paged kernels and no other, heads of 64 served as pairs
    of 128, and temporaries that hold no ``[rows, d_state, d_inner]`` array
    (4,096 rows of it would be 1.34 GB in float32)."""
    compiled, cache = _expert_cell_program(one_chip, "phi4-mini-flash", program)
    kernel = ("paged_window_attention_decode" if program == "decode"
              else "ragged_paged_attention")
    assert _kernel_names(compiled) == {kernel}
    memory = compiled.memory_analysis()
    assert cache == 921927680       # benchmark/configs/phi4-mini-flash.json serving.kv_bytes
    assert memory.alias_size_in_bytes >= cache - 64
    assert 8.6e9 < memory.argument_size_in_bytes < 8.7e9
    print(program, "temporaries", memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < (0.05e9 if program == "decode" else 0.6e9)
    assert "f32[4096,16,5120]" not in compiled.as_text()


@pytest.mark.parametrize("kind", ["unified", "prefill"])
@pytest.mark.parametrize("config", sorted(EXPERT_CELLS))
def test_the_6144_bucket_of_the_8192_context_cells_compiles_between_its_neighbours(
        one_chip, config, kind):
    """A context of 8,192 has a 6,144-token bucket (``engine._token_buckets``):
    both expert cells' ``unified_6144`` and ``prefill_6144`` programs compile
    for the chip, write every leaf of the cache in place as their neighbours
    do (no pad, slice or relayout of it), and their temporaries lie between
    the 4,096 and the 8,192 programs', which stay under 1 GB as they are held
    today (``k-exaone-236b-l8``'s prompt-only program under 2.7: its dense
    attention holds 8 heads' scores over 8,192 x 8,192 at once, 2.1 GB)."""
    limit = 2.7e9 if (config, kind) == ("k-exaone-236b-l8", "prefill") else 1e9
    temps = {}
    for tokens in (4096, 6144, 8192):
        compiled, pools = _expert_cell_program(one_chip, config, f"{kind}_t{tokens}")
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= pools - 64, tokens
        temps[tokens] = memory.temp_size_in_bytes
    assert temps[4096] <= temps[6144] <= temps[8192] < limit, temps


# every unified bucket of an 8,192 context (engine._token_buckets)
LONG_DOC_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096, 6144, 8192)


@pytest.mark.parametrize("heads", [16, XING_HEADS])
@pytest.mark.parametrize("bucket", LONG_DOC_BUCKETS)
def test_the_window_launch_compiles_at_every_bucket_of_both_latent_cells(one_chip, bucket, heads):
    """``ragged_mla_attention_window`` (PR 52: a unified window's own keys,
    decompressed) at the published head (128 + the rotated part 128 wide,
    values 128), bf16, 16 heads (``moonlight-16b-l9``) and 32
    (``xing4-29b-l8``), at every bucket the two cells' engines build: one
    kernel under a name the benchmark's ``^%?ragged_mla_attention`` reads, a
    head's keys and values whole in VMEM (12.6 MB at 8,192 rows), and beside
    it only the relayouts of its operands (a quarter GB at 32 heads: in the
    step program they fuse into the projections that make them)."""
    from dynamo_tpu.ops.pallas.mla_attention import ragged_mla_attention_window

    s = _sds(one_chip)
    head = lambda: s((bucket, heads, 128), jnp.bfloat16)  # noqa: E731
    tok = s((bucket,), jnp.int32)
    compiled = ragged_mla_attention_window.lower(
        head(), head(), head(), s((bucket, 128), jnp.bfloat16), head(), tok, tok,
        lanes=MLA["lanes"], scale=0.07).compile()
    assert _kernel_names(compiled) == {"ragged_mla_attention_window"}
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * max(bucket, 128) * heads * 128 * 2


def test_xings_unified_programs_fit_beside_their_weights_at_the_three_long_buckets(one_chip):
    """``xing4-29b-l8``'s ``unified_4096 / 6144 / 8192`` with both latent
    launches (32 heads: the window launch's q, k, v and output are 67 MB each
    at 8,192 rows, the merge's float32 halves 134 MB): the temporaries grow
    with the bucket and, with 13.15 GB of arguments, stay inside the 16.9 GB
    the runtime offers with 2 GB to spare (1.22 GB at 8,192; 1.10 before the
    window launch, my chip runs, PR 51)."""
    temps = {}
    for tokens in (4096, 6144, 8192):
        compiled, pools = _expert_cell_program(one_chip, "xing4-29b-l8", f"unified_t{tokens}")
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= pools - 64, tokens
        assert _kernel_names(compiled) == {*MLA_KERNELS["unified_t8192"], "gmm"}
        temps[tokens] = memory.temp_size_in_bytes
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16.9e9 - 2e9
    assert temps[4096] <= temps[6144] <= temps[8192] < 1.5e9, temps


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp8"])
def test_block_gather_compiles(one_chip, kv_dtype):
    s = _sds(one_chip)
    gather_blocks.lower(
        _cache(s, kv_dtype, 8, 128), s((32,), jnp.int32)
    ).compile()


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp8"])
def test_block_scatter_compiles(one_chip, kv_dtype):
    s = _sds(one_chip)
    scatter_blocks.lower(
        _cache(s, kv_dtype, 8, 128),
        s((32, BLOCK, 8, 128), KV_DTYPES[kv_dtype]), s((32,), jnp.int32),
    ).compile()


# the benchmark's two configurations as the cells serve them
# (benchmark/configs/*.json): family, layers, blocks of one layer, lanes, the
# rest; and the sparse-expert family on the same step programs, at
# Mixtral-8x7B's published widths cut by depth (no cell serves it yet)
STEP_CONFIGS = {
    "qwen3-4b": ("qwen3", LlamaConfig(
        vocab_size=151936, hidden_size=2560, intermediate_size=9728,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        max_position_embeddings=4096, rope_theta=1e6, rms_norm_eps=1e-6,
        tie_word_embeddings=True, qk_norm=True,
    ), 1152, 16),
    "mistral-7b-l16": ("mistral", LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128,
        max_position_embeddings=4096, rope_theta=1e4, sliding_window=4096,
    ), 2560, 8),
    "mixtral-8x7b-l4": ("mixtral", MixtralConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=4, num_heads=32, num_kv_heads=8, head_dim=128,
        max_position_embeddings=4096, rope_theta=1e6,
        num_experts=8, experts_per_token=2,
        grouped_matmul="pallas",    # what "auto" is on the chip
    ), 5120, 8),
}


def test_a_layers_expert_banks_are_read_where_they_lie(one_chip):
    """``k-exaone-236b-l8``'s decode step at the cell's shapes (10.4 GB of
    arguments): both pools are written in place, and the grouped product
    reads a layer's banks out of the stack (``ops/moe.py``): sliced out for
    the kernel they were 1.2 GB of temporaries a layer; the step holds 0.12."""
    compiled, pools = _expert_cell_program(one_chip, "k-exaone-236b-l8", "decode")
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pools - 64
    assert memory.temp_size_in_bytes < 400e6
    assert 10.3e9 < memory.argument_size_in_bytes < 10.7e9


# the routed cells' expert layers as a chip holds them (benchmark/configs/):
# choices a token, hidden, expert width, experts held, the first held, sparse
# layers in the stack, decode lanes, the router's width
EXPERT_LAYERS = {
    "k-exaone-236b-l8": (8, 6144, 2048, 16, 32, 7, 16, 128),
    "moonlight-16b-l9": (6, 2048, 1408, 64, 0, 8, 24, 64),
    "xing4-29b-l8": (4, 3584, 1024, 64, 0, 6, 24, 64),
}
# where every expert is held, the layer's temporaries at the 8,192 bucket by
# ``memory_analysis()`` (PR 56: 472 / 589 MB): the sorted rows' buffer (201 /
# 235 MB) and the rows the gather reads out of it, a choice's at a time (the
# same again); a share held keeps the float32 sum and a chunk (221 MB)
EXPERT_LAYER_TEMP_BYTES = {"moonlight-16b-l9": 520e6, "xing4-29b-l8": 650e6}


def _computations(hlo: str, root: str) -> list[str]:
    """The text of computation ``root`` of a compiled module and of every
    computation it calls, however deep (a fusion's, a nested loop's)."""
    blocks = {m.group(1): m.group(0) for m in re.finditer(
        r"^%?([\w.\-]+) \([^\n]*\{\n.*?^\}", hlo, re.M | re.S)}
    seen, todo = [], [root]
    while todo:
        name = todo.pop()
        if name in blocks and blocks[name] not in seen:
            seen.append(blocks[name])
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", blocks[name])
    return seen


@pytest.mark.parametrize("rows", ["decode", "8192"])
@pytest.mark.parametrize("config", sorted(EXPERT_LAYERS))
def test_the_expert_layers_walk_holds_a_chunks_rows(one_chip, config, rows):
    """``moe_experts`` at a routed cell's widths (``k-exaone-236b-l8``: 16 of
    128 experts held, 8 choices a token, banks stacked over 7 layers; the two
    latent models: 64 of 64 held, 1,408 and 1,024 wide), a decode step's lanes
    and the 8,192 bucket: the three grouped products are the Pallas kernel
    inside the walk's loop, over ONE chunk's rows, at the tiles
    ``moe.gmm_tiling`` gives those widths (an over-full tiling is refused
    here, not on the chip).  Where a SHARE is held nothing of ``tokens x
    choices`` rows but indices is left (the sorted buffers were 805 MB each at
    8,192; the program holds the tokens' float32 sum, 201 MB, and a chunk).
    Where EVERY expert is held the program holds the walk's rows in sorted
    order, ONE ``bf16[tokens x choices, hidden]`` buffer that the loop's body
    writes in place (no ``copy`` of its shape there) and that stays in HBM,
    nothing of that many rows as wide as an expert, and no float32 sum
    ``[tokens, hidden / 128, 128]`` with its ``scatter``.  The products keep
    the instruction name ``gmm*`` that ``benchmark/metrics/moe_*.json`` look
    for, with locations as short as the serving process makes them
    (``utils/compile_cache.py``): lowered inline in the loop's body they were
    ``tpu_custom_call.<n>`` on the chip and both metrics fell silent."""
    from dynamo_tpu.ops import moe

    s = _sds(one_chip)
    k, h, i, e, first, layers, lanes, routed = EXPERT_LAYERS[config]
    tokens = lanes if rows == "decode" else int(rows)
    bank = lambda a, b: (s((layers, e, a, b), jnp.bfloat16), s((), jnp.int32))  # noqa: E731

    def fn(x, ids, probs, gate, up, down, valid):
        return moe.moe_experts(
            x, ids, probs, gate, up, down, first_expert=first, experts_routed=routed,
            valid=valid, impl="pallas")

    full = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        compiled = jax.jit(fn).lower(
            s((tokens, h), jnp.bfloat16), s((tokens, k), jnp.int32), s((tokens, k), jnp.float32),
            bank(h, i), bank(h, i), bank(i, h), s((tokens,), jnp.bool_),
        ).compile()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", full)
    hlo = compiled.as_text()
    chunk = min(tokens * k, moe.CHUNK_ROWS)
    # (a chunk's rows are padded to whole row tiles for the kernel)
    padded = -(-chunk // (tm := moe.tile_rows(chunk))) * tm
    products = re.findall(
        r"%([\w.\-]+) = bf16\[(\d+),\d+\][^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(products) == 3, products
    assert all(re.match(r"gmm(\.\d+)?$", name) and int(m) == padded for name, m in products), products
    # (the count by expert compares [tokens x choices, experts held] inside a fusion)
    wide = {m for m in re.findall(rf"\w+\[{tokens * k},(\d+)\]", hlo) if int(m) >= min(h, i)}
    memory = compiled.memory_analysis()
    if e < routed:
        assert tokens * k == chunk or not wide, wide
        assert memory.temp_size_in_bytes < 300e6
        return
    assert tokens * k == chunk or wide == {str(h)}, wide
    buffer = rf"bf16\[{tokens * k},{h}\]"
    # the walk's loop is the one that carries the buffer
    (loop,) = {name for line in hlo.splitlines() if " while(" in line and re.search(buffer, line)
               for name in re.findall(r"body=%([\w.\-]+)", line)}
    body = _computations(hlo, loop)
    assert not [line for text in body for line in text.splitlines()
                if re.search(buffer + r"\S* copy(-start)?\(", line)]
    if tokens * k > chunk:      # (a decode step's one chunk IS the buffer)
        assert any(re.search(buffer + r"[^\n]* dynamic-update-slice\(", text) for text in body)
        # in HBM, not ferried through fast memory round every chunk ("S(1)")
        assert not re.search(buffer + r"\{[^}]*S\(1\)\}", "\n".join(body))
    # (the kernel's own group metadata is an int32 scatter of a few hundred places)
    assert f"f32[{tokens},{h // 128},128]" not in hlo
    assert not re.search(r"= f32\[\d+,\d+,128\][^\n]* scatter\(", hlo)
    assert memory.temp_size_in_bytes < (
        EXPERT_LAYER_TEMP_BYTES[config] if rows == "8192" else 10e6)


@functools.cache
def _compile_forward(one_chip, config, program):
    """A family's step forward at a cell's shapes, cache donated, as a
    compiled executable for the described chip (once a module)."""
    name, cfg, num_blocks, lanes = STEP_CONFIGS[config]
    family = get_family(name)
    s = _sds(one_chip)
    i32 = lambda *shape: s(shape, jnp.int32)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0))),
    )
    page = (cfg.num_layers, num_blocks, BLOCK, cfg.num_kv_heads, cfg.head_dim)
    cache = {"k": s(page, jnp.bfloat16), "v": s(page, jnp.bfloat16)}
    rope = s((MAX_LEN, cfg.head_dim // 2), jnp.float32)
    tables = i32(lanes, MAX_BLOCKS)
    if program == "decode":
        def fn(p, c, tok, bt, cl, sl, cos, sin):
            return family.forward_decode(
                p, cfg, tok, c, bt, cl, sl, cos, sin, attention="pallas")
        args = (i32(lanes), tables, i32(lanes), i32(lanes), rope, rope)
    else:
        t = int(program.removeprefix("unified_t"))
        tb = bucket_tb_tokens(cfg.num_heads // cfg.num_kv_heads, BLOCK, t)
        def fn(p, c, tok, bt, cl, pos, slot, lane, sl, sf, sc, pt, rows, cos, sin):
            return family.forward_unified(
                p, cfg, tok, c, bt, cl, pos, slot, lane, sl, sf, sc, pt, rows,
                cos, sin, attention="pallas", tb_tokens=tb)
        args = (i32(t), tables, i32(lanes), *(i32(t) for _ in range(6)),
                i32(t // tb), i32(lanes), rope, rope)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, *args).compile()
    cache_bytes = 2 * 2 * cfg.num_layers * num_blocks * BLOCK * cfg.num_kv_heads * cfg.head_dim
    return compiled, cache_bytes


@pytest.mark.parametrize("program", ["decode", "unified_t128", "unified_t4096"])
@pytest.mark.parametrize("config", sorted(STEP_CONFIGS))
def test_step_forward_writes_the_donated_cache_in_place(one_chip, config, program):
    """The cache rides the layer loop as a carry: the compiled forward
    aliases the donated K and V to its outputs and holds no copy of them
    among its temporaries (as per-layer scan inputs and stacked outputs it
    held a whole cache there, 2.72 / 2.68 GB; the sparse-expert family's own
    forwards still did, 2.01 / 2.05 / 3.29 GB beside this 1.34 GB cache,
    until it took the shared ones).

    The expert layer walks its live rows a chunk at a time (``ops/moe.py``):
    a chunk's rows (``[2048, hidden]`` in and out, twice ``[2048, 14336]``)
    and the tokens' float32 sum (``[tokens, hidden]``) are 265 MB at 4,096
    tokens (the sorted buffers of ``tokens x 2`` rows were 641 MB), 16 MB at
    128.  A tenth of the cache (134 MB: four layers of weights and a larger
    cache do not fit the chip) would still measure the experts at 4,096, so
    that case is held to less than ONE cache, which a copy of it cannot
    meet."""
    compiled, cache_bytes = _compile_forward(one_chip, config, program)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= cache_bytes
    expert_window = (config, program) == ("mixtral-8x7b-l4", "unified_t4096")
    assert memory.temp_size_in_bytes < (cache_bytes if expert_window else cache_bytes // 10)


@pytest.mark.parametrize("config,operand", [
    ("qwen3-4b", r"bf16\[1152,128,128\]\{[^}]*S\(1\)\}"),
    ("mistral-7b-l16", r"bf16\[40960,128,128\]\{[^}]*\}"),
])
def test_decode_kernel_reads_its_pages_from_fast_memory_where_a_layer_fits(
    one_chip, config, operand,
):
    """``_LayerPages.on_chip``: K and V of one ``qwen3-4b`` layer (75.5 MB)
    are sliced out of the carry and XLA's memory-space assignment keeps the
    slices in the chip's fast memory (``S(1)`` in the layout), where the
    one-query kernel runs twice as fast; ``mistral-7b-l16``'s (168 MB) do
    not fit, and its kernel reads the flat pages in HBM."""
    compiled, _ = _compile_forward(one_chip, config, "decode")
    hlo = compiled.as_text()
    (call,) = [
        line for line in hlo.splitlines()
        if "paged_window_attention_decode" in line and "custom-call(" in line
    ]
    k_name, v_name = re.findall(r"%[\w.\-]+", call.split("custom-call(")[1])[3:5]
    for name in (k_name, v_name):
        (definition,) = [
            line for line in hlo.splitlines()
            if line.strip().startswith(f"{name} = ")
        ]
        assert re.search(operand, definition.split(" = ")[1][:120]), definition[:200]


class _Hlo:
    """A compiled program's text by computation: ``name -> [(instruction,
    dtype, dims, op, line)]``, which computations are fusions' bodies, and
    which are loops' bodies."""

    _INSTR = re.compile(
        r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\w+\[[\d,]*\](?:\{[^ ]*\})?) ([\w\-]+)\(")
    BYTES = {"bf16": 2, "f32": 4, "s32": 4}

    def __init__(self, text):
        self.computations, self.fused, self.loop_bodies = {}, set(), set()
        current = None
        for line in text.splitlines():
            head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
            if head:
                current = self.computations.setdefault(head.group(1), [])
                continue
            m = self._INSTR.match(line)
            if m is None or current is None:
                continue
            name, result, op = m.groups()
            # (a tuple's result has no one shape: no dtype, no dims)
            dtype, dims = re.match(r"(?:(\w+)\[([\d,]*)\])?", result).groups()
            current.append((name, dtype, tuple(map(int, filter(None, (dims or "").split(",")))), op, line))
            if op == "fusion":
                self.fused.add(re.search(r"calls=%([\w.\-]+)", line).group(1))
            if op == "while":
                self.loop_bodies.add(re.search(r"body=%([\w.\-]+)", line).group(1))

    def inside(self, instr):
        """Every instruction of the computation a fusion calls, nested
        fusions' too (of another instruction: none)."""
        if instr[3] != "fusion":
            return []
        body = self.computations[re.search(r"calls=%([\w.\-]+)", instr[4]).group(1)]
        return [inner for i in body for inner in (i, *self.inside(i))]

    def launched(self, loops_only=False):
        """The instructions the program launches by themselves: those of no
        fusion's body (``loops_only``: of the loops' bodies alone)."""
        for name, instrs in self.computations.items():
            if name not in self.fused and (not loops_only or name in self.loop_bodies):
                yield from instrs

    def moved_in_loops(self, floor=1 << 20):
        """Bytes of the copies and transposes of ``floor`` bytes or more that
        an iteration of the program's loops runs, alone or inside a fusion."""
        total = 0
        for instr in self.launched(loops_only=True):
            for _, dtype, dims, op, _ in (instr, *self.inside(instr)):
                size = math.prod(dims) * self.BYTES.get(dtype, 4)
                if op in ("copy", "transpose") and size >= floor:
                    total += size
        return total


def _decode_program(one_chip, config):
    """(compiled decode forward, config, layers of each run) of a cell"""
    if config in STEP_CONFIGS:
        cfg = STEP_CONFIGS[config][1]
        return _compile_forward(one_chip, config, "decode")[0], cfg, (cfg.num_layers,)
    cfg = EXPERT_CELLS[config]()[1]
    return (_expert_cell_program(one_chip, config, "decode")[0], cfg,
            tuple(run.count for run in cfg.layer_runs()))


# operations that compute nothing: a fusion made of these alone is a move
_MOVES = {"parameter", "constant", "bitcast", "copy", "transpose", "dynamic-slice",
          "slice", "reshape", "get-tuple-element"}


@pytest.mark.parametrize("config", ["qwen3-4b", "mistral-7b-l16", "k-exaone-236b-l8"])
def test_a_decode_step_reads_its_projection_weights_where_they_lie(one_chip, config):
    """``_LayerOf``'s promise, for ``wq``, ``wk`` and ``wv`` too
    (``llama._qkv`` splits the heads on the activation where the rows are
    few): in a cell's compiled decode step nothing the program launches by
    itself (a copy, a transpose, a slice, a fusion that only moves) yields an
    array of the size of a layer's, or a run of layers', q or k/v projection
    weight, and every one-layer slice of a weight stack (the four of
    attention; a dense model's three of the MLP) sits inside the fusion of a
    plain product (``convolution ... dim_labels=bf_io->bf``).  Left to fold
    the head split into the product, XLA sliced ``wq``, ``wk`` and ``wv``
    into fast memory and transposed them there in every layer of every step:
    31.5 MB a ``qwen3-4b`` layer, 50.3 MB a ``mistral-7b-l16`` one, and
    ``k-exaone-236b-l8``'s seven sparse layers' ``wq`` whole, 88 MB a
    step."""
    compiled, cfg, runs = _decode_program(one_chip, config)
    hlo = _Hlo(compiled.as_text())
    hidden, q_cols, kv_cols = (
        cfg.hidden_size, cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim)
    sizes = {n * hidden * cols for n in (1, *runs) for cols in (q_cols, kv_cols)}
    slices = {(1, hidden, q_cols), (1, hidden, kv_cols), (1, q_cols, hidden)}
    if config in STEP_CONFIGS:
        slices |= {(1, hidden, cfg.intermediate_size), (1, cfg.intermediate_size, hidden)}
    moved, in_products = [], 0
    for instr in hlo.launched():
        name, dtype, dims, op, line = instr
        inner = hlo.inside(instr)
        if dtype == "bf16" and math.prod(dims) in sizes and (
                op in ("copy", "transpose", "dynamic-slice", "slice")
                or (inner and all(i[3] in _MOVES for i in inner))):
            moved.append((name, dims, op))
        sliced = sum(i[3] == "dynamic-slice" and i[2] in slices for i in inner)
        if sliced:
            assert any(i[3] == "convolution" and "dim_labels=bf_io->bf" in i[4]
                       for i in inner), line[:200]
            in_products += sliced
    assert not moved, moved
    assert in_products >= (7 if config in STEP_CONFIGS else 4), in_products


# the parent's (PR 45's tree: XLA's own choice), counted by `_Hlo.moved_in_loops`
@pytest.mark.parametrize("tokens,parent_bytes", [(2048, 81.8e6), (4096, 132.2e6)])
def test_a_wide_window_moves_no_more_bytes_than_xla_chose(one_chip, tokens, parent_bytes):
    """The other side of ``llama._split_on_activation``: at 2,048 rows and up
    the activation is the larger thing to turn (split there, ``qwen3-4b``'s
    ``unified_t2048`` loses 31.5 MB of weight copies a layer and gains two
    float32 relayouts of q of 33.6 MB each), so a wide window keeps the
    form XLA chooses and its layer moves no more bytes in copies and
    transposes of 1 MB or more than before the rule."""
    compiled, _ = _compile_forward(one_chip, "qwen3-4b", f"unified_t{tokens}")
    assert _Hlo(compiled.as_text()).moved_in_loops() <= parent_bytes + 0.05e6


@pytest.mark.parametrize("program", ["decode", "unified_t128"])
def test_a_step_programs_only_sort_sits_in_a_branch(one_chip, program):
    """The engine's own `dyn_decode_w1` and a `dyn_unified_t*` program at
    `qwen3-4b`'s vocabulary and 16 lanes (one narrow layer: the `sample`
    scope is the whole model's), compiled for the chip: ONE sort over
    `[lanes, vocab]`, in a computation that a `conditional` names as a
    branch, so a step whose lanes are all greedy never runs it; the top-20
    log-probabilities stay the chip's own `TopK` call, not a sort."""
    import dataclasses
    from tests.ops.test_sampling import sorts_by_reach

    _, cell, _, lanes = STEP_CONFIGS["qwen3-4b"]
    cfg = dataclasses.replace(
        cell, hidden_size=256, intermediate_size=512, num_layers=1, num_heads=2, num_kv_heads=1)
    engine = JaxLlmEngine(EngineConfig(
        model=cfg, model_family="qwen3", block_size=BLOCK, max_batch_size=lanes,
        num_blocks=64, max_model_len=512, attention_impl="pallas",
    ))
    name = ("decode",) if program == "decode" else ("unified", int(program.removeprefix("unified_t")))
    jit_fn, avals = engine._aot_jobs([100])[name]
    s = _sds(one_chip)
    hlo = jit_fn.lower(*jax.tree.map(lambda a: s(a.shape, a.dtype), avals)).compile().as_text()

    assert " conditional(" in hlo and sorts_by_reach(hlo) == (1, 0)
    assert re.search(rf"f32\[{lanes},{cfg.vocab_size}\][^\n]* sort\(", hlo)
    topk = re.findall(
        r"= \((f32\[[\d,]+\])[^\n]*?(s32\[[\d,]+\])[^\n]*? custom-call\([^\n]*custom_call_target=\"TopK\"", hlo)
    assert topk == [(f"f32[{lanes},20]", f"s32[{lanes},20]")]


def test_kernel_bytes_do_not_depend_on_the_call_stack(one_chip):
    """A program's persistent-cache key covers the Pallas kernel's bytecode,
    locations included.  Once the cache resolver ran, lowering the same
    kernel from two different Python call stacks gives identical bytes — so
    the AOT-compiled twin IS the program the device thread dispatches, and
    a restart finds it whoever traced first.  (With JAX's default full
    tracebacks the two differ; seen on the chip as every step program
    compiling twice.)"""
    from dynamo_tpu.utils.compile_cache import ensure_compile_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    ensure_compile_cache()
    jax.config.update("jax_compilation_cache_dir", prev_dir)

    def text():
        jax.clear_caches()  # a fresh trace each time, like a fresh process
        lowered = _ragged_case(_sds(one_chip), 64, "llama32_3b", "bf16")
        return lowered.compiler_ir().operation.get_asm(enable_debug_info=False)

    def from_a_deeper_stack():
        return (lambda: text())()

    assert text() == from_a_deeper_stack()


def test_engine_constructs_at_a_context_the_worklists_refused():
    """Context 32,768 was a construction error while the kernel prefetched
    [token blocks, tb_tokens * max_blocks_per_seq] worklists (one row tile
    of them exceeded scalar memory).  The live-page kernel has no such
    width: the engine constructs, with the unified step on."""
    import dataclasses

    cfg = dataclasses.replace(LlamaConfig.tiny(), max_position_embeddings=32768)
    engine = JaxLlmEngine(EngineConfig(
        model=cfg, block_size=16, max_batch_size=2, num_blocks=2048,
        max_model_len=32768, attention_impl="pallas_interpret",
    ))
    assert engine.unified_batch
    assert engine.stats()["kernel_config"] == {"tb_tokens": 64}
