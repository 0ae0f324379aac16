"""Hashes of the lowered text of the decode and unified forwards of each
family that shares models/llama.py's layer loop, at tiny sizes on the CPU.

A change to the shared loop (``LayerKind``, ``LayerRun``, ``_scan_layer_runs``)
for one family must leave the others' programs as they were: run this in the
parent's tree and in the change's and compare the lines.

    (cd <tree> && PYTHONPATH=<tree> python scripts/lowered_text.py)
"""
import hashlib
import os

os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from dynamo_tpu.models.registry import get_family
from dynamo_tpu.models.llama import KvPools, LlamaConfig
from dynamo_tpu.models.exaone_moe import ExaoneMoeConfig
from dynamo_tpu.models.mixtral import MixtralConfig
from dynamo_tpu.models.deepseek import DeepseekConfig
import dataclasses
def cases():
    yield "llama", LlamaConfig.tiny()
    yield "mistral", dataclasses.replace(LlamaConfig.tiny(), sliding_window=8)
    yield "exaone_moe", ExaoneMoeConfig.tiny()
    yield "mixtral", MixtralConfig.tiny()
    yield "deepseek_v3", DeepseekConfig.tiny_mla()
    yield "xing4_0", DeepseekConfig.tiny_xing()
lanes, bs, nb, T = 4, 4, 32, 32
for name, cfg in cases():
    fam = get_family(name)
    window = fam.window_pool_blocks(cfg, lanes, 64, bs) if fam.window_pool_blocks else 0
    pools = (lambda a: KvPools(a, a)) if window else (lambda a: a)
    params = jax.eval_shape(lambda: fam.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: fam.cache_init(cfg, nb, bs, None, **({"window_blocks": window} if window else {})))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    cos, sin = jax.eval_shape(lambda: fam.rope_tables(cfg))
    tables = pools(i32(lanes, 16))
    def dec(p, c, tok, bt, cl, sl, cos, sin):
        return fam.forward_decode(p, cfg, tok, c, bt, cl, sl, cos, sin, attention="jax")
    txt = jax.jit(dec).lower(params, cache, i32(lanes), tables, i32(lanes), i32(lanes), cos, sin).as_text()
    print(name, "decode", hashlib.sha256(txt.encode()).hexdigest()[:16], len(txt))
    if fam.forward_unified:
        def uni(p, c, tok, bt, cl, pos, slot, lane, sl, sf, sc, pt, rows, cos, sin):
            return fam.forward_unified(p, cfg, tok, c, bt, cl, pos, slot, lane, sl, sf, sc, pt, rows, cos, sin, attention="jax", tb_tokens=8)
        txt = jax.jit(uni).lower(params, cache, i32(T), tables, i32(lanes), i32(T), i32(T), i32(T), *(pools(i32(T)) for _ in range(3)), pools(i32(T//8)), i32(lanes), cos, sin).as_text()
        print(name, "unified", hashlib.sha256(txt.encode()).hexdigest()[:16], len(txt))
