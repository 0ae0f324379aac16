"""Time a llama-like family's whole step forward on the chip with the head
split of q/k/v on the ACTIVATION (``models/llama.py:_qkv`` behind its
``optimization_barrier``) and with the compiler free to fold it into the
product (which transposes the layer's weight in every step), at each row
count: the table that ``llama._split_on_activation`` was fitted on (PERF.md
section 6, PR 48).

    python scripts/qkv_split_bench.py [--config qwen3-4b ...] [--program decode unified_t128 ...]

One JSON line a (config, program): ms a call of both forms (median of
``--iters`` calls, each ended by block_until_ready, the cache donated and
handed on), and the difference a layer in microseconds (positive: the split
on the activation is faster).  Shapes are the benchmark cells' (the compile
test's ``STEP_CONFIGS``); weights are random, a prompt window is ONE span of
the bucket's length from position 0, a decode step's lanes sit at contexts of
300-645 tokens.  Exits 1 off the TPU: a CPU time is not a device time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK, MAX_LEN = 16, 4096


def _params(family, cfg):
    """Random bf16 weights leaf by leaf (no float32 copy of a stack)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    draw = jax.jit(
        lambda key, shape: 0.02 * jax.random.normal(key, shape, jnp.bfloat16),
        static_argnums=1)
    return jax.tree.unflatten(tree, [draw(k, a.shape).astype(a.dtype) for k, a in zip(keys, leaves)])


def _inputs(cfg, program, lanes, num_blocks):
    """The forward's arguments behind (params, cache), as numpy."""
    from dynamo_tpu.ops.pallas.ragged_attention import bucket_tb_tokens, pack_spans

    rng = np.random.default_rng(0)
    max_blocks = MAX_LEN // BLOCK
    tables = rng.integers(1, num_blocks, (lanes, max_blocks)).astype(np.int32)
    tables[0] = rng.permutation(num_blocks - 1)[:max_blocks] + 1   # the span's: distinct pages
    if program == "decode":
        lens = np.asarray([300 + 23 * i for i in range(lanes)], np.int32)
        pos = lens - 1
        slots = tables[np.arange(lanes), pos // BLOCK] * BLOCK + pos % BLOCK
        tok = rng.integers(0, cfg.vocab_size, lanes).astype(np.int32)
        return {}, (tok, tables, lens, slots.astype(np.int32))
    t = int(program.removeprefix("unified_t"))
    tb = bucket_tb_tokens(cfg.num_heads // cfg.num_kv_heads, BLOCK, t)
    pos = np.arange(t, dtype=np.int32)
    lane = np.zeros(t, np.int32)
    slot = (tables[0, pos // BLOCK] * BLOCK + pos % BLOCK).astype(np.int32)
    span_lane, span_first, span_count, kv_steps = pack_spans(
        lane, pos, lanes=lanes, tb_tokens=tb, block_size=BLOCK,
        sliding_window=cfg.sliding_window)
    lens = np.zeros(lanes, np.int32)
    lens[0] = t
    rows = np.zeros(lanes, np.int32)
    rows[0] = t - 1
    tok = rng.integers(0, cfg.vocab_size, t).astype(np.int32)
    return {"tb_tokens": tb}, (
        tok, tables, lens, pos, slot, lane, span_lane, span_first, span_count, kv_steps, rows)


def _time(fn, params, cache, args, iters):
    import jax

    for _ in range(3):
        out, cache = fn(params, cache, *args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out, cache = fn(params, cache, *args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3, cache


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", nargs="+", default=["qwen3-4b"])
    ap.add_argument("--program", nargs="+", default=[
        "decode", "unified_t128", "unified_t512", "unified_t1024", "unified_t2048", "unified_t4096"])
    ap.add_argument("--iters", type=int, default=20)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 1
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.registry import get_family
    from tests.ops.test_chip_compile import STEP_CONFIGS

    device = jax.devices()[0]
    for config in a.config:
        name, cfg, num_blocks, lanes = STEP_CONFIGS[config]
        family = get_family(name)
        params = _params(family, cfg)
        cache = family.cache_init(cfg, num_blocks, BLOCK, None)
        cos, sin = llama.make_rope_tables(cfg)
        for program in a.program:
            kwargs, args = _inputs(cfg, program, lanes, num_blocks)
            args = tuple(jnp.asarray(x) for x in args) + (cos[:MAX_LEN], sin[:MAX_LEN])
            forward = family.forward_decode if program == "decode" else family.forward_unified
            ms = {}
            rule = llama._split_on_activation
            for form, fits in (("folded", False), ("on_activation", True)):
                llama._split_on_activation = lambda rows, hidden, fits=fits: fits
                try:
                    fn = jax.jit(
                        lambda p, c, *rest: forward(p, cfg, rest[0], c, *rest[1:],
                                                    attention="pallas", **kwargs),
                        donate_argnums=(1,))
                    ms[form], cache = _time(fn, params, cache, args, a.iters)
                finally:
                    llama._split_on_activation = rule
            print(json.dumps({
                "config": config, "program": program, "device": device.device_kind,
                "rows": args[0].shape[0], "hidden": cfg.hidden_size,
                "rule_splits_on_activation": rule(args[0].shape[0], cfg.hidden_size),
                "ms": {k: round(v, 3) for k, v in ms.items()},
                "saved_us_a_layer": round(
                    (ms["folded"] - ms["on_activation"]) * 1e3 / cfg.num_layers, 1),
            }), flush=True)
        del params, cache
    return 0


if __name__ == "__main__":
    sys.exit(main())
