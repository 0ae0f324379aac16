"""Time the ragged paged-attention kernel ALONE on the chip, at the
benchmark cells' shapes (h32 kv8 d128, block 16, bf16 cache).

    python scripts/ragged_kernel_bench.py [--tree DIR] [--tb N ...] [--pages P ...]
    python scripts/ragged_kernel_bench.py --layers 36 [--num-blocks 1152]

Prints one JSON line per (workload, token block, pages a KV step): ms a call
(median of ``--iters`` timed calls, each ended by block_until_ready), token
blocks, pages copied, KV steps, microseconds a KV step.  ``--tree DIR`` times
the kernel of another checkout on the same workloads (the parent commit's:
one process a tree, both in one chip call); a tree whose kernel has no KV
step (one page an iteration, PR 28 - PR 34) is timed at ``--tb`` alone.
With ``--layers L`` it times both attention kernels as a step program
launches them, L times in one jitted scan: over ONE layer's pages ``[N, ...]``
with the tables as they are, and over the whole cache as flat pages
``[L * N, ...]`` with the tables offset by ``layer * N``
(``models/llama.py:_scan_layers``); ms a launch must agree (PERF.md section 5,
PR 33).  Both operands are a program's arguments, in HBM: this prices the
operand's SIZE, not where it lives (a layer sliced out inside a step program
may sit in the chip's fast memory, and the one-query kernel then runs twice as
fast: ``_LayerPages.on_chip``).  Exits 1 off the TPU: a CPU time is not a
device time.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

import numpy as np

H, KVH, D, BS = 32, 8, 128, 16
NUM_BLOCKS = 1152


def _workloads():
    """(name, lanes, bucket, max_blocks, [(lane, start, end)])
    A decode lane is a one-token span at its context's last position; the
    flat axis carries decodes first, then the prompt span (engine order)."""
    long_decodes = [(i, 1999 + 7 * i, 2000 + 7 * i) for i in range(7)]
    chat_decodes = [(i, 299 + 23 * i, 300 + 23 * i) for i in range(15)]
    return [
        ("long.span2048+7dec", 8, 4096, 256, long_decodes + [(7, 0, 2048)]),
        ("long.span2048", 8, 2048, 256, [(7, 0, 2048)]),
        ("long.span3584", 8, 4096, 256, [(7, 0, 3584)]),
        ("long.7dec+span8", 8, 32, 256, long_decodes + [(7, 0, 8)]),
        ("chat.span256@512+15dec", 16, 512, 256, chat_decodes + [(15, 256, 512)]),
        ("chat.span64@320+8dec", 16, 128, 256, chat_decodes[:8] + [(15, 256, 320)]),
    ]


def _flat(spans, bucket):
    lane = np.full((bucket,), -1, np.int32)
    pos = np.full((bucket,), -1, np.int32)
    cur = 0
    for ln, start, end in spans:
        n = end - start
        lane[cur:cur + n] = ln
        pos[cur:cur + n] = np.arange(start, end)
        cur += n
    return lane, pos


def _tables(lanes, max_blocks, rng, num_blocks=NUM_BLOCKS):
    """Distinct random pages: 130 a lane, and the last lane (the prompt
    span's) as many more as are left, up to a full table."""
    per = min(130, max_blocks, (num_blocks - 1) // lanes)
    perm = rng.permutation(num_blocks - 1) + 1
    bt = np.zeros((lanes, max_blocks), np.int32)
    bt[:, :per] = perm[: lanes * per].reshape(lanes, per)
    more = min(max_blocks - per, num_blocks - 1 - lanes * per)
    bt[-1, per:per + more] = perm[lanes * per: lanes * per + more]
    return bt


def _time(fn, args, iters):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3, min(ts) * 1e3


def _decode_workloads():
    """(name, lanes, max_blocks, [context length of each live lane]): the
    decode kernel at the cells' lane counts (`qwen3-4b.chat` 16 lanes with
    about 7 busy on short contexts, the long-prompt cells 8 full lanes)."""
    return [
        ("decode.chat.16lanes", 16, 256, [300 + 23 * i for i in range(7)]),
        ("decode.long.8lanes", 8, 256, [2000 + 7 * i for i in range(8)]),
    ]


def _bench_layers(a, dev, interpret) -> int:
    """Both kernels, ``--layers`` launches in one jitted scan, at N pages
    and at L * N pages with offset tables."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.pallas import paged_attention as pa
    from dynamo_tpu.ops.pallas import ragged_attention as ra

    layers, n = a.layers, a.num_blocks
    rng = np.random.default_rng(0)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    flat_shape = (layers * n, BS, KVH, D)
    k_all = jax.random.normal(kk, flat_shape, jnp.bfloat16)
    v_all = jax.random.normal(kv, flat_shape, jnp.bfloat16)
    # one layer's pages, as the slice a forward used to take: the LAST
    # layer's, so that both variants read the same values at the far end
    k_one, v_one = k_all[-n:], v_all[-n:]
    index = jnp.arange(layers, dtype=jnp.int32)

    def timed(launch, out_shape, tables, row):
        """``launch(k, v, tables) -> out`` scanned over the layer index;
        the outputs are summed so that no launch is dead code."""
        def scanned(offset):
            def run(k, v, bt):
                def body(acc, layer):
                    # min(layer, 0) is 0 in every iteration, but not to the
                    # compiler: no launch can be hoisted out of the loop
                    shift = layer * offset if offset else jnp.minimum(layer, 0)
                    out = launch(k, v, bt + shift)
                    return acc + out.astype(jnp.float32), None
                return jax.lax.scan(body, jnp.zeros(out_shape, jnp.float32), index)[0]
            return jax.jit(run)

        for variant, fn, k, v in (
            ("N", scanned(0), k_one, v_one), ("LxN", scanned(n), k_all, v_all),
        ):
            med, best = _time(fn, (k, v, tables), a.iters)
            print(json.dumps({
                **row, "device": dev.device_kind, "variant": variant,
                "pages": int(k.shape[0]), "launches": layers,
                "ms_a_launch": med / layers, "ms_a_launch_min": best / layers,
            }), flush=True)

    for name, lanes, bucket, max_blocks, spans in _workloads():
        tb = math.gcd(a.tb[0], bucket)  # as the engine packs a bucket
        if interpret:  # rehearsal: same code path, toy extents
            bucket, max_blocks, tb = 16, 4, 8
            spans = [(ln, s % 40, s % 40 + min(e - s, 6)) for ln, s, e in spans[-2:]]
        lane, pos = _flat(spans, bucket)
        bt = jnp.asarray(_tables(lanes, max_blocks, rng, n))
        q = jax.random.normal(kq, (bucket, H, D), jnp.bfloat16)
        meta = ra.pack_spans(lane, pos, lanes=lanes, tb_tokens=tb, block_size=BS)
        lane_j, pos_j = jnp.asarray(lane), jnp.asarray(pos)
        meta_j = tuple(jnp.asarray(m) for m in meta)
        timed(
            lambda k, v, t: ra.ragged_paged_attention(
                q, k, v, lane_j, pos_j, t, *meta_j, tb_tokens=tb,
                interpret=interpret,
            ),
            (bucket, H, D), bt,
            {"workload": name, "kernel": "ragged_paged_attention",
             "tb_tokens": tb, "pages_copied": int(meta[2].sum()),
             "kv_steps": int(meta[3].sum())},
        )
    for name, lanes, max_blocks, ctx in _decode_workloads():
        if interpret:
            max_blocks, ctx = 4, [min(c % 60 + 1, 60) for c in ctx[:2]]
        bt = jnp.asarray(_tables(lanes, max_blocks, rng, n))
        lens = np.zeros((lanes,), np.int32)
        lens[: len(ctx)] = ctx
        lens_j = jnp.asarray(lens)
        q = jax.random.normal(kq, (lanes, H, D), jnp.bfloat16)
        timed(
            lambda k, v, t: pa.paged_attention_decode(
                q, k, v, t, lens_j, interpret=interpret
            ),
            (lanes, H, D), bt,
            {"workload": name, "kernel": "paged_window_attention_decode",
             "lanes": lanes, "live_lanes": len(ctx)},
        )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None, metavar="DIR",
                    help="time the kernel of the checkout in DIR")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tb", type=int, nargs="*", default=[64],
                    help="largest token blocks to time every workload at; a "
                         "bucket is packed to gcd(this, bucket) tokens a block, "
                         "as the engine packs it (default: what the engine "
                         "derives for this head geometry)")
    ap.add_argument("--pages", type=int, nargs="*", default=[None],
                    help="pages a KV step (default: the kernel's own, from "
                         "the page size)")
    ap.add_argument("--check", action="store_true",
                    help="also print each row's largest error against the XLA "
                         "twin (ops/attention.py) over float32 copies of the "
                         "same operands")
    ap.add_argument("--layers", type=int, default=None,
                    help="time both attention kernels, this many launches in "
                         "one jitted scan, over one layer's pages and over "
                         "the whole cache as flat pages")
    ap.add_argument("--num-blocks", type=int, default=NUM_BLOCKS,
                    help="pages of ONE layer (with --layers)")
    ap.add_argument("--allow-cpu", action="store_true", help="rehearsal only")
    a = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, a.tree or root)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    interpret = dev.platform != "tpu"
    if a.layers:
        return _bench_layers(a, dev, interpret)
    from dynamo_tpu.ops.pallas import ragged_attention as ra

    kv_step = hasattr(ra, "kv_step_pages")  # else: one page an iteration
    rng = np.random.default_rng(0)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    k_cache = jax.random.normal(kk, (NUM_BLOCKS, BS, KVH, D), jnp.bfloat16)
    v_cache = jax.random.normal(kv, (NUM_BLOCKS, BS, KVH, D), jnp.bfloat16)
    for name, lanes, bucket, max_blocks, spans in _workloads():
        tbs = a.tb
        if interpret:  # rehearsal: same code path, toy extents
            bucket, max_blocks, tbs = 16, 4, [8]
            spans = [(ln, s % 40, s % 40 + min(e - s, 6)) for ln, s, e in spans[-2:]]
        lane, pos = _flat(spans, bucket)
        bt = _tables(lanes, max_blocks, rng)
        q = jax.random.normal(kq, (bucket, H, D), jnp.bfloat16)
        for tb in sorted({math.gcd(tb, bucket) for tb in tbs}):
            for pages in a.pages if kv_step else [None]:
                step = {"pages_per_step": pages} if kv_step else {}
                meta = ra.pack_spans(
                    lane, pos, lanes=lanes, tb_tokens=tb, block_size=BS, **step
                )
                args = (
                    q, k_cache, v_cache, jnp.asarray(lane), jnp.asarray(pos),
                    jnp.asarray(bt), *(jnp.asarray(m) for m in meta),
                )
                fn = lambda *xs: ra.ragged_paged_attention(  # noqa: E731
                    *xs, tb_tokens=tb, interpret=interpret, **step
                )
                med, best = _time(fn, args, a.iters)
                steps = int(meta[3].sum())
                err = {}
                if a.check:
                    from dynamo_tpu.ops.attention import ragged_paged_attention as twin

                    ctx = np.zeros((lanes,), np.int32)
                    np.maximum.at(ctx, lane[pos >= 0], pos[pos >= 0] + 1)
                    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
                    want = twin(
                        f32(q), f32(k_cache), f32(v_cache), jnp.asarray(bt),
                        jnp.asarray(ctx), jnp.asarray(lane), jnp.asarray(pos),
                    )
                    live = jnp.asarray(pos >= 0)[:, None, None]
                    err["max_abs_err"] = float(jnp.max(jnp.where(
                        live, jnp.abs(f32(fn(*args)) - want), 0.0
                    )))
                print(json.dumps({
                    "workload": name, "device": dev.device_kind,
                    "tree": a.tree or ".", "tb_tokens": tb,
                    "pages_per_step": (pages or ra.kv_step_pages(BS)) if kv_step else 1,
                    "ms": med, "ms_min": best, "token_blocks": bucket // tb,
                    "pages_copied": int(meta[2].sum()), "kv_steps": steps,
                    "us_a_step": 1e3 * med / max(steps, 1), **err,
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
