"""Time the ragged paged-attention kernel ALONE on the chip, at the
benchmark cells' shapes (h32 kv8 d128, block 16, bf16 cache).

    python scripts/ragged_kernel_bench.py [--parent DIR] [--iters N]

Prints one JSON line per (workload, variant): ms a call (median of
``--iters`` timed calls, each ended by block_until_ready), token blocks,
live pages, page iterations.  With ``--parent DIR`` (a checkout of the commit
before the kernel walked live pages only) it times THAT tree's kernel at its
full worklist width and at the tightest width that fits, which prices a dead
grid step and a live page (PERF.md section 6, PR 28).  Exits 1 off the TPU:
a CPU time is not a device time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

H, KVH, D, BS = 32, 8, 128, 16
NUM_BLOCKS = 1152


def _workloads():
    """(name, tb_tokens, lanes, bucket, max_blocks, [(lane, start, end)])
    A decode lane is a one-token span at its context's last position; the
    flat axis carries decodes first, then the prompt span (engine order)."""
    long_decodes = [(i, 1999 + 7 * i, 2000 + 7 * i) for i in range(7)]
    chat_decodes = [(i, 299 + 23 * i, 300 + 23 * i) for i in range(15)]
    return [
        ("long.span2048+7dec", 8, 8, 4096, 256, long_decodes + [(7, 0, 2048)]),
        ("long.span2048", 8, 8, 2048, 256, [(7, 0, 2048)]),
        ("long.7dec+span8", 8, 8, 32, 256, long_decodes + [(7, 0, 8)]),
        ("chat.span256@512+15dec", 4, 16, 512, 256, chat_decodes + [(15, 256, 512)]),
        ("chat.span64@320+8dec", 4, 16, 128, 256, chat_decodes[:8] + [(15, 256, 320)]),
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


def _tables(lanes, max_blocks, rng):
    per = min(130, max_blocks, (NUM_BLOCKS - 1) // lanes)
    perm = rng.permutation(NUM_BLOCKS - 1)[: lanes * per] + 1
    bt = np.zeros((lanes, max_blocks), np.int32)
    bt[:, :per] = perm.reshape(lanes, per)
    return bt


def _load_old(checkout):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "parent_ragged_attention",
        f"{checkout}/dynamo_tpu/ops/pallas/ragged_attention.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tb", type=int, nargs="*", default=None,
                    help="time every workload at these token-block sizes "
                         "instead of its cell's own")
    ap.add_argument("--check", default=None, metavar="DIR",
                    help="compare this tree's outputs, to the bit, with the "
                         "kernel of the checkout in DIR (page worklists)")
    ap.add_argument("--allow-cpu", action="store_true", help="rehearsal only")
    a = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, a.parent or root)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    interpret = dev.platform != "tpu"
    from dynamo_tpu.ops.pallas import ragged_attention as ra

    rng = np.random.default_rng(0)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    k_cache = jax.random.normal(kk, (NUM_BLOCKS, BS, KVH, D), jnp.bfloat16)
    v_cache = jax.random.normal(kv, (NUM_BLOCKS, BS, KVH, D), jnp.bfloat16)
    work = _workloads()
    if a.tb:
        work = [(n, tb, *rest) for n, _, *rest in work for tb in a.tb]
    for name, tb, lanes, bucket, max_blocks, spans in work:
        if interpret:  # rehearsal: same code path, toy extents
            bucket, max_blocks = 16, 4
            spans = [(ln, s % 40, s % 40 + min(e - s, 6)) for ln, s, e in spans[-2:]]
        lane, pos = _flat(spans, bucket)
        bt = _tables(lanes, max_blocks, rng)
        q = jax.random.normal(kq, (bucket, H, D), jnp.bfloat16)
        base = {
            "workload": name, "device": dev.device_kind, "tb_tokens": tb,
            "token_blocks": bucket // tb, "parent": bool(a.parent),
        }
        if a.parent:
            full = tb * max_blocks
            tight = ra.pack_page_meta(lane, pos, bt, tb_tokens=tb, block_size=BS)
            need = tight[0].shape[1]
            widths = {"full": (full, 1), "tight": (-(-need // 8) * 8, 1)}
            if tb == 4:
                widths["tuned520x8"] = (520, 8)
            outs = {}
            for variant, (ps, pps) in widths.items():
                if ps < need:
                    continue
                meta = ra.pack_page_meta(
                    lane, pos, bt, tb_tokens=tb, block_size=BS, page_slots=ps
                )
                args = (
                    q, k_cache, v_cache, jnp.asarray(lane), jnp.asarray(pos),
                    *(jnp.asarray(m) for m in meta),
                )
                fn = lambda *xs, pps=pps: ra.ragged_paged_attention(  # noqa: E731
                    *xs, tb_tokens=tb, pages_per_step=pps, interpret=interpret
                )
                med, best = _time(fn, args, a.iters)
                outs[variant] = np.asarray(fn(*args).astype(jnp.float32))
                print(json.dumps({
                    **base, "variant": variant, "page_slots": ps,
                    "pages_per_step": pps, "ms": med, "ms_min": best,
                    "live_pages": int(meta[3].sum()),
                    "page_iterations": int(meta[0].size),
                }), flush=True)
            ref = outs.pop("full")
            for variant, o in outs.items():
                assert np.array_equal(ref, o), f"{name}: {variant} != full"
        else:
            spans_meta = ra.pack_spans(
                lane, pos, lanes=lanes, tb_tokens=tb, block_size=BS
            )
            args = (
                q, k_cache, v_cache, jnp.asarray(lane), jnp.asarray(pos),
                jnp.asarray(bt), *(jnp.asarray(m) for m in spans_meta),
            )
            fn = lambda *xs: ra.ragged_paged_attention(  # noqa: E731
                *xs, tb_tokens=tb, interpret=interpret
            )
            med, best = _time(fn, args, a.iters)
            live = int(spans_meta[-1].sum())
            row = {
                **base, "variant": "live_pages", "ms": med, "ms_min": best,
                "live_pages": live, "page_iterations": live,
            }
            if a.check:
                old = _load_old(a.check)
                meta = old.pack_page_meta(
                    lane, pos, bt, tb_tokens=tb, block_size=BS
                )
                want = old.ragged_paged_attention(
                    q, k_cache, v_cache, jnp.asarray(lane), jnp.asarray(pos),
                    *(jnp.asarray(m) for m in meta),
                    tb_tokens=tb, interpret=interpret,
                )
                row["equal_to_parent_bitwise"] = bool(
                    jnp.array_equal(fn(*args), want)
                )
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
