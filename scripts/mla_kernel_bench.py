"""Time the MLA kernels ALONE on the chip (ops/pallas/mla_attention.py) at the
shapes of the two ``long-doc`` cells: 16 heads (``moonlight-16b-l9``) and 32
(``xing4-29b-l8``) over one latent of 512 and a rotated key stored 128 wide,
bf16, pages of 16 tokens out of 9 x 11,008 flat pages, 24 lanes, 512-page
tables.

    python scripts/mla_kernel_bench.py [--iters N] [--heads 16 32] [--blocks 1024] [--splash]

Prints one JSON line a case and head count: ms a launch (median of
``--iters`` timed calls, each ended by block_until_ready) for (a) the ONE
absorbed launch every row had until PR 52 (each row walks its pages up to its
own position), (b) what a unified step launches now: the absorbed launch over
the pages RESIDENT before the window (``resident_ms``) and the flash launch
over the window's own keys, decompressed (``window_ms``, at each ``--blocks``
size); the (query, key) pairs each attends; and each one's share of 197
TFLOP/s by the engine's own count of the work (``observability/perf.py
_latent_cost``: 2 x heads x (512 + 64 + 512) a pair absorbed, 2 x heads x
(192 + 128) decompressed).  Cases: a prompt span of 2,048 / 4,096 / 7,680
tokens alone (its bucket's token blocks), a 4,096 span beside 23 decodes at 4k
context, a 4,096-token chunk that continues a resident prefix of 4,000, and 24
decodes at 4k context through the ragged launch and through the decode launch.
``--splash``: the window part through the kernel JAX ships
(``jax.experimental.pallas.ops.tpu.splash_attention``, heads' keys 256 wide)
as well, the comparison PR 52 chose by.  Exits 1 off the TPU: a CPU time is
not a device time.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

LATENT, ROPE_PAGE, NOPE, ROPE, V_DIM, BLOCK, PAGES = 512, 128, 128, 64, 128, 16, 9 * 11008
LANES, MAX_BLOCKS, CONTEXT = 24, 512, 4096
PEAK_FLOPS = 197e12     # v5e (observability/perf.py DEVICE_PEAKS)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--heads", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--blocks", type=int, nargs="+", default=[1024])
    ap.add_argument("--splash", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas.mla_attention import (
        last_resident_pos,
        mla_paged_attention_decode,
        ragged_mla_attention,
        ragged_mla_attention_window,
    )
    from dynamo_tpu.ops.pallas.ragged_attention import bucket_tb_tokens, pack_spans

    if jax.default_backend() != "tpu":
        print("mla_kernel_bench: no TPU", file=sys.stderr)
        return 1
    keys = jax.random.split(jax.random.PRNGKey(42), 8)
    ck = jax.random.normal(keys[0], (PAGES, BLOCK, LATENT), jnp.bfloat16)
    kr = jax.random.normal(keys[1], (PAGES, BLOCK, ROPE_PAGE), jnp.bfloat16)
    # lane i's pages: a stride through layer 3's blocks
    tables = jnp.asarray((3 * 11008 + (
        np.arange(LANES)[:, None] * MAX_BLOCKS + np.arange(MAX_BLOCKS)[None, :]) % 11008
    ).astype(np.int32))

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(times)

    def splash_ms(heads, bucket, lane, q, k, v):
        """The window part through JAX's own kernel: a causal mask a head,
        the lanes as segments, the keys 192 -> 256 wide (a head's own copy of
        the rotated part), the log-sum-exp saved."""
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
            splash_attention_mask as sm,
        )

        b = min(512, bucket)
        kernel = sk.make_splash_mha_single_device(
            sm.MultiHeadMask([sm.CausalMask((bucket, bucket))] * heads),
            block_sizes=sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b),
            save_residuals=True)
        ids = sk.SegmentIds(q=lane, kv=lane)
        return timed(jax.jit(lambda q, k, v: kernel(q, k, v, segment_ids=ids)), q, k, v)

    def case(name, heads, spans):
        """``spans``: (lane, first position, tokens) packed in order."""
        lane = np.concatenate([np.full(n, ln) for ln, _, n in spans])
        pos = np.concatenate([np.arange(a, a + n) for _, a, n in spans])
        bucket = 1 << int(np.ceil(np.log2(len(lane))))
        tb = bucket_tb_tokens(heads, BLOCK, bucket)
        pad = bucket - len(lane)
        lane = np.concatenate([lane, np.full(pad, LANES)]).astype(np.int32)
        pos = np.concatenate([pos, np.full(pad, -1)]).astype(np.int32)
        resident = last_resident_pos(lane, pos, LANES)
        draw = lambda i, *shape: jax.random.normal(keys[i], shape, jnp.bfloat16)  # noqa: E731
        q_lat, q_rope = draw(2, bucket, heads, LATENT), draw(3, bucket, heads, ROPE_PAGE)

        def absorbed(walk_pos, **kw):
            meta = pack_spans(lane, walk_pos, lanes=LANES, tb_tokens=tb, block_size=BLOCK)
            ms = timed(
                lambda *a: ragged_mla_attention(*a, scale=0.072, tb_tokens=tb, **kw),
                q_lat, q_rope, ck, kr, jnp.asarray(lane), jnp.asarray(walk_pos), tables,
                *(jnp.asarray(m) for m in meta))
            return ms, int(meta[2].sum())

        pairs = sum(n * a + n * (n + 1) // 2 for _, a, n in spans)
        own = sum(n * (n + 1) // 2 for _, _, n in spans)
        absorbed_flops = 2 * heads * (LATENT + ROPE + LATENT)
        own_flops = 2 * heads * (NOPE + ROPE + V_DIM)
        share = lambda flops, ms: round(100 * flops / PEAK_FLOPS / (ms * 1e-3), 2)  # noqa: E731
        one_ms, one_pages = absorbed(pos)
        resident_ms, resident_pages = absorbed(resident, with_lse=True)
        line = {
            "case": name, "heads": heads, "bucket": bucket, "pairs": int(pairs),
            "window_pairs": int(own),
            "absorbed_ms": round(one_ms, 4), "absorbed_pages": one_pages,
            "absorbed_peak_pct": share(pairs * absorbed_flops, one_ms),
            "resident_ms": round(resident_ms, 4), "resident_pages": resident_pages,
        }
        own_args = (
            draw(4, bucket, heads, NOPE), q_rope, draw(5, bucket, heads, NOPE),
            draw(6, bucket, ROPE_PAGE), draw(7, bucket, heads, V_DIM),
            jnp.asarray(lane), jnp.asarray(pos))
        for block in args.blocks:
            ms = timed(
                lambda *a: ragged_mla_attention_window(
                    *a, lanes=LANES, scale=0.072, block=block), *own_args)
            tag = "" if block == args.blocks[0] else f"_b{block}"
            line[f"window_ms{tag}"] = round(ms, 4)
            line[f"window_peak_pct{tag}"] = share(own * own_flops, ms)
        line["both_ms"] = round(resident_ms + line["window_ms"], 4)
        if args.splash and bucket % 128 == 0:
            wide = lambda a, b: jnp.concatenate(  # noqa: E731
                [a, jnp.broadcast_to(b, (*a.shape[:-1], b.shape[-1]))], -1).transpose(1, 0, 2)
            line["splash_ms"] = round(splash_ms(
                heads, bucket, jnp.asarray(lane),
                wide(own_args[0], q_rope), wide(own_args[2], own_args[3][:, None, :]),
                own_args[4].transpose(1, 0, 2)), 4)
        print(json.dumps(line), flush=True)

    for heads in args.heads:
        for n in (2048, 4096, 7680):
            case(f"span_{n}", heads, [(0, 0, n)])
        decodes = [(ln, CONTEXT - 1, 1) for ln in range(1, LANES)]
        case("span_4096_beside_23_decodes_at_4k", heads, [*decodes, (0, 0, 4096)])
        case("chunk_4096_after_4000_resident", heads, [(0, 4000, 4096)])
        case("24_decodes_at_4k_ragged_launch", heads, [(0, CONTEXT - 1, 1), *decodes])
        ctx = jnp.full((LANES,), CONTEXT, jnp.int32)
        ms = timed(
            lambda *a: mla_paged_attention_decode(*a, scale=0.072),
            jax.random.normal(keys[2], (LANES, heads, LATENT), jnp.bfloat16),
            jax.random.normal(keys[3], (LANES, heads, ROPE_PAGE), jnp.bfloat16),
            ck, kr, tables, ctx)
        print(json.dumps({
            "case": "24_decodes_at_4k_decode_launch", "heads": heads, "ms": round(ms, 4),
            "pairs": LANES * CONTEXT,
            "peak_pct": round(100 * LANES * CONTEXT * 2 * heads * (2 * LATENT + ROPE)
                              / PEAK_FLOPS / (ms * 1e-3), 2),
            "bytes_pct": round(100 * LANES * CONTEXT * 2 * (LATENT + ROPE_PAGE)
                               / 819e9 / (ms * 1e-3), 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
