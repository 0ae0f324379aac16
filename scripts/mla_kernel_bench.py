"""Time the MLA kernels ALONE on the chip (ops/pallas/mla_attention.py) at the
shapes of ``moonlight-16b-l9.long-doc``: 16 heads over one latent of 512 and
a rotated key stored 128 wide, bf16, pages of 16 tokens out of 9 x 11,008
flat pages, 24 lanes, 512-page tables.

    python scripts/mla_kernel_bench.py [--iters N]

Prints one JSON line a case: ms a launch (median of ``--iters`` timed calls,
each ended by block_until_ready), the pairs of (query, key) it attends, and
its share of the roofline by the engine's own count of the work
(``observability/perf.py:_latent_cost``: absorbed products a pair, 1,280 B a
cached token as stored) at the v5e's published peaks.  Cases: a prompt span
of 2,048 / 4,096 / 7,680 tokens alone (its bucket's token blocks), a 4,096
span beside 23 decodes at 4k context, and 24 decodes at 4k context through
the ragged launch and through the decode launch.  Exits 1 off the TPU: a CPU
time is not a device time.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

HEADS, LATENT, ROPE_PAGE, BLOCK, PAGES = 16, 512, 128, 16, 9 * 11008
LANES, MAX_BLOCKS, TB, CONTEXT = 24, 512, 16, 4096
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # v5e (observability/perf.py DEVICE_PEAKS)
PAIR_FLOPS = 2 * HEADS * (LATENT + 64) + 2 * HEADS * LATENT
TOKEN_BYTES = 2 * (LATENT + ROPE_PAGE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas.mla_attention import (
        mla_paged_attention_decode,
        ragged_mla_attention,
    )
    from dynamo_tpu.ops.pallas.ragged_attention import pack_spans

    if jax.default_backend() != "tpu":
        print("mla_kernel_bench: no TPU", file=sys.stderr)
        return 1
    keys = jax.random.split(jax.random.PRNGKey(42), 4)
    ck = jax.random.normal(keys[0], (PAGES, BLOCK, LATENT), jnp.bfloat16)
    kr = jax.random.normal(keys[1], (PAGES, BLOCK, ROPE_PAGE), jnp.bfloat16)
    # lane i's pages: a stride through layer 3's blocks
    tables = (3 * 11008 + (np.arange(LANES)[:, None] * MAX_BLOCKS + np.arange(MAX_BLOCKS)[None, :])
              % 11008).astype(np.int32)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(times)

    def report(case, ms, pairs, pages):
        floor_ms = max(pairs * PAIR_FLOPS / PEAK_FLOPS, pages * BLOCK * TOKEN_BYTES / PEAK_BYTES) * 1e3
        print(json.dumps({"case": case, "ms": round(ms, 4), "pairs": int(pairs),
                          "pages_copied": int(pages), "roofline_pct": round(100 * floor_ms / ms, 2)}),
              flush=True)

    def ragged(case, spans):
        """``spans``: (lane, first position, tokens) packed in order."""
        lane = np.concatenate([np.full(n, ln) for ln, _, n in spans])
        pos = np.concatenate([np.arange(a, a + n) for _, a, n in spans])
        bucket = 1 << int(np.ceil(np.log2(max(len(lane), TB))))
        pad = bucket - len(lane)
        lane = np.concatenate([lane, np.full(pad, -1)]).astype(np.int32)
        pos = np.concatenate([pos, np.full(pad, -1)]).astype(np.int32)
        meta = pack_spans(lane, pos, lanes=LANES, tb_tokens=TB, block_size=BLOCK)
        q_lat = jax.random.normal(keys[2], (bucket, HEADS, LATENT), jnp.bfloat16)
        q_rope = jax.random.normal(keys[3], (bucket, HEADS, ROPE_PAGE), jnp.bfloat16)
        ms = timed(
            lambda *a: ragged_mla_attention(*a, scale=0.072, tb_tokens=TB),
            q_lat, q_rope, ck, kr, jnp.asarray(lane), jnp.asarray(pos), jnp.asarray(tables),
            *(jnp.asarray(m) for m in meta))
        pairs = sum(n * a + n * (n + 1) // 2 for _, a, n in spans)
        report(case, ms, pairs, int(meta[2].sum()))

    for n in (2048, 4096, 7680):
        ragged(f"span_{n}", [(0, 0, n)])
    decodes = [(ln, CONTEXT - 1, 1) for ln in range(1, LANES)]
    ragged("span_4096_beside_23_decodes_at_4k", [(0, 0, 4096), *decodes])
    ragged("24_decodes_at_4k_ragged_launch", [(0, CONTEXT - 1, 1), *decodes])
    ctx = jnp.full((LANES,), CONTEXT, jnp.int32)
    ms = timed(
        lambda *a: mla_paged_attention_decode(*a, scale=0.072),
        jax.random.normal(keys[2], (LANES, HEADS, LATENT), jnp.bfloat16),
        jax.random.normal(keys[3], (LANES, HEADS, ROPE_PAGE), jnp.bfloat16),
        ck, kr, jnp.asarray(tables), ctx)
    report("24_decodes_at_4k_decode_launch", ms, LANES * CONTEXT, LANES * CONTEXT // BLOCK)
    return 0


if __name__ == "__main__":
    sys.exit(main())
