"""Time the expert layer ALONE on the chip (``ops/moe.py:moe_experts``), at
the shapes of ``k-exaone-236b-l8``: hidden 6144, experts of 2048, 8 choices a
token over 128 experts of which 16 are held, banks stacked over 2 layers.

    python scripts/moe_walk_bench.py [--tree DIR] [--tokens T ...] [--chunk C ...]

Prints one JSON line per (tokens, chunk, routing): ms a call (median of
``--iters`` timed calls, each ended by block_until_ready) and the layer's
``MOE_STATS``.  Routing ``even`` draws 8 of 128 experts a token (an eighth
held); ``all_held`` draws them among the 16 held (every row live: what a chip
that holds all its experts sees).  ``--tree DIR`` times another checkout's
expert layer on the same inputs (the parent commit's: one process a tree, both
in one chip call); ``--chunk`` sets this tree's ``CHUNK_ROWS`` and is ignored
by a tree that has none.  Exits 1 off the TPU: a CPU time is not a device time.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

H, I, K, E_ALL, E_HELD, LAYERS = 6144, 2048, 8, 128, 16, 2


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None)
    ap.add_argument("--tokens", type=int, nargs="+", default=[16, 1024, 4096, 8192])
    ap.add_argument("--chunk", type=int, nargs="+", default=[None])
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, args.tree or str(Path(__file__).resolve().parents[1]))
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import moe

    if jax.default_backend() != "tpu":
        print("moe_walk_bench: no TPU", file=sys.stderr)
        return 1
    keys = jax.random.split(jax.random.PRNGKey(41), 8)
    banks = [
        (jax.random.normal(kk, (LAYERS, E_HELD, *shape), jnp.bfloat16) / 64, jnp.int32(1))
        for kk, shape in zip(keys[:3], ((H, I), (H, I), (I, H)))
    ]
    run = jax.jit(lambda x, ids, probs, *b: moe.moe_experts(
        x, ids, probs, *[(b[i], b[i + 1]) for i in (0, 2, 4)], first_expert=32,
    ))
    flat = [a for bank in banks for a in bank]
    chunks = args.chunk if hasattr(moe, "CHUNK_ROWS") else [None]
    for t in args.tokens:
        x = jax.random.normal(keys[3], (t, H), jnp.bfloat16)
        probs = jax.nn.softmax(jax.random.normal(keys[4], (t, K)), axis=-1)
        scores = jax.random.uniform(keys[5], (t, E_ALL))
        routings = {
            "even": jax.lax.top_k(scores, K)[1],
            "all_held": 32 + jax.lax.top_k(scores[:, :E_HELD], K)[1],
        }
        for chunk in chunks:
            if chunk is not None:
                moe.CHUNK_ROWS = chunk
                run.clear_cache()
            for name, ids in routings.items():
                ids = ids.astype(jnp.int32)
                out, stats = jax.block_until_ready(run(x, ids, probs, *flat))
                times = []
                for _ in range(args.iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(x, ids, probs, *flat))
                    times.append(time.perf_counter() - t0)
                print(json.dumps({
                    "tree": args.tree or ".", "tokens": t, "routing": name,
                    "chunk": getattr(moe, "CHUNK_ROWS", None),
                    "ms": round(statistics.median(times) * 1e3, 4),
                    "stats": dict(zip(moe.MOE_STATS, stats.tolist())),
                    "device": jax.devices()[0].device_kind,
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
