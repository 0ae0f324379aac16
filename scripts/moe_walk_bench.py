"""Time the expert layer ALONE on the chip (``ops/moe.py:moe_experts``) and
the grouped product inside it, at a routed configuration's widths (defaults:
``k-exaone-236b-l8``: hidden 6144, experts of 2048, 8 choices a token over 128
experts of which 16 are held, banks stacked over 2 layers).

    python scripts/moe_walk_bench.py [--tree DIR] [--tokens T ...] [--chunk C ...] [--routing R ...]
        [--hidden H --width I --experts E --held E_HELD --choices K --layers L]
        [--tiling rule tm,tk,tn tm,tk,tn/tm,tk,tn ...]

The routed widths the tree's users run (PERF.md section 5 has their tables):

    moonlight-16b-l9   --hidden 2048 --width 1408 --experts 64 --held 64 --choices 6
    xing4-29b-l8       --hidden 3584 --width 1024 --experts 64 --held 64 --choices 4
    k-exaone-236b-l8   the defaults (16 of 128 held; both routings)
    mixtral-8x7b-l4    --hidden 4096 --width 14336 --experts 8 --held 8 --choices 2

Prints one JSON line per (tokens, chunk, tiling, routing): ``ms`` a call of
the layer (median of ``--iters`` timed calls, each ended by
block_until_ready), the layer's ``MOE_STATS``, ``combine``: how the tree
summed the walk's rows per token (``gather`` where ``rows_gathered`` counts
them: every expert of the router held; else ``scatter_add``; a tree is told
the router's width if it asks for it), and ``up_ms`` / ``down_ms``: one
grouped product over the walk's first chunk (``[rows, hidden] @ [hidden,
width]`` and back), timed ``REPS`` launches a call so the host's part of a
call is a sixteenth.  Routing ``even`` draws the choices among all experts;
``all_held`` among the held ones (every row live: what a chip that holds all
its experts sees; the same as ``even`` where all are held, and then left
out).  ``--tiling``: ``rule`` is what the tree's ``ops/moe.py`` chooses;
``tm,tk,tn`` puts that tiling in the rule's place for all three products
(``tk`` and ``tn`` clipped to the product's depth and width), ``a/b`` gives
the up products ``a`` and the down product ``b``.  The override exists here
only: it patches ``moe.gmm_tiling`` and ``moe.tile_rows``, the library has
no flag.  ``--tree DIR`` times another checkout's expert layer on the same
inputs (the parent commit's: one process a tree, both in one chip call);
``--chunk`` sets this tree's ``CHUNK_ROWS``, and a tree without the rule runs
``rule`` alone.  Exits 1 off the TPU: a CPU time is not a device time.
"""

from __future__ import annotations

import argparse
import inspect
import json
import statistics
import sys
import time
from pathlib import Path

REPS = 16


def _override(spec: str, hidden: int):
    """``moe.gmm_tiling`` / ``moe.tile_rows`` that return ``spec``'s tiles."""
    up, _, down = spec.partition("/")
    up = tuple(int(v) for v in up.split(","))
    down = tuple(int(v) for v in down.split(",")) if down else up
    assert up[0] == down[0], "one row tile for the three products (rows_multiplied counts by it)"

    def gmm_tiling(m, k, n, itemsize):
        tm, tk, tn = up if k == hidden else down
        return tm, min(tk, k), min(tn, n)

    return gmm_tiling, lambda m: up[0]


def _median_ms(fn, iters: int) -> float:
    import jax

    jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None)
    ap.add_argument("--tokens", type=int, nargs="+", default=[16, 1024, 4096, 8192])
    ap.add_argument("--chunk", type=int, nargs="+", default=[None])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--hidden", type=int, default=6144)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--choices", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--tiling", nargs="+", default=["rule"])
    ap.add_argument("--routing", nargs="+", default=["even", "all_held"], choices=["even", "all_held"])
    args = ap.parse_args()
    sys.path.insert(0, args.tree or str(Path(__file__).resolve().parents[1]))
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import moe

    if jax.default_backend() != "tpu":
        print("moe_walk_bench: no TPU", file=sys.stderr)
        return 1
    h, width, k, e_all, e_held, layers = (
        args.hidden, args.width, args.choices, args.experts, args.held, args.layers)
    first = min(32, e_all - e_held)
    keys = jax.random.split(jax.random.PRNGKey(41), 8)
    banks = [
        jax.random.normal(kk, (layers, e_held, *shape), jnp.bfloat16) / 64
        for kk, shape in zip(keys[:3], ((h, width), (h, width), (width, h)))
    ]
    has_rule = hasattr(moe, "gmm_tiling")
    rule = (moe.gmm_tiling, moe.tile_rows) if has_rule else None

    # (the parent of PR 56 takes no router's width: every tree scatter-added)
    told = ({"experts_routed": e_all}
            if "experts_routed" in inspect.signature(moe.moe_experts).parameters else {})

    def layer(x, ids, probs, *b):
        return moe.moe_experts(
            x, ids, probs, *[(bank, jnp.int32(1)) for bank in b], first_expert=first, **told)

    def products(rows, bank, sizes):
        """``REPS`` launches of one product, a layer's banks in turn."""

        def body(i, acc):
            out = moe.grouped_matmul(rows, (bank, i % layers), sizes)
            return acc + out[0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, REPS, body, jnp.float32(0))

    def measure(t, x, ids, probs):
        """One line's readings: the layer, then each product over the walk's
        first chunk (each held expert's rows clipped to it)."""
        run = jax.jit(lambda *a: layer(*a))     # a trace of its own a tiling
        _, stats = jax.block_until_ready(run(x, ids, probs, *banks))
        line = {
            "ms": round(_median_ms(lambda: run(x, ids, probs, *banks), args.iters), 4),
            "stats": dict(zip(moe.MOE_STATS, stats.tolist())),
        }
        line["combine"] = "gather" if line["stats"].get("rows_gathered") else "scatter_add"
        c = min(t * k, getattr(moe, "CHUNK_ROWS", t * k))
        local = ids.reshape(-1) - first
        sizes = jnp.sum(local[:, None] == jnp.arange(e_held), axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        sizes = jnp.clip(ends, 0, c) - jnp.clip(ends - sizes, 0, c)
        for which, bank, rows in (
            ("up", banks[0], x[jnp.arange(c) % t]),
            ("down", banks[2], jnp.tile(x, (1, -(-width // h)))[jnp.arange(c) % t, :width]),
        ):
            shot = jax.jit(lambda *a: products(*a))
            line[f"{which}_ms"] = round(
                _median_ms(lambda: shot(rows, bank, sizes), args.iters) / REPS, 4)
            if has_rule:
                line[f"{which}_tiling"] = moe.gmm_tiling(c, *bank.shape[2:], 2)
        line["chunk_rows_live"] = int(jnp.sum(sizes))
        return line

    chunks = args.chunk if hasattr(moe, "CHUNK_ROWS") else [None]
    for t in args.tokens:
        x = jax.random.normal(keys[3], (t, h), jnp.bfloat16)
        probs = jax.nn.softmax(jax.random.normal(keys[4], (t, k)), axis=-1)
        scores = jax.random.uniform(keys[5], (t, e_all))
        routings = {"even": jax.lax.top_k(scores, k)[1]}
        if e_held < e_all:
            routings["all_held"] = first + jax.lax.top_k(scores[:, :e_held], k)[1]
        routings = {name: ids for name, ids in routings.items() if name in args.routing}
        for chunk in chunks:
            if chunk is not None:
                moe.CHUNK_ROWS = chunk
            for spec in args.tiling if has_rule else ["rule"]:
                if has_rule:
                    moe.gmm_tiling, moe.tile_rows = rule if spec == "rule" else _override(spec, h)
                for name, ids in routings.items():
                    try:
                        line = measure(t, x, ids.astype(jnp.int32), probs)
                    except Exception as exc:  # noqa: BLE001 - a tiling the chip's compiler refuses
                        line = {"error": " ".join(str(exc).split())[:300]}
                    print(json.dumps({
                        "tree": args.tree or ".", "tokens": t, "routing": name,
                        "chunk": getattr(moe, "CHUNK_ROWS", None), "tiling": spec, **line,
                        "device": jax.devices()[0].device_kind,
                    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
