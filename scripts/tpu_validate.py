"""Kernel validation: compile + run every Pallas kernel and the
quantized/fp8 paths on tiny shapes against their XLA twins, printing one
JSON line per check.  Each check is independent; a failed check does not
stop later ones and makes the exit code non-zero.

Usage:  python scripts/tpu_validate.py            # on the chip: real Mosaic
        JAX_PLATFORMS=cpu python scripts/...      # CPU: interpret mode, a
                                                  # correctness check only
        python scripts/tpu_validate.py --bench [--out FILE]
            # kernel microbenchmarks: Pallas paged attention vs the XLA
            # gather twin, gather_blocks vs fancy indexing, the ragged
            # kernel's autotune sweep — per-shape us/iter + effective GB/s.
            # A DEVICE measurement: needs a TPU and exits non-zero without
            # one, or when its own calibration rows exceed the chip's
            # published peaks (then no row is trustworthy and nothing is
            # written).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check(name):
    def wrap(fn):
        CHECKS.append((name, fn))
        return fn

    return wrap


CHECKS: list = []
INTERPRET = False  # set in main(): True off-TPU (Mosaic needs real hardware)


@check("paged_attention_gqa")
def _gqa():
    import jax, jax.numpy as jnp, numpy as np  # noqa: E401

    from dynamo_tpu.ops.attention import paged_decode_attention
    from dynamo_tpu.ops.pallas import paged_attention_decode

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, 8, (2, 4)), jnp.int32)
    ctx = jnp.asarray([13, 7], jnp.int32)
    out = np.asarray(paged_attention_decode(q, k, v, tables, ctx, interpret=INTERPRET))
    ref = np.asarray(paged_decode_attention(q, k, v, tables, ctx))
    rel = float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-9))
    assert rel < 0.05, rel
    return {"rel": round(rel, 5)}


@check("paged_window_attention")
def _window():
    import jax.numpy as jnp, numpy as np  # noqa: E401

    from dynamo_tpu.ops.attention import paged_window_attention
    from dynamo_tpu.ops.pallas import paged_window_attention_decode

    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((2, 3, 8, 128)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, 8, (2, 4)), jnp.int32)
    ctx = jnp.asarray([15, 9], jnp.int32)
    out = np.asarray(paged_window_attention_decode(q, k, v, tables, ctx, interpret=INTERPRET))
    ref = np.asarray(paged_window_attention(q, k, v, tables, ctx))
    rel = float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-9))
    assert rel < 0.05, rel
    return {"rel": round(rel, 5)}


@check("mla_kernels")
def _mla():
    import jax.numpy as jnp, numpy as np  # noqa: E401

    from dynamo_tpu.ops.pallas.mla_attention import (
        mla_paged_attention_decode,
        mla_paged_window_attention_decode,
    )

    rng = np.random.default_rng(2)
    ck = jnp.asarray(rng.standard_normal((8, 8, 128)), jnp.bfloat16)
    kr = jnp.asarray(rng.standard_normal((8, 8, 64)), jnp.bfloat16)
    q_lat = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.bfloat16)
    q_rope = jnp.asarray(rng.standard_normal((2, 4, 64)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, 8, (2, 3)), jnp.int32)
    ctx = jnp.asarray([10, 6], jnp.int32)
    out = mla_paged_attention_decode(q_lat, q_rope, ck, kr, tables, ctx, scale=0.07, interpret=INTERPRET)
    assert np.isfinite(np.asarray(out)).all()
    q_lat_w = jnp.asarray(rng.standard_normal((2, 2, 4, 128)), jnp.bfloat16)
    q_rope_w = jnp.asarray(rng.standard_normal((2, 2, 4, 64)), jnp.bfloat16)
    out_w = mla_paged_window_attention_decode(
        q_lat_w, q_rope_w, ck, kr, tables, ctx + 1, scale=0.07, interpret=INTERPRET
    )
    assert np.isfinite(np.asarray(out_w)).all()
    return {}


@check("block_copy")
def _copy():
    import jax.numpy as jnp, numpy as np  # noqa: E401

    from dynamo_tpu.ops.pallas import gather_blocks, scatter_blocks

    pool = jnp.arange(8 * 8 * 128, dtype=jnp.bfloat16).reshape(8, 8, 128)
    ids = jnp.asarray([3, 1, 6], jnp.int32)
    g = gather_blocks(pool, ids, interpret=INTERPRET)
    out = scatter_blocks(jnp.zeros_like(pool), g, jnp.asarray([0, 4, 7], jnp.int32), interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(pool[3]))
    return {}


@check("int8_matmul")
def _int8():
    import jax, jax.numpy as jnp, numpy as np  # noqa: E401

    from dynamo_tpu.ops.quant import mm, quantize_matrix

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((128, 512)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((512, 256)) * 0.05, jnp.float32)
    qm = quantize_matrix(w)
    t0 = time.monotonic()
    out = np.asarray(jax.jit(mm)(x, qm))
    ref = np.asarray(x.astype(jnp.float32) @ w)
    rel = float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-9))
    assert rel < 0.05, rel
    return {"rel": round(rel, 4), "s": round(time.monotonic() - t0, 2)}


@check("fp8_cache_ops")
def _fp8():
    import jax.numpy as jnp, numpy as np  # noqa: E401

    from dynamo_tpu.ops.attention import paged_decode_attention, write_decode_kv
    from dynamo_tpu.ops.pallas import paged_attention_decode

    fp8 = jnp.dtype("float8_e4m3fn")
    rng = np.random.default_rng(4)
    k = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.float32).astype(fp8)
    v = jnp.asarray(rng.standard_normal((8, 8, 2, 128)), jnp.float32).astype(fp8)
    k2, v2 = write_decode_kv(
        k, v, jnp.ones((1, 2, 128), jnp.float32), jnp.ones((1, 2, 128), jnp.float32),
        jnp.asarray([5], jnp.int32),
    )
    assert k2.dtype == fp8
    q = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, 8, (2, 4)), jnp.int32)
    ctx = jnp.asarray([13, 7], jnp.int32)
    out = np.asarray(paged_attention_decode(q, k2, v2, tables, ctx, interpret=INTERPRET))
    ref = np.asarray(paged_decode_attention(q, k2, v2, tables, ctx))
    rel = float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-9))
    assert rel < 0.08, rel
    return {"rel": round(rel, 4)}


# ---------------------------------------------------------------------------
# kernel microbenchmarks (--bench)
# ---------------------------------------------------------------------------


def _time_us(fn, *args, iters: int, chain=None) -> float:
    """Median-of-3 timing of ``iters`` dispatches (one final sync), after a
    warmup call that eats the compile.

    ``chain(args, out) -> args`` feeds each iteration's output back into the
    next iteration's inputs: back-to-back *identical* dispatches can be
    overlapped below us, and a first version of this timer reported 8,300
    TFLOP/s on a 197 TFLOP/s chip.  A data dependency between iterations
    serializes them.

    The end-of-loop sync is a HOST READBACK of one element of the final
    output, which transitively waits on the whole dependent chain; the
    calibration rows (bench_calibration) verify the resulting ceiling, and
    run_bench refuses to write a table when they exceed the chip's peaks."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    def sync(out):
        leaf = out[0] if isinstance(out, tuple) else jax.tree.leaves(out)[0]
        return float(jnp.ravel(leaf)[0])  # device slice + scalar fetch

    sync(fn(*args))  # compile + warm
    samples = []
    for _ in range(3):
        a = args
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*a)
            if chain is not None:
                a = chain(a, out)
        sync(out)
        samples.append((time.perf_counter() - t0) / iters)
    return sorted(samples)[1] * 1e6


def bench_attention(iters: int) -> list[dict]:
    """Pallas paged-attention decode vs the XLA gather fallback — the
    measurement behind engine.py's attention_impl="auto" choice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.attention import paged_decode_attention
    from dynamo_tpu.ops.pallas import paged_attention_decode

    rows = []
    # (batch, ctx) — decode-regime shapes bracketing the headline geometry
    # (ISL 3000, batch 16, 8B-class heads) plus the high-batch / long-ctx
    # corner where the kernel's page-skipping matters.  Interpret mode
    # (off-TPU) runs a token small set: placeholders, never consulted.
    shapes = (
        ((2, 128),)
        if INTERPRET
        else ((4, 1024), (16, 1024), (16, 3072), (32, 2048), (64, 1024))
    )
    for batch, ctx in shapes:
        kvh, d, bs = 8, 128, 16
        nblocks_seq = (ctx + bs - 1) // bs
        pool = batch * nblocks_seq + 8
        rng = np.random.default_rng(0)
        k = jnp.asarray(rng.standard_normal((pool, bs, kvh, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((pool, bs, kvh, d)), jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((batch, 32, d)), jnp.bfloat16)
        tables = jnp.asarray(
            rng.permutation(pool)[: batch * nblocks_seq].reshape(batch, nblocks_seq),
            jnp.int32,
        )
        ctx_lens = jnp.full((batch,), ctx, jnp.int32)

        pallas_fn = jax.jit(
            lambda q, k, v, t, c: paged_attention_decode(
                q, k, v, t, c, interpret=INTERPRET
            )
        )
        xla_fn = jax.jit(paged_decode_attention)
        # serialize iterations by feeding the output (same shape/dtype as q,
        # values bounded — a convex combination of v) back in as the query
        chain = lambda a, out: (out,) + a[1:]  # noqa: E731
        us_p = _time_us(pallas_fn, q, k, v, tables, ctx_lens, iters=iters,
                        chain=chain)
        us_x = _time_us(xla_fn, q, k, v, tables, ctx_lens, iters=iters,
                        chain=chain)
        # effective bandwidth: every decode step streams the context's K+V
        bytes_kv = 2 * batch * ctx * kvh * d * 2  # bf16
        rows.append(
            {
                "bench": "paged_attention_decode",
                "batch": batch,
                "ctx": ctx,
                "pallas_us": round(us_p, 1),
                "xla_us": round(us_x, 1),
                "pallas_gbps": round(bytes_kv / us_p / 1e3, 1),
                "xla_gbps": round(bytes_kv / us_x / 1e3, 1),
                "pallas_speedup": round(us_x / us_p, 3),
            }
        )
    return rows


def bench_block_copy(iters: int) -> list[dict]:
    """gather_blocks (Pallas) vs XLA fancy indexing — the extract path of
    KV transfer/offload (engine._jit_extract uses the XLA form today)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas import gather_blocks

    rows = []
    for n_gather in (4,) if INTERPRET else (16, 64, 256):
        pool_n, bs, kvh, d = (64, 16, 8, 128) if INTERPRET else (2048, 16, 8, 128)
        rng = np.random.default_rng(1)
        pool = jnp.asarray(
            rng.standard_normal((pool_n, bs, kvh, d)), jnp.bfloat16
        )
        ids = jnp.asarray(rng.permutation(pool_n)[:n_gather], jnp.int32)

        # each iteration gathers a different (data-dependently derived) id
        # set so repeat dispatches can't be elided — see _time_us
        def _next_ids(i, g):
            bump = 1 + jnp.int32(jnp.abs(g[0, 0, 0, 0].astype(jnp.float32)) < 0)
            return (i + bump) % pool_n

        pallas_fn = jax.jit(
            lambda p, i: (g := gather_blocks(p, i, interpret=INTERPRET),
                          _next_ids(i, g))
        )
        xla_fn = jax.jit(lambda p, i: (g := p[i], _next_ids(i, g)))
        chain = lambda a, out: (a[0], out[1])  # noqa: E731
        us_p = _time_us(pallas_fn, pool, ids, iters=iters, chain=chain)
        us_x = _time_us(xla_fn, pool, ids, iters=iters, chain=chain)
        bytes_moved = n_gather * bs * kvh * d * 2 * 2  # read + write, bf16
        rows.append(
            {
                "bench": "gather_blocks",
                "n_blocks": n_gather,
                "pallas_us": round(us_p, 1),
                "xla_us": round(us_x, 1),
                "pallas_gbps": round(bytes_moved / us_p / 1e3, 1),
                "xla_gbps": round(bytes_moved / us_x / 1e3, 1),
                "pallas_speedup": round(us_x / us_p, 3),
            }
        )
    return rows


def bench_ragged_packed(iters: int) -> list[dict]:
    """Packed decode lanes vs the padded per-lane-block layout, through the
    SAME ragged kernel — the measurement behind the unified step's dense
    packing.  A decode-heavy window of N single-token lanes used to burn N
    mostly-empty token blocks (each lane padded to its own block); per-row
    lane routing packs them into ceil(N/tb) blocks.  blocks_* and
    block_reduction are host-side packing facts (hardware-independent —
    the tier-1 regression diff gates on them); the timings are only
    meaningful compiled on real hardware."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas import pack_spans, ragged_paged_attention

    rows = []
    tb = 8
    # decode-heavy windows: every lane one token at the context tail
    shapes = (
        ((8, 32), (16, 32)) if INTERPRET else ((8, 1024), (16, 1024), (16, 3072))
    )
    qh, kvh, d = (4, 2, 128) if INTERPRET else (32, 8, 128)
    bs = 8 if INTERPRET else 16
    for lanes, ctx in shapes:
        nblocks_seq = (ctx + bs - 1) // bs
        pool = lanes * nblocks_seq + 8
        rng = np.random.default_rng(0)
        k = jnp.asarray(rng.standard_normal((pool, bs, kvh, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((pool, bs, kvh, d)), jnp.bfloat16)
        tables = np.asarray(
            rng.permutation(pool)[: lanes * nblocks_seq].reshape(
                lanes, nblocks_seq
            ),
            np.int32,
        )

        def layout(packed: bool):
            # packed: lanes share token blocks densely; padded: each lane
            # rounds up to its own whole block (the pre-packing layout)
            t = -(-lanes // tb) * tb if packed else lanes * tb
            token_lane = np.full((t,), lanes, np.int32)
            token_pos = np.full((t,), -1, np.int32)
            for lane in range(lanes):
                row = lane if packed else lane * tb
                token_lane[row] = lane
                token_pos[row] = ctx - 1
            meta = pack_spans(
                token_lane, token_pos, lanes=lanes, tb_tokens=tb,
                block_size=bs,
            )
            q = jnp.asarray(
                rng.standard_normal((t, qh, d)), jnp.bfloat16
            )
            args = (q, k, v, jnp.asarray(token_lane), jnp.asarray(token_pos),
                    jnp.asarray(tables), *(jnp.asarray(a) for a in meta))
            return args, t // tb

        fn = jax.jit(
            lambda q, *rest: ragged_paged_attention(
                q, *rest, tb_tokens=tb, interpret=INTERPRET,
            ).astype(q.dtype)
        )
        chain = lambda a, out: (out,) + a[1:]  # noqa: E731
        args_packed, blocks_packed = layout(packed=True)
        args_padded, blocks_padded = layout(packed=False)
        us_packed = _time_us(fn, *args_packed, iters=iters, chain=chain)
        us_padded = _time_us(fn, *args_padded, iters=iters, chain=chain)
        rows.append(
            {
                "bench": "ragged_packed_decode",
                "lanes": lanes,
                "ctx": ctx,
                "tb_tokens": tb,
                "blocks_packed": blocks_packed,
                "blocks_padded": blocks_padded,
                "block_reduction": round(blocks_padded / blocks_packed, 2),
                "packed_us": round(us_packed, 1),
                "padded_us": round(us_padded, 1),
                "packed_speedup": round(us_padded / us_packed, 3),
            }
        )
    return rows


# the standard autotuned geometries: the tiny tier-1 test shape and the
# llama3-8b serving shape.  The cost-model rows for these are COMMITTED in
# KERNEL_PERF.json (tests/bench/test_kernel_perf_ragged.py ratchets them),
# and --out rewrites the whole table, so the bench must regenerate them.
AUTOTUNE_GEOMETRIES = (
    # (num_heads, num_kv_heads, head_dim, block_size, lanes,
    #  max_blocks_per_seq, dtypes, buckets)
    (4, 2, 16, 4, 4, 32, ("float32",), (16, 32, 64, 128)),
    (32, 8, 128, 16, 16, 256, ("float32", "bfloat16", "float8_e4m3fn"),
     (32, 64, 128, 256, 512, 1024, 2048, 4096)),
)


def bench_autotune(iters: int) -> list[dict]:
    """Ragged-kernel tunable sweep (ops/autotune.py): tb_tokens per
    geometry.  Off-TPU the deterministic
    cost model scores the grid (hardware-independent rows, device_kind=
    "any"); on real hardware each candidate is additionally WALL-CLOCK
    timed over the synthetic prompt window and the measured winner is
    stamped with this chip's device_kind.  The swept grid prints to
    stdout per candidate; only winner rows enter the table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import autotune
    from dynamo_tpu.ops.pallas import pack_spans, ragged_paged_attention

    dev = jax.devices()[0]
    rows = []
    for h, kvh, d, bs, lanes, mb, dtypes, buckets in AUTOTUNE_GEOMETRIES:
        geom = autotune.Geometry(
            num_heads=h, num_kv_heads=kvh, head_dim=d, block_size=bs,
            lanes=lanes, max_blocks_per_seq=mb,
        )
        for dtype in dtypes:
            # hardware-independent cost-model winner (always emitted: the
            # committed rows the tier-1 ratchet diffs must survive --out)
            modeled = autotune.sweep(geom, dtype=dtype, buckets=buckets)
            for cand in modeled.pop("grid"):
                print(json.dumps({"bench": "autotune_grid",
                                  "geometry": geom.key, "dtype": dtype,
                                  "source": "cost_model", **cand}))
            rows.append(modeled)
        if INTERPRET:
            continue  # interpret wall clocks say nothing about hardware

        # measured sweep at the serving dtype: time the compiled kernel on
        # this chip over the synthetic prompt window
        jdt = jnp.bfloat16
        rng = np.random.default_rng(0)
        pool = lanes * mb + 8
        k = jnp.asarray(rng.standard_normal((pool, bs, kvh, d)), jdt)
        v = jnp.asarray(rng.standard_normal((pool, bs, kvh, d)), jdt)

        bt = jnp.asarray(
            rng.permutation(pool)[: lanes * mb].reshape(lanes, mb), jnp.int32
        )

        def runner(cand):
            tb = cand["tb_tokens"]
            token_lane, token_pos = autotune._synthetic_workloads(geom, tb)[0]
            meta = pack_spans(
                token_lane, token_pos, lanes=lanes, tb_tokens=tb,
                block_size=bs,
            )
            q = jnp.asarray(
                rng.standard_normal((token_lane.shape[0], h, d)), jdt
            )
            fn = jax.jit(
                lambda q, *rest: ragged_paged_attention(
                    q, *rest, tb_tokens=tb, interpret=INTERPRET,
                ).astype(q.dtype)
            )
            chain = lambda a, out: (out,) + a[1:]  # noqa: E731
            us = _time_us(
                fn, q, k, v,
                jnp.asarray(token_lane), jnp.asarray(token_pos), bt,
                *(jnp.asarray(a) for a in meta),
                iters=iters, chain=chain,
            )
            print(json.dumps({"bench": "autotune_grid",
                              "geometry": geom.key, "dtype": "bfloat16",
                              "source": "measured", **cand,
                              "us": round(us, 1)}))
            return us

        measured = autotune.sweep(
            geom, dtype="bfloat16", buckets=buckets, runner=runner,
            device_kind=dev.device_kind,
        )
        measured.pop("grid")
        rows.append(measured)
    return rows


def bench_calibration(iters: int) -> list[dict]:
    """Self-check rows proving the timing methodology: a dependent-chain
    matmul with known FLOPs and a dependent-chain stream with known bytes.
    If achieved TFLOP/s or GB/s exceed the chip's public peaks (v5e:
    ~197 TFLOP/s bf16, ~0.82 TB/s HBM), every other row is suspect."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows = []
    rng = np.random.default_rng(2)
    n = 256 if INTERPRET else 4096
    x = jnp.asarray(rng.standard_normal((n, n)) * 0.01, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((n, n)) * 0.01, jnp.bfloat16)
    mm = jax.jit(lambda x, w: (x @ w) * jnp.bfloat16(0.1))
    us = _time_us(mm, x, w, iters=iters, chain=lambda a, o: (o, a[1]))
    rows.append({
        "bench": "calib_matmul", "n": n, "us": round(us, 1),
        "tflops": round(2 * n**3 / us / 1e6, 1),
    })

    m = 1 << 14 if INTERPRET else 1 << 27  # 128M bf16 elements = 256MB buffer
    a = jnp.ones((m,), jnp.bfloat16)
    # constant must be bf16-representable and != 1.0 or XLA folds the mul
    # to identity and no memory moves (1.00390625 = next bf16 above 1)
    scale = jax.jit(lambda a: a * jnp.bfloat16(1.00390625))
    us = _time_us(scale, a, iters=max(2, iters // 4),
                  chain=lambda args, o: (o,))
    rows.append({
        "bench": "calib_stream", "mb": m * 2 // 2**20, "us": round(us, 1),
        # read + write
        "gbps": round(2 * m * 2 / us / 1e3, 1),
    })
    return rows


def run_bench(out_path: str | None) -> int:
    import jax

    from dynamo_tpu.observability.perf import device_peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"--bench is a device measurement and "
                          f"needs a TPU; found platform {dev.platform!r}"}))
        return 1
    flops_peak, bytes_peak = device_peaks(dev.device_kind)  # unknown: raises
    global INTERPRET
    INTERPRET = False
    iters = 50
    table = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "interpret": False,
        "note": (
            "compiled and timed on the device named above. "
            "autotune_ragged rows (ops/autotune.py schema v1) carry the "
            "tuned ragged-kernel configs keyed (geometry, device_kind, "
            "dtype): cost_model rows are chip-blind (device_kind=any), "
            "measured rows bind only on their exact device_kind; engine "
            "precedence is explicit DYN_AUTOTUNE_* knob > tuned row > "
            "heuristic default"
        ),
        "rows": [],
    }
    for fn in (bench_calibration, bench_attention, bench_block_copy,
               bench_ragged_packed, bench_autotune):
        try:
            rows = fn(iters)
        except Exception as exc:  # noqa: BLE001 — independent benches
            rows = [{"bench": fn.__name__, "ok": False,
                     "error": f"{type(exc).__name__}: {exc}"[:300]}]
        for row in rows:
            print(json.dumps(row))
            sys.stdout.flush()
        table["rows"].extend(rows)
    # Methodology gate: if the known-FLOPs/known-bytes calibration rows
    # exceed the chip's published peaks, the timing didn't serialize and NO
    # row in this table is trustworthy: fail, write nothing.
    calib_ok = True
    for row in table["rows"]:
        if row.get("bench") == "calib_matmul" and "tflops" in row:
            calib_ok &= row["tflops"] <= flops_peak / 1e12 * 1.15
        if row.get("bench") == "calib_stream" and "gbps" in row:
            calib_ok &= row["gbps"] <= bytes_peak / 1e9 * 1.25
    if not calib_ok:
        print(json.dumps({"error": "calibration rows exceed the device's "
                          "published peaks; no table written"}))
        return 1
    table["calib_ok"] = True
    if out_path:
        with open(out_path, "w") as f:
            json.dump(table, f, indent=2)
        print(json.dumps({"wrote": out_path}))
    return 0


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--bench", action="store_true",
                        help="kernel microbenchmarks instead of validation")
    parser.add_argument("--out", default=None,
                        help="write the kernel-perf table JSON here")
    args = parser.parse_args()

    import jax

    if args.bench:
        return run_bench(args.out)

    dev = jax.devices()[0]
    global INTERPRET
    INTERPRET = dev.platform != "tpu"
    print(json.dumps({"device": str(dev), "platform": dev.platform,
                      "interpret": INTERPRET}))
    failed = 0
    for name, fn in CHECKS:
        t0 = time.monotonic()
        try:
            extra = fn() or {}
            print(json.dumps({"check": name, "ok": True,
                              "s": round(time.monotonic() - t0, 1), **extra}))
        except Exception as exc:  # noqa: BLE001 — independent checks
            failed += 1
            print(json.dumps({"check": name, "ok": False,
                              "error": f"{type(exc).__name__}: {exc}"[:300]}))
        sys.stdout.flush()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
