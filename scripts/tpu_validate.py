"""Kernel validation: compile + run every Pallas kernel and the
quantized/fp8 paths on tiny shapes against their XLA twins, printing one
JSON line per check.  Each check is independent; a failed check does not
stop later ones and makes the exit code non-zero.

Usage:  python scripts/tpu_validate.py            # on the chip: real Mosaic
        JAX_PLATFORMS=cpu python scripts/...      # CPU: interpret mode, a
                                                  # correctness check only
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


def main() -> int:
    import jax

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
