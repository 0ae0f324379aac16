"""Kernel autotuner for the ragged packed attention family.

The ragged kernels copy exactly the live pages of each token block, a KV
step of ``kv_step_pages`` of them at a time
(ops/pallas/ragged_attention.py), so one tunable is left:

- ``tb_tokens`` — the largest token block of the packed ragged kernel
  (default ``default_tb_tokens``: what keeps one KV head's score rows at
  256, at most 64): a larger block copies a prompt span's pages fewer
  times, a smaller one wastes fewer masked rows on the pages of packed
  decode lanes.

This module sweeps it per **(model geometry, device_kind, dtype)** key.
On CPU the sweep is scored by a deterministic cost model over the REAL
host packer (``pack_spans`` builds the span lists for two synthetic
windows, so the step counts are exact) with per-block / per-step /
per-score-tile prices read off a TPU v5e (PERF.md section 6, PR 37); on TPU
``scripts/tpu_validate.py --bench`` passes a wall-clock ``runner`` and the
winner is measured, not modeled.  Winners persist as provenance-stamped
rows in ``KERNEL_PERF.json`` (same table the calibration benches write);
the engine resolves them at init with the precedence **explicit knob >
row measured on this device kind > the default from the head geometry**
(a cost-model row is a record, it binds nothing).

Row schema (version 3)::

    {"bench": "autotune_ragged", "geometry": "h4kv2d64-bs4-l4-mb16",
     "device_kind": "any" | "<jax device_kind>", "dtype": "float32",
     "source": "cost_model" | "measured", "version": 3,
     "tb_tokens": 16, "cost": 123.4, "swept": 5}

``source="cost_model"`` rows are stamped ``device_kind="any"`` and are
never resolved; ``source="measured"`` rows are only trusted for the device
kind that produced them.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

RAGGED_BENCH = "autotune_ragged"
SCHEMA_VERSION = 3

# cost-model prices in nanoseconds, fitted to the kernel alone on a TPU v5e at
# h32 kv8 d128, block 16, bf16, token blocks of 16-64 and KV steps of 4-16
# pages over six windows (scripts/ragged_kernel_bench.py; PERF.md section 6,
# PR 37; 54 rows, the worst a third off): a token block's fixed cost, a KV
# step's (its copies' wait and the loop), and one KV head's score tile of
# 8 rows x 128 positions (both products and the softmax over it).  The rows
# are of the kernel with its head loop unrolled at trace time; as the loop
# it ships with, a step costs about 1.5 x (ROADMAP S4), which moves no
# winner: every candidate pays it alike.
_NS_BLOCK = 1300.0
_NS_STEP = 1000.0
_NS_TILE = 12.6


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The shape key the tuned parameters depend on: attention geometry,
    cache page size, and the engine's packing envelope (decode lanes and
    worst-case pages per lane)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    block_size: int
    lanes: int               # max_batch_size — decode lanes per window
    max_blocks_per_seq: int

    @property
    def key(self) -> str:
        return (
            f"h{self.num_heads}kv{self.num_kv_heads}d{self.head_dim}"
            f"-bs{self.block_size}-l{self.lanes}-mb{self.max_blocks_per_seq}"
        )


def _synthetic_workloads(geom: Geometry, tb: int):
    """Deterministic (token_lane, token_pos) windows the cost model scores.
    A unified window always carries a prompt span (decode-only iterations
    run the decode program), so both do: a whole prompt of a quarter of the
    context beside the other lanes decoding mid-stream, and a 2-page chunk
    beside them.  Derived purely from the geometry — no RNG, no wall
    clock."""
    lanes = geom.lanes
    bs = geom.block_size
    mid = max(bs, (geom.max_blocks_per_seq * bs) // 2)

    def pad_to(arr, fill):
        t_pad = -(-len(arr) // tb) * tb
        out = np.full(t_pad, fill, np.int32)
        out[: len(arr)] = arr
        return out

    # decode lanes first, then the span (the engine's flat-axis order)
    d_lane = np.arange(1, lanes, dtype=np.int32)
    d_pos = np.array([mid - 1 + (i % bs) for i in range(1, lanes)], np.int32)
    windows = []
    for span in (max(2 * bs, mid // 2), 2 * bs):
        lane = np.concatenate([d_lane, np.zeros(span, np.int32)])
        pos = np.concatenate([d_pos, np.arange(span, dtype=np.int32)])
        windows.append((pad_to(lane, lanes), pad_to(pos, -1)))
    return windows


def _pack_stats(geom: Geometry, tb: int):
    """Run the real host packer over the synthetic windows; return
    per-window (token blocks, KV steps) pairs."""
    from dynamo_tpu.ops.pallas.ragged_attention import pack_spans

    stats = []
    for token_lane, token_pos in _synthetic_workloads(geom, tb):
        kv_steps = pack_spans(
            token_lane, token_pos, lanes=geom.lanes,
            tb_tokens=tb, block_size=geom.block_size,
        )[3]
        stats.append((kv_steps.shape[0], int(kv_steps.sum())))
    return stats


def cost_model(geom: Geometry, tb: int) -> float:
    """Deterministic score (modeled nanoseconds a layer, lower is better)
    for one token-block size."""
    from dynamo_tpu.ops.pallas.ragged_attention import kv_step_pages

    rows = tb * geom.num_heads // geom.num_kv_heads
    positions = kv_step_pages(geom.block_size) * geom.block_size
    tiles = geom.num_kv_heads * -(-rows // 8) * -(-positions // 128)
    per_step = _NS_STEP + _NS_TILE * tiles
    return float(sum(
        _NS_BLOCK * num_tb + per_step * steps
        for num_tb, steps in _pack_stats(geom, tb)
    ))


def candidate_grid(geom: Geometry, buckets: tuple[int, ...] = ()) -> list[dict]:
    """The swept tb_tokens candidates.  ``buckets`` (the engine's unified
    token buckets) constrain them: a tb that does not divide every bucket
    would force the split fallback, so it is never a valid winner."""
    from dynamo_tpu.ops.pallas.ragged_attention import default_tb_tokens

    default_tb = default_tb_tokens(
        geom.num_heads // geom.num_kv_heads, geom.block_size
    )
    return [
        {"tb_tokens": t}
        for t in sorted({1, 2, 4, 8, 16, 32, 64, default_tb})
        if t <= max(geom.lanes, default_tb)
        and all(b % t == 0 for b in buckets)
    ]


def sweep(
    geom: Geometry,
    *,
    dtype: str = "float32",
    buckets: tuple[int, ...] = (),
    runner=None,
    device_kind: str | None = None,
) -> dict:
    """Score every candidate and return the winner row (plus the swept
    grid under ``"grid"`` for bench reporting).  ``runner`` is an optional
    ``callable(candidate) -> wall_us | None`` — when present the sweep is
    *measured* and stamped with the real device kind; otherwise the
    deterministic cost model scores it (``device_kind="any"``)."""
    grid = candidate_grid(geom, buckets)
    if not grid:
        raise ValueError(f"no feasible candidates for {geom.key}")
    scored = []
    for cand in grid:
        if runner is not None:
            cost = runner(dict(cand))
        else:
            cost = cost_model(geom, cand["tb_tokens"])
        if cost is None:
            continue
        scored.append((float(cost), cand))
    if not scored:
        raise ValueError(f"no candidate survived the sweep for {geom.key}")
    scored.sort(key=lambda it: (it[0], sorted(it[1].items())))
    best_cost, best = scored[0]
    row = {
        "bench": RAGGED_BENCH,
        "geometry": geom.key,
        "device_kind": device_kind if runner is not None else "any",
        "dtype": str(dtype),
        "source": "measured" if runner is not None else "cost_model",
        "version": SCHEMA_VERSION,
        **best,
        "cost": round(best_cost, 3),
        "swept": len(grid),
    }
    row["grid"] = [
        {**cand, "cost": round(cost, 3)} for cost, cand in scored
    ]
    return row


# ------------------------------------------------------------ persistence


def _row_key(row: dict) -> tuple:
    return (
        row.get("bench"), row.get("geometry"), row.get("device_kind"),
        row.get("dtype"), row.get("source"), row.get("version"),
    )


def load_table(path) -> dict:
    """Read a KERNEL_PERF-format table ({header..., "rows": [...]}) or
    return an empty shell when the file does not exist / fails to parse."""
    try:
        with open(path) as fh:
            table = json.load(fh)
    except (OSError, ValueError):
        return {"rows": []}
    if not isinstance(table, dict):
        return {"rows": []}
    table.setdefault("rows", [])
    return table


def tune(
    path,
    geom: Geometry,
    *,
    dtype: str = "float32",
    buckets: tuple[int, ...] = (),
    runner=None,
    device_kind: str | None = None,
) -> tuple[dict, bool]:
    """Sweep-or-load: return ``(row, cached)``.  An existing row for the
    same (bench, geometry, device_kind, dtype, source, version) key is a
    cache hit — the file is not touched and no sweep runs.  Otherwise the
    winner is upserted into ``path`` (header and unrelated rows are
    preserved)."""
    source = "measured" if runner is not None else "cost_model"
    kind = device_kind if runner is not None else "any"
    probe = {
        "bench": RAGGED_BENCH, "geometry": geom.key, "device_kind": kind,
        "dtype": str(dtype), "source": source, "version": SCHEMA_VERSION,
    }
    table = load_table(path)
    for row in table["rows"]:
        if _row_key(row) == _row_key(probe):
            return row, True
    row = sweep(
        geom, dtype=dtype, buckets=buckets, runner=runner,
        device_kind=device_kind,
    )
    row = {k: v for k, v in row.items() if k != "grid"}
    table["rows"] = [
        r for r in table["rows"] if _row_key(r) != _row_key(row)
    ] + [row]
    tmp = f"{path}.tmp"
    with open(tmp, "w") as fh:
        json.dump(table, fh, indent=1)
        fh.write("\n")
    os.replace(tmp, path)
    return row, False


def resolve(
    table: dict,
    *,
    geometry_key: str,
    device_kind: str | None,
    dtype: str,
    bench: str = RAGGED_BENCH,
) -> dict | None:
    """Pick the tuned row for a geometry: a row MEASURED on this exact
    device kind.  A cost-model row (``device_kind="any"``) is a record of
    what the model prefers on its synthetic windows and binds nothing: the
    default the kernel derives from the head geometry was measured on the
    chip at the cells' windows (PERF.md section 6, PR 37), and a guess does
    not outrank it.  Rows for other devices, dtypes, or schema versions
    never match."""
    rows = [
        r for r in table.get("rows", ())
        if r.get("bench") == bench
        and r.get("geometry") == geometry_key
        and r.get("dtype") == str(dtype)
        and r.get("version") == SCHEMA_VERSION
        and "tb_tokens" in r
    ]
    measured = [
        r for r in rows
        if r.get("source") == "measured"
        and device_kind is not None
        and r.get("device_kind") == device_kind
    ]
    return measured[0] if measured else None
