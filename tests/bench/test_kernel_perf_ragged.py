"""KERNEL_PERF.json regression diff for the packed-lane ragged kernel.

The ``ragged_packed_decode`` rows record what dense lane packing buys over
the padded per-lane-block layout.  The packing fields (``blocks_packed`` /
``blocks_padded`` / ``block_reduction``) are host-side facts computed by
``pack_spans``' layout math — hardware-independent, so tier-1 can gate
on them on any box: if a change to the packer or the engine's flat-axis
layout silently regresses the block count, the recomputed layout here stops
matching the artifact and this test fails.  Timing fields are advisory
(interpret-mode rows are labeled; only real-hardware rows would gate
speed)."""

import json
from pathlib import Path

ARTIFACT = Path(__file__).parent.parent.parent / "KERNEL_PERF.json"


def _ragged_rows():
    rows = [
        r for r in json.loads(ARTIFACT.read_text())["rows"]
        if r.get("bench") == "ragged_packed_decode"
    ]
    assert rows, "KERNEL_PERF.json lost its ragged_packed_decode rows"
    return rows


def test_kernel_perf_has_packed_lane_rows():
    rows = _ragged_rows()
    # the headline decode-heavy geometry must be present: 16 single-token
    # lanes in one window
    assert any(r["lanes"] == 16 for r in rows)
    for r in rows:
        for key in ("lanes", "ctx", "tb_tokens", "blocks_packed",
                    "blocks_padded", "block_reduction", "packed_us",
                    "padded_us", "packed_speedup"):
            assert key in r, (key, r)


def test_packed_layout_block_reduction_holds():
    """The acceptance floor: a 16-lane decode-heavy window must pack into
    at least 4x fewer token blocks than the padded layout (at tb=8 it is
    exactly 8x), and packed must never dispatch MORE blocks than padded."""
    for r in _ragged_rows():
        assert r["blocks_packed"] <= r["blocks_padded"], r
        if r["lanes"] >= 16:
            assert r["block_reduction"] >= 4.0, r


def test_artifact_matches_packer_layout_math():
    """Regression diff proper: recompute each row's packing from the same
    layout rule the bench (and the engine's _run_unified) uses and diff it
    against the artifact — a packer change that alters the layout must come
    with a refreshed KERNEL_PERF.json."""
    for r in _ragged_rows():
        lanes, tb = r["lanes"], r["tb_tokens"]
        packed = -(-lanes // tb)   # dense: lanes share blocks
        padded = lanes             # one mostly-empty block per lane
        assert r["blocks_packed"] == packed, r
        assert r["blocks_padded"] == padded, r
        assert r["block_reduction"] == round(padded / packed, 2), r


def test_bench_path_reproduces_rows_in_interpret_mode():
    """The bench function itself stays runnable and emits rows whose
    packing fields agree with the artifact's layout math (tiny interpret
    geometry — timings ignored)."""
    import sys

    sys.path.insert(0, str(Path(__file__).parent.parent.parent / "scripts"))
    import tpu_validate

    tpu_validate.INTERPRET = True
    rows = tpu_validate.bench_ragged_packed(1)
    assert {r["lanes"] for r in rows} >= {8, 16}
    for r in rows:
        assert r["blocks_packed"] == -(-r["lanes"] // r["tb_tokens"])
        assert r["blocks_padded"] == r["lanes"]
        assert r["packed_us"] > 0 and r["padded_us"] > 0


def test_artifact_autotune_rows_match_cost_model():
    """Ratchet for tuned rows: every committed ``autotune_ragged``
    cost-model row must be exactly what ops/autotune.py's deterministic
    sweep produces for its geometry today — a cost-model or packer change
    that moves a winner must ship a regenerated KERNEL_PERF.json."""
    import re

    from dynamo_tpu.ops import autotune

    rows = [
        r for r in json.loads(ARTIFACT.read_text())["rows"]
        if r.get("bench") == autotune.RAGGED_BENCH
        and r.get("source") == "cost_model"
    ]
    assert rows, "KERNEL_PERF.json lost its autotune_ragged rows"
    # the committed set must cover the tiny tier-1 geometry AND a
    # headline serving geometry
    keys = {r["geometry"] for r in rows}
    assert "h4kv2d16-bs4-l4-mb32" in keys
    assert any(k.startswith("h32") for k in keys)
    pat = re.compile(r"h(\d+)kv(\d+)d(\d+)-bs(\d+)-l(\d+)-mb(\d+)")
    for r in rows:
        assert r["device_kind"] == "any", r       # cost model is chip-blind
        assert r["version"] == autotune.SCHEMA_VERSION, r
        h, kvh, d, bs, lanes, mb = map(int, pat.fullmatch(r["geometry"]).groups())
        geom = autotune.Geometry(
            num_heads=h, num_kv_heads=kvh, head_dim=d,
            block_size=bs, lanes=lanes, max_blocks_per_seq=mb,
        )
        fresh = autotune.sweep(geom, dtype=r["dtype"])
        assert "page_slots" not in r and "pages_per_step" not in r, r
        for key in ("tb_tokens", "cost"):
            assert fresh[key] == r[key], (key, fresh[key], r)
