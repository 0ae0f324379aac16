"""Kernel autotuner (ops/autotune.py): deterministic CPU sweeps, row
provenance, persistence cache hits, and the engine's resolution chain
(explicit knob > KERNEL_PERF.json row measured on this device kind >
the default from the head geometry; a cost-model row binds nothing).

Everything here is tier-1: the cost model runs the REAL host packer over
synthetic workloads — no wall clock, no RNG — so the same geometry always
produces the same winner on any box.
"""

import json

import jax
import pytest

from dynamo_tpu.ops import autotune


TINY = autotune.Geometry(
    num_heads=4, num_kv_heads=2, head_dim=16,
    block_size=4, lanes=4, max_blocks_per_seq=32,
)


def test_sweep_winner_is_deterministic_and_feasible():
    a = autotune.sweep(TINY, buckets=(16, 32, 64))
    b = autotune.sweep(TINY, buckets=(16, 32, 64))
    grid_a = a.pop("grid")
    b.pop("grid")
    assert a == b
    # provenance: a CPU sweep is a hardware-independent cost-model row
    assert a["bench"] == autotune.RAGGED_BENCH
    assert a["source"] == "cost_model"
    assert a["device_kind"] == "any"
    assert a["dtype"] == "float32"
    assert a["version"] == autotune.SCHEMA_VERSION
    assert a["geometry"] == TINY.key == "h4kv2d16-bs4-l4-mb32"
    assert a["swept"] == len(grid_a) == 5           # tb_tokens 1 ... 16
    # the row carries the one tunable left and nothing of the worklists
    assert "page_slots" not in a and "pages_per_step" not in a
    # every bucket stays packable at the tuned tb
    assert all(b_ % a["tb_tokens"] == 0 for b_ in (16, 32, 64))
    # a tb that does not divide a bucket is never a candidate
    assert [c["tb_tokens"] for c in autotune.candidate_grid(TINY, (6, 18))] == [1, 2]


def test_cost_model_counts_kv_steps_and_the_price_of_score_tiles():
    """The model's terms, from the packer's own counts: a larger token
    block copies a prompt span's pages fewer times (fewer KV steps, fewer
    blocks) and pays more score tiles a step.  At the serving geometry its
    synthetic windows (fifteen lanes decoding at half the context beside a
    prompt of a quarter of it, and beside a two-page chunk) are decode-heavy,
    where every step of a 64-token block carries 256 mostly masked rows: the
    model prefers 16 there, the chip prefers 64 on the cells' windows (a
    2,048-token span beside 7 decodes: 2.60 / 2.35 / 2.02 ms a layer at 16 /
    32 / 64, PERF.md section 6, PR 37), which is why a cost-model row binds
    nothing."""
    stats = {tb: autotune._pack_stats(TINY, tb) for tb in (1, 2, 4)}
    for small, large in ((1, 2), (2, 4)):
        for (nb_s, steps_s), (nb_l, steps_l) in zip(stats[small], stats[large]):
            assert nb_l < nb_s and steps_l < steps_s
    assert autotune.cost_model(TINY, 4) < autotune.cost_model(TINY, 2)
    serving = autotune.Geometry(
        num_heads=32, num_kv_heads=8, head_dim=128,
        block_size=16, lanes=16, max_blocks_per_seq=256,
    )
    assert autotune.cost_model(serving, 16) < autotune.cost_model(serving, 4)
    assert autotune.cost_model(serving, 16) < autotune.cost_model(serving, 64)
    assert autotune.resolve(
        {"rows": [autotune.sweep(serving)]}, geometry_key=serving.key,
        device_kind="TPU v5 lite", dtype="float32",
    ) is None


def test_tune_persists_and_rerun_is_cache_hit(tmp_path):
    path = tmp_path / "KERNEL_PERF.json"
    row, cached = autotune.tune(path, TINY, buckets=(16, 32))
    assert cached is False
    table = json.loads(path.read_text())
    assert [r["geometry"] for r in table["rows"]] == [TINY.key]
    # the persisted row carries full provenance but not the swept grid
    assert "grid" not in table["rows"][0]
    for key in ("bench", "geometry", "device_kind", "dtype", "source",
                "version", "tb_tokens", "cost", "swept"):
        assert key in table["rows"][0], key
    before = path.read_text()
    row2, cached2 = autotune.tune(path, TINY, buckets=(16, 32))
    assert cached2 is True
    assert row2 == row
    assert path.read_text() == before  # no-op: file untouched
    # header and unrelated rows survive an upsert
    table["platform"] = "tpu"
    table["rows"].append({"bench": "calib_matmul", "tflops": 1.0})
    path.write_text(json.dumps(table))
    other = autotune.Geometry(
        num_heads=8, num_kv_heads=8, head_dim=64,
        block_size=8, lanes=8, max_blocks_per_seq=16,
    )
    autotune.tune(path, other, buckets=(32,))
    table2 = json.loads(path.read_text())
    assert table2["platform"] == "tpu"
    benches = [r["bench"] for r in table2["rows"]]
    assert benches.count("calib_matmul") == 1
    assert benches.count(autotune.RAGGED_BENCH) == 2


def test_measured_rows_outrank_cost_model_rows():
    modeled = {
        "bench": autotune.RAGGED_BENCH, "geometry": TINY.key,
        "device_kind": "any", "dtype": "float32", "source": "cost_model",
        "version": autotune.SCHEMA_VERSION, "tb_tokens": 4,
    }
    measured = dict(modeled, device_kind="TPU v5 lite", source="measured",
                    tb_tokens=2)
    table = {"rows": [modeled, measured]}
    # exact-kind measured row wins
    got = autotune.resolve(
        table, geometry_key=TINY.key, device_kind="TPU v5 lite",
        dtype="float32",
    )
    assert got is measured
    # on a different chip nothing binds: the modeled row is a record
    got = autotune.resolve(
        table, geometry_key=TINY.key, device_kind="TPU v6e", dtype="float32",
    )
    assert got is None
    # dtype and geometry are part of the key
    assert autotune.resolve(
        table, geometry_key=TINY.key, device_kind=None, dtype="bfloat16",
    ) is None
    assert autotune.resolve(
        table, geometry_key="h1kv1d8-bs4-l2-mb4", device_kind=None,
        dtype="float32",
    ) is None
    # rows of the schema that carried worklist widths no longer bind
    old = dict(measured, version=1, page_slots=8, pages_per_step=1)
    assert autotune.resolve(
        {"rows": [old]}, geometry_key=TINY.key, device_kind="TPU v5 lite",
        dtype="float32",
    ) is None


def test_measured_runner_stamps_device_kind():
    calls = []

    def runner(cand):
        calls.append(cand)
        # pretend tb=2 candidates are fastest on this "hardware"
        return 10.0 if cand["tb_tokens"] == 2 else 100.0

    row = autotune.sweep(
        TINY, buckets=(16, 32), runner=runner, device_kind="TPU v5 lite",
    )
    assert row["source"] == "measured"
    assert row["device_kind"] == "TPU v5 lite"
    assert row["tb_tokens"] == 2
    assert len(calls) == row["swept"]


# ---------------------------------------------------------------- engine


def _engine(tmp_path, monkeypatch, table_rows=None, **overrides):
    from tests.engine.test_jax_engine import make_engine

    if table_rows is not None:
        path = tmp_path / "perf.json"
        path.write_text(json.dumps({"rows": table_rows}))
        monkeypatch.setenv("DYN_KERNEL_PERF", str(path))
    return make_engine(**overrides)


def _tuned_row(**kw):
    """A row measured on the device these tests run on."""
    row = {
        "bench": autotune.RAGGED_BENCH, "geometry": TINY.key,
        "device_kind": jax.devices()[0].device_kind, "dtype": "float32",
        "source": "measured",
        "version": autotune.SCHEMA_VERSION, "tb_tokens": 2,
    }
    row.update(kw)
    return row


def test_engine_resolves_tuned_row(tmp_path, monkeypatch):
    """The tiny test engine (geometry == TINY) must pick its tunables from
    a matching autotune row and report the provenance in stats()."""
    engine = _engine(
        tmp_path, monkeypatch, table_rows=[_tuned_row()],
        num_blocks=64, block_size=4, max_batch_size=4, max_model_len=128,
    )
    try:
        kc = engine.stats()["kernel_config"]
        assert kc["source"] == "tuned"
        assert kc["geometry"] == TINY.key
        assert kc == {"tb_tokens": 2, "source": "tuned", "geometry": TINY.key}
        assert engine._unified_tb == 2
    finally:
        engine.stop()


def test_engine_default_without_rows(tmp_path, monkeypatch):
    engine = _engine(
        tmp_path, monkeypatch, table_rows=[],
        num_blocks=64, block_size=4, max_batch_size=4, max_model_len=128,
    )
    try:
        kc = engine.stats()["kernel_config"]
        assert kc["source"] == "default"
        # two query heads share a KV head: 64 tokens keep a product's score
        # rows under 256; the buckets (16, 32, 64) pack to blocks of 16 / 32 / 64
        assert kc["tb_tokens"] == 64
        assert [engine._tb_for(b) for b in (16, 32, 64)] == [16, 32, 64]
        # the worklist width, its overflow counter and the pages folded
        # into a grid step went with the static worklists
        assert set(kc) == {"tb_tokens", "source", "geometry"}
        assert "unified_ps_overflows_total" not in engine.stats()
    finally:
        engine.stop()


def test_engine_knob_outranks_tuned_row(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_AUTOTUNE_TB", "1")
    engine = _engine(
        tmp_path, monkeypatch, table_rows=[_tuned_row()],
        num_blocks=64, block_size=4, max_batch_size=4, max_model_len=128,
    )
    try:
        kc = engine.stats()["kernel_config"]
        assert kc["source"] == "knob"
        assert kc["tb_tokens"] == 1
    finally:
        engine.stop()


def test_engine_autotune_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_AUTOTUNE", "0")
    engine = _engine(
        tmp_path, monkeypatch, table_rows=[_tuned_row()],
        num_blocks=64, block_size=4, max_batch_size=4, max_model_len=128,
    )
    try:
        assert engine.stats()["kernel_config"]["source"] == "default"
    finally:
        engine.stop()


def test_engine_rejects_tuned_tb_that_breaks_buckets(tmp_path, monkeypatch):
    """A tuned tb that cannot pack every unified bucket must fall back to
    the default (warn, not wedge every window into the split path), whose
    blocks are cut to what each bucket holds."""
    engine = _engine(
        tmp_path, monkeypatch,
        table_rows=[_tuned_row(tb_tokens=16)],
        num_blocks=64, block_size=4, max_batch_size=4, max_model_len=128,
        prefill_buckets=(24, 48),
    )
    try:
        kc = engine.stats()["kernel_config"]
        assert kc["source"] == "default"
        assert kc["tb_tokens"] == 64
        assert [engine._tb_for(b) for b in (24, 48)] == [8, 16]
    finally:
        engine.stop()
