"""The trace reduction on a hand-built trace (times in nanoseconds)."""

import pytest

from benchmark import trace
from benchmark.readers import device_idle_share, op_time_share

S = 1e9


def planes():
    ops = [("fusion.1", 0.0, 1 * S), ("ragged_paged_attention", 1 * S, 2 * S),
           ("fusion.1", 2.5 * S, 1 * S),           # overlaps the kernel's tail
           ("copy.7", 6 * S, 1 * S), ("fusion.1", 9 * S, 1 * S)]
    modules = [("jit_step(1)", 0.0, 3.5 * S), ("jit_sample(2)", 6 * S, 1 * S),
               ("jit_step(1)", 9 * S, 1 * S)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules, "Steps": [("0", 0, 10 * S)]},
            "/host:CPU": {"python": [("x", 0.0, 100 * S)]}}


def test_busy_is_the_union_and_the_window_the_span():
    out = trace.reduce_planes({k: v for k, v in planes().items() if k.startswith("/device")})
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(3.5 + 1 + 1)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["ops"]["fusion.1"] == pytest.approx(3.0)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]
    assert out["idle_gaps"][0] == ["jit_step(1)->jit_sample(2)", pytest.approx(2.5)]
    assert out["idle_gaps"][1] == ["jit_sample(2)->jit_step(1)", pytest.approx(2.0)]


def test_two_chips_average_and_an_idle_chip_is_left_out():
    p = {k: v for k, v in planes().items() if k.startswith("/device")}
    p["/device:TPU:1"] = {"XLA Ops": [("fusion.1", 0.0, 10 * S)]}
    p["/device:TPU:2"] = {"XLA Ops": []}
    out = trace.reduce_planes(p)
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((5.5 + 10) / 2)
    assert out["window_s"] == pytest.approx(10.0)


def test_nothing_on_the_device_reads_as_nothing():
    out = trace.reduce_planes({"/device:TPU:0": {"XLA Ops": []}})
    assert out["busy_s"] == 0.0 and out["chips"] == 0
    ctx = {"trace": out}
    assert device_idle_share.read(ctx) is None
    assert op_time_share.read(ctx, pattern="attention") is None


def test_readers_on_the_reduced_trace():
    ctx = {"trace": trace.reduce_planes({"/device:TPU:0": planes()["/device:TPU:0"]})}
    assert device_idle_share.read(ctx) == pytest.approx(45.0)
    assert op_time_share.read(ctx, pattern="ragged|paged_attention") == pytest.approx(
        100 * 2 / 5.5)
    assert op_time_share.read(ctx, pattern="no_such_kernel") is None


def test_a_recorded_trace_loads(tmp_path):
    """A real (CPU) trace through the real loader: no device plane, so the
    reduction finds nothing, and says so."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.start_options())
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    out = trace.reduce_dir(str(tmp_path))
    assert out["busy_s"] == 0.0 and out["device_ops"] == []
