"""The trace reduction on a hand-built trace (times in nanoseconds)."""

import pytest

from benchmark import trace
from benchmark.readers import device_idle_share, kernel_roofline, op_time_share

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
    # how many events each operation's time is of, in the same order
    assert out["op_events"] == {"fusion.1": 3, "ragged_paged_attention": 1, "copy.7": 1}
    assert list(out["op_events"]) == list(out["ops"])
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
    assert out["op_events"]["fusion.1"] == 4 and out["ops"]["fusion.1"] == pytest.approx(13.0)


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


def test_the_reduction_of_a_file_keeps_what_a_metric_looks_for(monkeypatch, tmp_path):
    """``reduce_dir`` cuts the per-operation tables to the largest 200 and to
    every operation a pattern matches, however little time it took."""
    ops, at = [], 0.0
    for k in range(260):
        dur = (300 - k) * 1e3
        ops += [(f"fusion.{k}", at, dur)]
        at += dur
    ops += [("tiny_kernel.3", at, 10.0), ("tiny_kernel.3", at + 10.0, 10.0), ("other_tiny", at + 20.0, 5.0)]
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "load_planes", lambda path: {"/device:TPU:0": {"XLA Ops": ops}})
    plain = trace.reduce_dir(str(tmp_path))
    assert len(plain["ops"]) == 200 and "tiny_kernel.3" not in plain["ops"]
    kept = trace.reduce_dir(str(tmp_path), keep=["^tiny_kernel", "^no_such"])
    assert len(kept["ops"]) == 201 and "other_tiny" not in kept["ops"]
    assert kept["ops"]["tiny_kernel.3"] == pytest.approx(20e-9) and kept["op_events"]["tiny_kernel.3"] == 2
    assert list(kept["op_events"]) == list(kept["ops"])
    assert kept["busy_s"] == plain["busy_s"]
    ctx = {"trace": kept}
    assert kernel_roofline.matched(ctx, "^tiny_kernel") == (pytest.approx(20e-9), 2)
    assert kernel_roofline.matched({"trace": plain}, "^tiny_kernel") is None


PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}


def roofline_ctx(flops, moved, **more):
    """A window of 40 s of engine steps on a device that idles a tenth of the
    traced span, a quarter of whose busy time is the kernel's: 9 s of it."""
    ops = [("my_kernel.1", 0.0, 1.0 * S), ("fusion.9", 1.0 * S, 3.0 * S), ("my_kernel.1", 4.5 * S, 0.125 * S)]
    out = trace.reduce_planes({"/device:TPU:0": {"XLA Ops": ops}})
    out["busy_s"], out["window_s"] = 4.5, 5.0       # idle share 0.1; the kernel 1.125 of 4.5 s busy
    zero = {"k_flops": 0, "k_bytes": 0, "more_bytes": 0, "engine_step_time_total_s": 10.0}
    end = {"k_flops": flops, "k_bytes": moved, "more_bytes": 0, "engine_step_time_total_s": 50.0}
    return dict({"trace": out, "peaks": PEAKS, "stats0": {"stats": zero}, "stats1": {"stats": end}}, **more)


def test_a_kernels_roofline_share_on_a_hand_built_window():
    args = dict(pattern="^my_kernel", flops=["k_flops"], bytes=["k_bytes", "more_bytes"])
    # bound by bytes: 1.44e12 B / 800e9 B/s = 1.8 s of the kernel's 9 s
    assert kernel_roofline.read(roofline_ctx(100e12, 1.44e12), **args) == pytest.approx(20.0)
    # bound by operations: 900e12 / 200e12 = 4.5 s of 9 s
    assert kernel_roofline.read(roofline_ctx(900e12, 1.44e12), **args) == pytest.approx(50.0)
    assert kernel_roofline.matched(roofline_ctx(1, 1), "^my_kernel") == (pytest.approx(1.125), 2)
    # nothing to read: a counter the program does not keep, no trace, no such
    # kernel in it, no peaks, counters that did not move; never a 0
    ctx = roofline_ctx(100e12, 1.44e12)
    assert kernel_roofline.read(ctx, "^my_kernel", ["k_flops"], ["not_kept"]) is None
    assert kernel_roofline.read(ctx, "^no_such_kernel", ["k_flops"], ["k_bytes"]) is None
    assert kernel_roofline.read(dict(ctx, trace=None), **args) is None
    assert kernel_roofline.read(dict(ctx, peaks=None), **args) is None
    assert kernel_roofline.read(dict(ctx, stats0=None), **args) is None
    assert kernel_roofline.read(roofline_ctx(0, 0), **args) is None


@pytest.mark.parametrize("flops,moved", [(1e12, 1e9), (1500e12, 1e9), (1e12, 7.1e12), (1799e12, 7.19e12)])
def test_work_the_peaks_allow_in_the_kernels_time_never_reads_over_100(flops, moved):
    """Whatever the chip could do in the kernel's 9 s (1,800e12 operations,
    7.2e12 bytes) reads at most 100; the reader clips nothing, so a count
    past that reads past it and shows."""
    args = dict(pattern="^my_kernel", flops=["k_flops"], bytes=["k_bytes"])
    assert 0 < kernel_roofline.read(roofline_ctx(flops, moved), **args) <= 100.0
    assert kernel_roofline.read(roofline_ctx(2 * 1800e12, moved), **args) == pytest.approx(200.0)
