"""The share of the window's engine step time that moving some bytes would
need at the chip's published HBM rate (%): the change of the program's
cumulative counter ``bytes`` (the bytes a perfect implementation of one layer
moves) / peak HBM rate, over the change of ``engine_step_time_total_s``.  A
floor over the whole window, no trace in it; set beside the device time the
layer's operations really take it is their distance from the roofline.  None
where the program keeps no such counter."""

from benchmark.readers.counter_delta import delta


def read(ctx, bytes):  # noqa: A002 - the argument is the counter's role, as in kernel_roofline
    peaks = ctx.get("peaks")
    step_s = delta(ctx, "engine_step_time_total_s")
    moved = delta(ctx, bytes)
    if not peaks or not step_s or moved is None:
        return None
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / step_s
