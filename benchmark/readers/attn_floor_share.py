"""The share of the window's engine step time that attention would need at
the chip's published peaks (%): the larger of (operations of both attention
kernels / peak bf16 rate) and (KV bytes both kernels read / peak HBM rate),
over the step time, all from the program's own cumulative counters, no trace
in it.  A floor over the whole window: set beside ``attn_kernel_share.long``
(the share of device time the kernels really take) it is their distance
from the roofline.  The counters are kept where the worklists are built:
operations follow the attended context, bytes the whole pages walked."""

from benchmark.readers.counter_delta import delta

FLOPS = ("ragged_attn_flops_total", "decode_attn_flops_total")
BYTES = ("ragged_kv_read_bytes_total", "decode_kv_read_bytes_total")


def read(ctx):
    peaks = ctx.get("peaks")
    step_s = delta(ctx, "engine_step_time_total_s")
    flops = [delta(ctx, k) for k in FLOPS]
    moved = [delta(ctx, k) for k in BYTES]
    if not peaks or not step_s or None in flops or None in moved:
        return None
    floor_s = max(sum(flops) / peaks["bf16_flops_per_s"],
                  sum(moved) / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / step_s
