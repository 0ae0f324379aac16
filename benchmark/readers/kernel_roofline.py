"""A kernel's share of its roofline over the window (%): the least time the
chip's published peaks allow for the work the program counted for it, the
larger of (operations / peak bf16 rate) and (bytes / peak HBM rate), over the
device time the kernel took.

``flops`` and ``bytes`` name the program's cumulative counters of that work
(``engine.stats()``, read at the window's two ends); ``pattern`` matches the
kernel's operations in the trace.  The trace holds a few seconds of the
window, the counters all of it, so the kernel's time over the window is
estimated: its share of the traced busy time x the window's engine step time
x (1 - idle share).  None where a counter, the trace or the kernel's
operations are missing, or the counters did not move.  A reading over 100 is a
fault in the count or in the time, not a result; nothing here clips it."""

import re

from benchmark.readers import device_idle_share
from benchmark.readers.counter_delta import delta


def matched(ctx, pattern):
    """``(device seconds, events)`` of the traced operations whose names
    ``pattern`` matches, summed over chips, or None where there are none."""
    tr = ctx.get("trace") or {}
    rx = re.compile(pattern)
    names = [name for name in tr.get("ops") or {} if rx.search(name)]
    if not names:
        return None
    counts = tr.get("op_events") or {}
    return sum(tr["ops"][n] for n in names), sum(counts.get(n, 0) for n in names)


def read(ctx, pattern, flops, bytes):  # noqa: A002 - the metric files' own word
    peaks, idle = ctx.get("peaks"), device_idle_share.idle(ctx)
    step_s, inside = delta(ctx, "engine_step_time_total_s"), matched(ctx, pattern)
    work = [delta(ctx, key) for key in flops]
    moved = [delta(ctx, key) for key in bytes]
    if not peaks or idle is None or not step_s or inside is None or None in work + moved:
        return None
    tr = ctx["trace"]
    kernel_s = inside[0] / (tr["busy_s"] * tr["chips"]) * step_s * (1.0 - idle)
    floor_s = max(sum(work) / peaks["bf16_flops_per_s"], sum(moved) / peaks["hbm_bytes_per_s"])
    if floor_s <= 0 or kernel_s <= 0:
        return None
    return 100.0 * floor_s / kernel_s
