"""Host time of a mean busy engine step over the window, in ms."""

from benchmark.readers.counter_delta import delta


def read(ctx):
    t, n = delta(ctx, "engine_step_time_total_s"), delta(ctx, "engine_busy_steps_total")
    if t is None or not n:
        return None
    return 1e3 * t / n
