"""The change of one cumulative counter over the change of another across the
window, times ``scale``: a mean per step (seconds -> ms with 1000), a share
(100), a mean count (1).  None where either counter is missing at either end
(a program that does not keep it) or the denominator did not move."""

from benchmark.readers.counter_delta import delta


def read(ctx, numerator, denominator, scale=1.0):
    num, den = delta(ctx, numerator), delta(ctx, denominator)
    if num is None or not den:
        return None
    return scale * num / den
