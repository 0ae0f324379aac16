"""Device time inside the operations whose trace names match ``pattern``,
over the device's busy time (%)."""

import re


def read(ctx, pattern):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("ops"):
        return None
    rx = re.compile(pattern)
    inside = sum(sec for name, sec in tr["ops"].items() if rx.search(name))
    if not inside:
        return None
    return 100.0 * inside / (tr["busy_s"] * tr["chips"])
