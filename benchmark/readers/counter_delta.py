"""The change of one of the engine's cumulative counters over the window."""


def delta(ctx, key):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or s0.get("stats") is None or s1.get("stats") is None:
        return None
    if key not in s0["stats"] or key not in s1["stats"]:
        return None
    return s1["stats"][key] - s0["stats"][key]


def read(ctx, counter):
    return delta(ctx, counter)
