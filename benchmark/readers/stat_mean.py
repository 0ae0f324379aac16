"""The mean of a GAUGE of ``engine.stats()`` (a level, not a cumulative
counter: blocks in use, requests waiting) over the readings the run took of
it: the window's two ends and, in a traced run, one a second between them.
None where no reading carries the key (a program that does not keep it)."""


def read(ctx, key):
    ends = [s["stats"] for s in (ctx.get("stats0"), ctx.get("stats1")) if s and s.get("stats")]
    seen = [s[key] for s in [*ends, *(ctx.get("samples") or [])] if key in s]
    if not seen:
        return None
    return sum(seen) / len(seen)
