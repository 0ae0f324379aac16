"""A percentile of a program span's durations inside the window, from the
difference of the span's duration histogram at the window's two ends
(``stats()["spans"]``: per component and name a count, a total and the
counts of a fixed geometric histogram whose shape rides beside them).

The answer is off by at most one bucket's width (the histogram's ratio, 1.2
in the program as written).  None where the program keeps no such histogram
or the span did not occur in the window."""


def window_counts(ctx, component, name):
    """(bucket counts gained in the window, the histogram's shape) or None."""
    ends = []
    for key in ("stats0", "stats1"):
        spans = ((ctx.get(key) or {}).get("stats") or {}).get("spans")
        if not spans or "hist" not in spans:
            return None
        ends.append(spans)
    row1 = ends[1].get("series", {}).get(component, {}).get(name)
    if row1 is None:
        return None
    row0 = ends[0].get("series", {}).get(component, {}).get(name)
    before = row0["counts"] if row0 else [0] * len(row1["counts"])
    return [b - a for a, b in zip(before, row1["counts"])], ends[1]["hist"]


def percentile(counts, hist, q):
    """The ``q``-th percentile in seconds: bucket 0 is [0, min_s), bucket i is
    [min_s * ratio**(i-1), min_s * ratio**i), the last one is open above."""
    total = sum(counts)
    if total <= 0:
        return None
    rank = q / 100.0 * total
    seen = 0
    for i, n in enumerate(counts):
        if n and seen + n >= rank:
            inside = (rank - seen) / n
            if i == 0:
                return inside * hist["min_s"]
            low = hist["min_s"] * hist["ratio"] ** (i - 1)
            return low if i == len(counts) - 1 else low * hist["ratio"] ** inside
        seen += n
    return None


def read(ctx, component, name, q, scale=1e3):
    got = window_counts(ctx, component, name)
    if got is None:
        return None
    value = percentile(got[0], got[1], q)
    return None if value is None else scale * value
