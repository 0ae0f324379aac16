"""The reduction from client samples to end-to-end metrics.  Pure Python."""

from __future__ import annotations

import bisect
import math
import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, the way the benchmark's contract takes it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttfts_ms(records, seconds: float, miss_ms: float) -> list[float]:
    """Due instant -> first content chunk, for every request due inside the
    window.  A request that failed or never answered counts as ``miss_ms``."""
    out = []
    for r in records:
        if not 0.0 <= r["due"] < seconds:
            continue
        if r.get("error") or not r["chunks"]:
            out.append(miss_ms)
        else:
            out.append((r["chunks"][0][0] - r["due"]) * 1e3)
    return out


def gaps_ms(records, seconds: float) -> list[float]:
    """Gaps between consecutive content chunks of one request, over every gap
    that closed inside the window (whatever request it belongs to)."""
    out = []
    for r in records:
        ch = r["chunks"]
        for (t_a, _), (t_b, _) in zip(ch, ch[1:]):
            if 0.0 <= t_b <= seconds:
                out.append((t_b - t_a) * 1e3)
    return out


def gaps_of_requests_ms(records, first: int, last: int) -> list[float]:
    """The same gaps of a fixed set of answers: the requests whose plan index
    lies in ``[first, last)``, wherever in time their gaps close.  Above the
    knee, behind a backlog that never empties, a first-come server takes the
    same steps in the same order at any speed, so over a fixed set of answers
    a percentile names the same step on both sides of a pair; over a fixed
    stretch of time it names whichever step a faster server happens to reach
    (benchmark/README.md, "The tail over a fixed set of answers")."""
    out = []
    for r in records:
        if first <= r["index"] < last:
            ch = r["chunks"]
            out += [(t_b - t_a) * 1e3 for (t_a, _), (t_b, _) in zip(ch, ch[1:])]
    return out


def tokens_unsplit(records, seconds: float) -> int:
    """Prompt tokens of every request whose first chunk arrived in the window
    plus every output token that arrived in it: a step that straddles an edge
    of the window counts whole or not at all, by the instant its answers
    arrived (a prompt of 2,000 tokens is 3% of a long-prompt window)."""
    total = 0
    for r in records:
        ch = r["chunks"]
        if ch and 0.0 <= ch[0][0] <= seconds:
            total += r["prompt_tokens"]
        total += sum(n for t, n in ch if 0.0 <= t <= seconds)
    return total


STEP_EPS_S = 0.008  # answers of one engine step reach the client within this


def tokens_in_window(records, seconds: float) -> float:
    """The same tokens, each credited evenly over the engine step that
    produced it as the client sees steps: from the latest arrival of any
    stream before this one (the previous step's answers) to this arrival.
    Inside the window nothing changes; a step that straddles an edge counts
    by the share of it that lies inside.  No token is counted twice or
    dropped: over all time the sum is the unsplit one."""
    arrivals = sorted(t for r in records for t, _ in r["chunks"])
    total = 0.0
    for r in records:
        for k, (t, n) in enumerate(r["chunks"]):
            n = n + r["prompt_tokens"] if k == 0 else n
            at = bisect.bisect_left(arrivals, t - STEP_EPS_S) - 1
            if at < 0:  # nothing arrived before: no step to spread over
                total += n if 0.0 <= t <= seconds else 0.0
                continue
            start = arrivals[at]
            inside = min(t, seconds) - max(start, 0.0)
            if inside > 0.0:
                total += n * inside / (t - start)
    return total


def end_to_end(records, seconds: float, miss_ms: float, gap_requests=None) -> dict:
    """Every end-to-end metric the client can compute, by name.  With a
    cell's ``gap_requests`` (``[first, last)`` by plan index) ``itl_p95_ms``
    is taken over those answers' gaps; ``itl_p95_window_ms`` is always the
    tail of every gap that closed inside the window."""
    out = {"tok_per_s": tokens_in_window(records, seconds) / seconds,
           "tok_per_s_unsplit": tokens_unsplit(records, seconds) / seconds}
    tt = ttfts_ms(records, seconds, miss_ms)
    if tt:
        out["ttft_p95_ms"] = percentile(tt, 95)
    gp = gaps_ms(records, seconds)
    if gp:
        out["itl_p50_ms"] = percentile(gp, 50)
        out["itl_p95_ms"] = out["itl_p95_window_ms"] = percentile(gp, 95)
    if gap_requests:
        fixed = gaps_of_requests_ms(records, *gap_requests)
        if fixed:
            out["itl_p95_ms"] = percentile(fixed, 95)
        else:
            out.pop("itl_p95_ms", None)
    return out
