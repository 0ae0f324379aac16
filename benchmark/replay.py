#!/usr/bin/env python3
"""A cell's plan served on the host by a model of today's server.

    python3 benchmark/replay.py --workload <cell> [--seconds 51] [--fit <run.py --dump file>]

For a cell whose mix opens on a backlog (``cells/<cell>.json`` carries
``gap_requests`` and ``replay``).  The model is the scheduler's policy as the
long-prompt cells meet it: first come first served, at most ONE prompt
admitted a step, ``--max-batch-size`` lanes, a prompt served whole in one
window beside the running answers, a lane that an answer gave up taken again
``admit_lag_steps`` steps later (the step loop runs that far ahead of what
it has read back); and a step's time from the cell's ``replay``:
``decode_ms`` a decode step, ``fixed + per_bucket_token x bucket
+ per_token_sq x tokens^2`` milliseconds a prompt window (``bucket`` = the
compile bucket that holds the prompt and the lanes).  It is no measurement:
it says where in time a range of answers falls on a server of about this
speed (the rules for ``gap_requests`` in README.md), and it gives the tier-1
tests client records whose steps can be scaled.  Pure Python and numpy.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import arith, traffic  # noqa: E402

BUCKETS = (256, 512, 1024, 2048, 4096, 8192)
ADMIT_LAG_STEPS = 2   # read off the dumps of PR 32: two decode steps on one lane fewer, then the window
SAME_STEP_S = 0.02    # a straggler of the same step's answers is no step of its own
TEMPLATE_TOKENS = 4   # t0, the user mark, the turn's close, the assistant mark


def bucket_of(tokens: int, lanes: int) -> int:
    """The compile bucket of a window that holds a prompt and every lane's token."""
    return next((b for b in BUCKETS if b >= tokens + lanes), BUCKETS[-1])


def prompt_ms(model: dict, tokens: int, lanes: int) -> float:
    p = model["prompt_ms"]
    return p["fixed"] + p["per_bucket_token"] * bucket_of(tokens, lanes) + p["per_token_sq"] * tokens * tokens


def serve(plan, model: dict, lanes: int, lead_in_s: float, *,
          decode_scale: float = 1.0, prompt_scale: float = 1.0) -> list[dict]:
    """Client records (``index``, ``due``, ``prompt_tokens``, ``chunks``) of
    every request of ``plan`` that is no probe, each served to its last
    token.  ``decode_scale`` and ``prompt_scale`` stretch every decode step
    and every prompt window."""
    todo = [r for r in plan if not r["probe"]]
    records = [{"index": r["index"], "due": r["due"], "error": None, "chunks": [],
                "prompt_tokens": r["prompt_len"] + TEMPLATE_TOKENS} for r in todo]
    left = {}                 # position in ``todo`` -> tokens still to come
    freed = []                # the step from which each lane that was given up can be taken again
    now, nxt, step = -lead_in_s, 0, 0
    while left or nxt < len(todo):
        if not left and todo[nxt]["due"] > now:
            now = todo[nxt]["due"]
        freed = [s for s in freed if s > step]
        if nxt < len(todo) and len(left) + len(freed) < lanes and todo[nxt]["due"] <= now:
            now += prompt_ms(model, records[nxt]["prompt_tokens"], lanes) * prompt_scale / 1e3
            left[nxt] = todo[nxt]["output_len"]
            nxt += 1
        else:
            now += model["decode_ms"] * decode_scale / 1e3
        step += 1
        for k in list(left):
            records[k]["chunks"].append((now, 1))
            left[k] -= 1
            if not left[k]:
                del left[k]
                freed.append(step + model["admit_lag_steps"])
    return records


def prompt_windows(timeline) -> list[tuple[int, float]]:
    """``(prompt tokens, milliseconds)`` of each prompt window a dumped run's
    clients saw: from the latest arrival of any stream before a request's
    first chunk to that chunk."""
    arrivals = sorted(t for row in timeline for t in row[5])
    out = []
    for _, _, _, tokens, _, chunks in timeline:
        before = bisect.bisect_left(arrivals, chunks[0] - SAME_STEP_S) if chunks else 0
        if before:
            out.append((tokens, (chunks[0] - arrivals[before - 1]) * 1e3))
    return out


def fit(timeline, lanes: int) -> dict:
    """The step-time model of a dumped run: the median gap as the decode
    step, least squares over its prompt windows."""
    import numpy as np

    gaps = [(b - a) * 1e3 for row in timeline for a, b in zip(row[5], row[5][1:])]
    seen = prompt_windows(timeline)
    rows = np.array([[1.0, bucket_of(n, lanes), n * n] for n, _ in seen])
    (fixed, per_bucket, per_sq), *_ = np.linalg.lstsq(rows, np.array([ms for _, ms in seen]), rcond=None)
    return {"admit_lag_steps": ADMIT_LAG_STEPS, "decode_ms": round(arith.percentile(gaps, 50), 2),
            "prompt_ms": {"fixed": round(float(fixed), 2), "per_bucket_token": round(float(per_bucket), 5),
                          "per_token_sq": round(float(per_sq), 8)}}


def lanes_of(loaded: dict) -> int:
    """The lanes of what ``run.load_cell`` found: the last
    ``--max-batch-size`` wins, as on the server's command line."""
    args = [str(a) for a in [*loaded["config"]["serving"]["args"], *loaded["own"].get("serving_args", [])]]
    return int(args[max(i for i, a in enumerate(args) if a == "--max-batch-size") + 1])


def serve_cell(loaded: dict, seconds: float, model: dict | None = None, **scales) -> list[dict]:
    """``serve`` for what ``run.load_cell`` found: the cell's own plan at
    ``seconds``, its lanes, and its committed ``replay`` unless ``model`` is given."""
    mix = loaded["mix"]
    return serve(traffic.schedule(mix, loaded["rate"], seconds), model or loaded["own"]["replay"],
                 lanes_of(loaded), float(mix.get("lead_in_s", 0.0)), **scales)


def placement(records, gap_requests, seconds: float) -> dict:
    """Where the range falls: what the rules for ``gap_requests`` ask."""
    first, last = gap_requests
    inside = [r for r in records if first <= r["index"] < last]
    return {"requests": len(inside), "gaps": len(arith.gaps_of_requests_ms(records, first, last)),
            "opens_s": min(r["chunks"][0][0] for r in inside),
            "closes_s": max(r["chunks"][-1][0] for r in inside),
            "finished_by_the_close": sum(1 for r in records if r["chunks"][-1][0] <= seconds),
            "first_answered_by_the_close": sum(1 for r in records if r["chunks"][0][0] <= seconds)}


def main(argv=None) -> int:
    from benchmark.run import load_cell, load_json

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--fit", default=None, help="fit the model to this dump and print it")
    args = p.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    loaded = load_cell(bench, args.workload)
    own = loaded["own"]
    seconds = args.seconds or float(bench["run_seconds"])
    model = None
    if args.fit:
        model = fit(load_json(Path(args.fit))["timeline"], lanes_of(loaded))
        print(json.dumps({"replay": model}))
    records = serve_cell(loaded, seconds, model)
    e2e = arith.end_to_end(records, seconds, 0.0, own.get("gap_requests"))
    print(json.dumps({"placement": placement(records, own["gap_requests"], seconds),
                      "replayed": {k: e2e[k] for k in ("itl_p95_ms", "itl_p95_window_ms", "tok_per_s")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
