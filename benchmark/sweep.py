#!/usr/bin/env python3
"""The knee sweep: one server start, one window per rate, a table.

    python benchmark/sweep.py --workload <cell> --rates 1,2,3 --seconds 30 --seed 1 --out <csv>

Run once by the builder when a cell is defined (never by the driver): the
highest rate the server sustains is the last one at which the backlog when
the window closes stays small and the second half's time to first token does
not run away from the first half's.  The table is kept beside the traffic
file (``benchmark/traffic/<mix>.sweep.<config>.csv``)."""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import arith, client, run as runner, traffic  # noqa: E402
from benchmark.server import Server, hf_config, write_model_dir  # noqa: E402

COLUMNS = ("rate_rps", "offered", "failed", "in_flight_at_close", "finished_per_s", "tok_per_s",
           "ttft_p50_ms", "ttft_p95_ms", "ttft_p50_first_half_ms", "ttft_p50_second_half_ms",
           "itl_p50_ms", "itl_p95_ms", "preemptions", "drain_s")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", required=True)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    bench = runner.load_json(ROOT / "BENCHMARK.json")
    loaded = runner.load_cell(bench, args.workload)
    config, mix = loaded["config"], loaded["mix"]
    hf = hf_config(config)
    work = runner.WORK / (args.workload + ".sweep")
    work.mkdir(parents=True, exist_ok=True)
    write_model_dir(work / "model", config)
    serving = [str(a) for a in [*config["serving"]["args"], *loaded["own"].get("serving_args", [])]]
    server = Server(work / "model", serving, args.seed, work / "server.log",
                    time.monotonic() + 1500)
    rows = []
    try:
        device = server.json_after("jax devices: ", "jax")
        if device["platform"] != "tpu" and not args.allow_cpu:
            print(f"need a TPU, server sees {device}", file=sys.stderr)
            return 1
        server.wait_for("listening on http://", "listen")
        print(f"ready after {time.monotonic() - server.t0:.1f} s", flush=True)
        for rate in [float(r) for r in args.rates.split(",")]:
            plan = traffic.schedule(mix, rate, args.seconds)
            filled = traffic.fill(plan, mix, hf["vocab_size"], args.seed)
            runner.warm_up(server.port, filled, hf["vocab_size"], args.seed)
            s0 = server.ask("/stats")["stats"]
            t = time.monotonic()
            d = client.drive(server.port, "bench", plan, filled, args.seconds, 180.0)
            drain = time.monotonic() - t - args.seconds - float(mix.get("lead_in_s", 0.0))
            s1 = server.ask("/stats")["stats"]
            recs = d["records"]
            e = arith.end_to_end(recs, args.seconds, miss_ms=1e6)
            half = args.seconds / 2
            tt = lambda lo, hi: [(r["chunks"][0][0] - r["due"]) * 1e3 for r in recs  # noqa: E731
                                 if lo <= r["due"] < hi and r["chunks"]]
            a, b = tt(0, half), tt(half, args.seconds)
            done = sum(1 for r in recs if r["done"] is not None and 0 <= r["done"] <= args.seconds)
            rows.append({
                "rate_rps": rate, "offered": len(recs),
                "failed": sum(1 for r in recs if r["error"]),
                "in_flight_at_close": d["in_flight_at_close"],
                "finished_per_s": done / args.seconds, "tok_per_s": e["tok_per_s"],
                "ttft_p50_ms": arith.percentile(a + b, 50) if a + b else None,
                "ttft_p95_ms": e.get("ttft_p95_ms"),
                "ttft_p50_first_half_ms": arith.percentile(a, 50) if a else None,
                "ttft_p50_second_half_ms": arith.percentile(b, 50) if b else None,
                "itl_p50_ms": e.get("itl_p50_ms"), "itl_p95_ms": e.get("itl_p95_ms"),
                "preemptions": s1["num_preemptions_total"] - s0["num_preemptions_total"],
                "drain_s": drain,
            })
            print(json.dumps(rows[-1]), flush=True)
    finally:
        server.stop()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(",".join(COLUMNS) + "\n" + "\n".join(
        ",".join("" if r[c] is None else f"{r[c]:.6g}" for c in COLUMNS) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
