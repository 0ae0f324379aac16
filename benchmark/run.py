#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``; its ``config`` and ``traffic`` name
``benchmark/configs/<config>.json`` and ``benchmark/traffic/<traffic>.json``,
``benchmark/cells/<cell>.json`` holds the rate the cell is offered (the one
place a rate is written) and, on a mix that opens on a backlog, the range of
requests whose gaps are its ``itl_p95_ms``, the configuration's file names its own
``reference`` and ``shapes`` modules (``benchmark/modules.py``), and each
per-layer metric is ``benchmark/metrics/<name>.json`` naming a reader module
under ``benchmark/readers/``.  Nothing here knows a cell, a configuration, a
reference, a block, a mix or a metric by name.

Set-up (counted in ``setup_s``): write the model directory, start the server
child (``benchmark/launcher.py`` = the program's ``dynamo_tpu.cli.run`` entry
with ``--warmup``), wait until it listens, send the warm-up requests (every
shared prefix once, so the prefix cache starts warm), run the lead-in.  Then
the window: open loop over HTTP at the cell's fixed rate for ``--seconds``.
Then: drain, read counters and memory, stop the server, and only then run
the reference (``benchmark/reference/check.py``, its own process) over a
sample of what was served.  This process never imports JAX.
"""

from __future__ import annotations

_T_START = __import__("time").monotonic()

import argparse
import hashlib
import importlib
import json
import os
import random
import shutil
import subprocess
import sys
import time
import urllib.parse
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import arith, client, modules, traffic  # noqa: E402
from benchmark.shapes import load_peaks  # noqa: E402
from benchmark.server import BenchFailure, Server, hf_config, write_model_dir  # noqa: E402

BENCH_DIR = ROOT / "benchmark"
WORK = ROOT / ".bench_work"
RUN_DEADLINE_S = 340.0       # the driver allows 360
COLD_DEADLINE_S = 1150.0     # … and 1200 for a run that compiles


def say(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(bench: dict, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Everything a run needs to know about a cell, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / cfg_entry["file"]) if (ROOT / cfg_entry["file"]).exists() \
        else load_json(bench_dir / "configs" / f"{cell['config']}.json")
    mix = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    cell_file = bench_dir / "cells" / f"{name}.json"
    own = load_json(cell_file) if cell_file.exists() else {}
    if "rate_rps" not in own:
        raise SystemExit(f"cell {name!r} has no offered rate: {cell_file} must give rate_rps")
    return {"cell": cell, "config": config, "mix": mix, "own": own,
            "rate": float(own["rate_rps"]),
            "reference": modules.path_of(config, "reference", bench_dir, cell["config"]),
            "shapes": modules.load(modules.path_of(config, "shapes", bench_dir, cell["config"]))}


def cache_dir() -> Path:
    """JAX's persistent compilation cache, where the program keeps it too."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or ROOT / ".jax_cache")


def warm_markers(cache: Path, cell: str, work: Path = WORK) -> tuple[Path, Path]:
    """Both written when a run of ``cell`` from this checkout has reached its
    end: until then the cell's server and reference compile, and the run gets
    the long deadline.  One lies under ``cache`` (a cache that is warm for
    another cell, or for another checkout, says nothing: the serving
    arguments and the reference's shapes are the cell's, and a kernel's key
    holds the checkout's path).  The other lies in the checkout itself: a
    cache directory that outlives the machine brings its markers along, and a
    new machine compiles the cell's programs again all the same (PR 49: three
    runs killed at the short deadline in their first run on a machine)."""
    return (cache / f"bench-warm.{cell}.{hashlib.sha1(str(ROOT).encode()).hexdigest()[:12]}",
            work / f"bench-warm.{cell}")


def metrics_of(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group] if "workloads" not in m or cell in m["workloads"]]


def metric_specs(bench: dict, cell: str, bench_dir: Path = BENCH_DIR) -> list[tuple[dict, dict]]:
    """``(entry, its own file)`` of each per-layer metric this cell reports."""
    return [(m, load_json(bench_dir / "metrics" / f"{m['name']}.json"))
            for m in metrics_of(bench, cell, "per_layer")]


def op_patterns(specs) -> list[str]:
    """The operation names the cell's readers look for in the trace (a
    reader's argument ``pattern``): the reduction drops none of them."""
    return sorted({spec["args"]["pattern"] for _, spec in specs
                   if "pattern" in spec.get("args", {})})


def read_per_layer(bench: dict, cell: str, ctx: dict, bench_dir: Path = BENCH_DIR) -> dict:
    """Each per-layer metric through the reader its own file names.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m, spec in metric_specs(bench, cell, bench_dir):
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def warm_up(port: int, filled: dict, vocab: int, seed: int) -> list[dict]:
    """Every shared prefix once (so the window starts with a warm prefix
    cache) and one plain request; sent one after another, before the clock."""
    rng = random.Random(seed ^ 0x5EED)
    plan = []
    for p in range(len(filled["prefixes"])):
        plan.append({"index": -1 - p, "due": 0.0, "prompt_len": 8, "output_len": 4, "prefix": p, "probe": 0})
    plan.append({"index": -100, "due": 0.0, "prompt_len": 24, "output_len": 4, "prefix": -1, "probe": 0})
    extra = {"prefixes": filled["prefixes"],
             "prompts": {r["index"]: [rng.randrange(traffic.RESERVED, vocab)
                                      for _ in range(r["prompt_len"])] for r in plan}}
    out = []
    for r in plan:
        out += client.drive(port, "bench", [r], extra, 0.0, 120.0)["records"]
    return out


def pick_sample(records, plan_by_index, filled, k: int, seed: int) -> list[dict]:
    """``k`` of the window's finished requests drawn from the seed, the
    longest among them, and every probe that finished."""
    ok = [r for r in records if not r["error"] and r["done"] is not None
          and len(r["ids"]) == r["output_len"]]
    done = [r for r in ok if not r["probe"]]
    if not done:
        return []
    size = lambda r: r["prompt_tokens"] + len(r["ids"])  # noqa: E731
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    chosen = [longest, *rest[: max(0, k - 1)], *[r for r in ok if r["probe"]]]
    return [{"index": r["index"], "served_ids": r["ids"],
             "top": r["top"] if r["probe"] and len(r["top"]) == len(r["ids"]) else None,
             "served_logprobs": r["logprobs"] if len(r["logprobs"]) == len(r["ids"]) else None,
             "served_passes": r["passes"] if len(r["passes"]) == len(r["ids"]) else None,
             "prompt_ids": traffic.templated_ids(plan_by_index[r["index"]], filled)}
            for r in chosen]


def run_reference(job: dict, work: Path, deadline: float) -> dict:
    job_path, out_path = work / "check_job.json", work / "check_out.json"
    job_path.write_text(json.dumps(job))
    out_path.unlink(missing_ok=True)
    log = open(work / "check.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, str(BENCH_DIR / "reference" / "check.py"), str(job_path), str(out_path)],
        cwd=ROOT, stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
    try:
        proc.wait(timeout=max(10.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    log.close()
    if proc.returncode != 0 or not out_path.exists():
        tail = "\n".join((work / "check.log").read_text(errors="replace").splitlines()[-15:])
        raise BenchFailure(f"reference child failed (rc={proc.returncode}):\n{tail}")
    return load_json(out_path)


def compared(check: dict, counts: dict, limits: dict) -> list[tuple[str, float, float]]:
    """``(name, value, limit)`` of each number compared: a value holds while
    it is at most its limit (a least count is held as its negative)."""
    return [
        ("failed_requests", counts["failed"], 0),
        ("token_count_mismatches", counts["mismatched"], 0),
        ("checked_tokens_min", -check.get("tokens", 0), -limits["min_checked_tokens"]),
        ("logit_gap_max", check.get("gap_max", float("inf")), limits["gap_max"]),
        ("logprob_err_mean", check.get("logprob_err_mean", float("inf")), limits["logprob_err_mean"]),
        ("probed_tokens_min", -check.get("probed_tokens", 0), -limits["min_probed_tokens"]),
        ("topk_err_mean", check.get("topk_err_mean", float("inf")), limits["topk_err_mean"]),
    ]


def compare(check: dict, counts: dict, limits: dict) -> tuple[bool, list[str]]:
    """Each number compared beside its limit; every one has to hold."""
    ok, lines = True, []
    for name, value, limit in compared(check, counts, limits):
        good = value <= limit
        ok &= good
        lines.append(f"compare {name}: value {value!r} limit {limit!r} {'ok' if good else 'FAIL'}")
    return ok, lines


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the builder's own switches; the driver passes neither, and neither
    # changes what the window offers or how it is served
    p.add_argument("--control", choices=("fp8",), default=None,
                   help="also read the control: the reference at this precision")
    p.add_argument("--dump", default=None, help="write the run's details to this JSON file")
    return p.parse_args(argv)


def run_deadline(started: float, markers) -> float:
    return started + (RUN_DEADLINE_S if all(m.exists() for m in markers) else COLD_DEADLINE_S)


def run(args, *, require_platform: str | None = "tpu", launcher=None, bench_path=None,
        bench_dir: Path = BENCH_DIR, env_overlay=None,
        started: float | None = None) -> tuple[int, dict | None]:
    """``started`` is the instant set-up is counted from and the deadline
    runs from: the process's start for ``main``, now for a caller that
    imported this module long ago."""
    started = time.monotonic() if started is None else started
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    loaded = load_cell(bench, args.workload, bench_dir)
    cell, config, mix = loaded["cell"], loaded["config"], loaded["mix"]
    if not (ROOT / "dynamo_tpu" / "cli" / "run.py").exists():
        print("benchmark/run.py needs the program (dynamo_tpu/) beside it", file=sys.stderr)
        return 2, None
    hf = hf_config(config)
    seconds = float(args.seconds)
    rate = loaded["rate"]
    weights_seed = args.seed % 2147483647
    work = WORK / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    model_dir = work / "model"
    write_model_dir(model_dir, config)
    plan = traffic.schedule(mix, rate, seconds)
    filled = traffic.fill(plan, mix, hf["vocab_size"], args.seed)
    plan_by_index = {r["index"]: r for r in plan}
    gap_requests = loaded["own"].get("gap_requests")
    if gap_requests and gap_requests[1] > sum(1 for r in plan if not r["probe"]):
        raise SystemExit(f"--seconds {seconds:g} offers fewer requests than the cell's "
                         f"gap_requests {gap_requests}: itl_p95_ms would be another set's")
    serving = [str(a) for a in [*config["serving"]["args"], *loaded["own"].get("serving_args", [])]]
    markers = warm_markers(cache_dir(), args.workload)
    deadline = run_deadline(started, markers)
    server = Server(model_dir, serving, weights_seed, work / "server.log", deadline,
                    launcher=launcher, env_overlay=env_overlay)
    holder: dict = {"samples": []}
    try:
        device = server.json_after("jax devices: ", "jax to come up")
        say(f"device: {json.dumps(device)}")
        if require_platform and (device["platform"] != require_platform
                                 or device["count"] < cell["chips"]):
            print(f"need {cell['chips']} {require_platform} chip(s), server sees {device}",
                  file=sys.stderr)
            return 1, None
        server.wait_for("listening on http://", "the server to listen")
        ready_s = time.monotonic() - started
        warm = warm_up(server.port, filled, hf["vocab_size"], args.seed)
        bad = [r["error"] for r in warm if r["error"]]
        if bad:
            raise BenchFailure(f"warm-up request failed: {bad[0]}")

        def stats_into(key):
            def hook():
                holder[key] = server.ask("/stats")
            return hook

        hooks = [(0.0, stats_into("stats0")), (seconds, stats_into("stats1"))]
        if args.trace:
            span = min(float(mix.get("trace_s", 3.0)), seconds / 2)
            t_a = seconds / 2 - span / 2
            trace_dir = work / "trace"
            hooks += [(t_a, lambda: server.ask(f"/trace/start?dir={trace_dir}", post=True)),
                      (t_a + span, lambda: server.ask("/trace/stop", post=True))]
            hooks += [(float(s), lambda: holder["samples"].append(server.ask("/stats")["stats"]))
                      for s in range(1, int(seconds)) if not t_a - 1 <= s <= t_a + span + 1]
        driven = client.drive(server.port, "bench", plan, filled, seconds,
                              float(mix.get("drain_s", 30.0)), hooks)
        setup_s = driven["t0"] - started
        after = server.ask("/stats")
        trace = None
        if args.trace:
            keep = json.dumps(op_patterns(metric_specs(bench, args.workload, bench_dir)))
            trace = server.ask("/trace/reduce?" + urllib.parse.urlencode({"keep": keep}),
                               timeout=240.0)
    finally:
        server.stop()

    records = driven["records"]
    probes = [r for r in records if r["probe"]]
    records = [r for r in records if not r["probe"]]
    failed = [r for r in records + probes if r["error"] and not r["error"].startswith("cancelled")]
    mismatched = [r for r in records if r["done"] is not None and not r["error"] and (
        len(r["ids"]) != r["output_len"]
        or (r["usage"] or {}).get("prompt_tokens") != r["prompt_tokens"])]
    say(f"offered {len(records)} requests at {rate} /s; still in flight when the window "
        f"closed {driven['in_flight_at_close']}; cancelled at the drain deadline "
        f"{driven['cancelled']}; failed {len(failed)}")
    for r in failed[:5]:
        say(f"failed request {r['index']}: {r['error']}")
    if holder.get("stats0") and holder.get("stats1"):
        s0, s1 = holder["stats0"]["stats"], holder["stats1"]["stats"]
        say("in the window: " + ", ".join(
            f"{k} {s1[k] - s0[k]}" for k in ("num_preemptions_total", "prefill_tokens_total",
                                             "decode_tokens_total", "engine_busy_steps_total")
            if k in s0 and k in s1)
            + f"; waiting at its close {s1.get('num_requests_waiting')}")
    e2e = arith.end_to_end(records, seconds, miss_ms=(seconds + float(mix.get("drain_s", 30.0))) * 1e3,
                           gap_requests=gap_requests)
    e2e["setup_s"] = setup_s
    peaks = [m.get("peak_bytes_in_use") or 0 for s in (holder.get("stats1"), after) if s
             for m in s["memory"]]
    device_out = dict(device, memory_peak_bytes=max(peaks) if peaks else None)

    limits = config["limits"]
    sample = pick_sample(records + probes, plan_by_index, filled, int(mix.get("check_requests", 6)), args.seed)
    t_ref = time.monotonic()
    check = run_reference(
        {"hf": hf, "reference": str(loaded["reference"]), "weights_seed": weights_seed,
         "samples": sample, "control": args.control,
         "require_platform": require_platform}, work, deadline) if sample else {}
    ref_s = time.monotonic() - t_ref
    counts = {"failed": len(failed), "mismatched": len(mismatched)}
    correct, lines = compare(check, counts, limits)
    if args.control and check:
        lines.append(f"control ({args.control} reference) logit_gap_max {check.get('control_gap_max')!r} "
                     f"logprob_err_mean {check.get('control_logprob_err_mean')!r} "
                     f"topk_err_mean {check.get('control_topk_err_mean')!r}")
    say(f"ready after {ready_s:.1f} s, set-up {setup_s:.1f} s, reference {ref_s:.1f} s over "
        f"{check.get('tokens', 0)} served tokens of {len(sample)} requests "
        f"({check.get('mismatch', '?')} not the reference's first choice)")

    if args.trace:
        ctx = {"records": records, "seconds": seconds, "stats0": holder.get("stats0"),
               "stats1": holder.get("stats1"), "samples": holder["samples"], "trace": trace,
               "hf": hf, "config": config, "mix": mix, "e2e": e2e, "shapes": loaded["shapes"],
               "peaks": load_peaks(device["kind"]) if require_platform else None}
        metrics = read_per_layer(bench, args.workload, ctx, bench_dir)
        device_out.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in metrics_of(bench, args.workload, "end_to_end") if m["name"] in e2e}
    result = {"correct": bool(correct), "attempted": len(records), "failed": len(failed),
              "metrics": metrics, "device": device_out}
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["gap_kinds"]}
    # each number compared beside its limit, last in the line (a number that
    # could not be read is null: the line stays JSON)
    result["compared"] = {name: {"value": value if value < float("inf") else None, "limit": limit}
                          for name, value, limit in compared(check, counts, limits)}
    if args.dump:
        Path(args.dump).parent.mkdir(parents=True, exist_ok=True)
        Path(args.dump).write_text(json.dumps({
            "args": vars(args), "result": result, "e2e": e2e, "check": check, "trace": trace,
            "stats0": holder.get("stats0"), "stats1": holder.get("stats1"), "ready_s": ready_s,
            "reference_s": ref_s, "in_flight_at_close": driven["in_flight_at_close"],
            "late_ms": [(r["sent"] - r["due"]) * 1e3 for r in records if r["sent"] is not None],
            "ttfts_ms": sorted(arith.ttfts_ms(records, seconds, 0.0)),
            "timeline": [[r["index"], r["due"], r["sent"], r["prompt_tokens"], r["done"],
                          [t for t, _ in r["chunks"]]] for r in records],
        }))
    for line in lines:
        print(line, flush=True)
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    for marker in markers:
        marker.parent.mkdir(parents=True, exist_ok=True)
        marker.touch()
    return 0, result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        rc, _ = run(args, started=_T_START)
    except BenchFailure as exc:
        print(f"benchmark failed: {exc}", file=sys.stderr)
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
