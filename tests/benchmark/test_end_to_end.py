"""The rest of a run with the look for a chip skipped: the harness drives the
program's own server on the CPU at a tiny size, samples what it served and
holds it against the reference.

- sound runs come out ``correct``, on three seeds;
- the control (the float8 reference in the program's place) reads above the
  limit on the same seeds at this size;
- with the timed path broken underneath (the model's logits rolled by one
  id where they are produced) ``correct`` comes out false;
- a cell that carries ``gap_requests`` reports the tail of those answers' gaps.
"""

import json
import sys
import textwrap
from pathlib import Path

import pytest

from benchmark import arith
from benchmark import run as runner

from .helpers import TINY_MISTRAL, TINY_MIX, tiny_bench

ROOT = Path(__file__).resolve().parents[2]
# set from the readings this file prints (see test_sound_and_control): at
# this size (CPU, PR 26) the bf16 program read topk_err_mean 0.0069-0.0074 and
# logprob_err_mean 0.0044-0.0048 over these seeds, the float8 control
# 0.0573-0.0576 and 0.040-0.043; the widest gap 0.009 against 0.10-0.20
LIMITS = {"gap_max": 0.05, "logprob_err_mean": 0.014, "topk_err_mean": 0.02,
          "min_checked_tokens": 100, "min_probed_tokens": 40}
SEEDS = [11, 2**31 + 5, 987654321]


def drive(tmp_path, seed, *, launcher=None, control=None, own=None, seconds=3):
    hf = dict(TINY_MISTRAL, vocab_size=2000)
    bench = tiny_bench(tmp_path, hf, LIMITS)
    if own:
        (tmp_path / "cells" / "tiny.tinychat.json").write_text(json.dumps(own))
    mix = dict(TINY_MIX, output_tokens={"dist": "lognormal", "median": 40, "sigma": 0.3,
                                        "min": 24, "max": 64}, check_requests=6)
    (tmp_path / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    dump = tmp_path / "dump.json"
    argv = ["--workload", "tiny.tinychat", "--seed", str(seed), "--seconds", str(seconds),
            "--trace", "0", "--dump", str(dump)] + (["--control", control] if control else [])
    rc, result = runner.run(runner.parse(argv), require_platform=None, launcher=launcher,
                            bench_path=bench, bench_dir=tmp_path,
                            env_overlay={"JAX_PLATFORMS": "cpu"})
    return rc, result, json.loads(dump.read_text())


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_and_control(tmp_path, seed):
    rc, result, dump = drive(tmp_path, seed, control="fp8")
    check = dump["check"]
    print(f"seed {seed}: sound gap_max {check['gap_max']:.4f} logprob_err_mean "
          f"{check['logprob_err_mean']:.5f} topk_err_mean {check['topk_err_mean']:.5f}; control "
          f"gap_max {check['control_gap_max']:.4f} logprob_err_mean "
          f"{check['control_logprob_err_mean']:.5f} topk_err_mean {check['control_topk_err_mean']:.5f}")
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    # each number compared beside its limit, last in the line, as the lines on both streams have them
    assert list(result["compared"]) == ["failed_requests", "token_count_mismatches", "checked_tokens_min",
                                        "logit_gap_max", "logprob_err_mean", "probed_tokens_min", "topk_err_mean"]
    assert result["compared"]["topk_err_mean"] == {"value": check["topk_err_mean"], "limit": LIMITS["topk_err_mean"]}
    assert result["compared"]["checked_tokens_min"] == {"value": -check["tokens"], "limit": -LIMITS["min_checked_tokens"]}
    assert all(row["value"] <= row["limit"] for row in result["compared"].values())
    assert set(result["metrics"]) == {"itl_p50_ms", "itl_p95_ms", "tok_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert check["tokens"] >= LIMITS["min_checked_tokens"]
    # the control, put in the program's place, is not correct
    assert check["probed_tokens"] >= LIMITS["min_probed_tokens"]
    assert check["control_topk_err_mean"] > 2 * LIMITS["topk_err_mean"]
    assert check["topk_err_mean"] < LIMITS["topk_err_mean"] / 2


def test_a_broken_timed_path_is_not_correct(tmp_path):
    launcher = tmp_path / "broken_launcher.py"
    launcher.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import jax.numpy as jnp
        import dynamo_tpu.models.llama as llama
        sound = llama._logits
        llama._logits = lambda params, cfg, x: jnp.roll(sound(params, cfg, x), 1, axis=-1)
        from benchmark import launcher
        raise SystemExit(launcher.main(sys.argv[1:]))
    """))
    rc, result, dump = drive(tmp_path, SEEDS[0], launcher=[sys.executable, str(launcher)])
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False
    assert result["compared"]["logit_gap_max"] == {"value": dump["check"]["gap_max"], "limit": LIMITS["gap_max"]}
    assert dump["check"]["gap_max"] > LIMITS["gap_max"]
    assert dump["check"]["topk_err_mean"] > LIMITS["topk_err_mean"]


def test_a_cell_with_a_range_reads_its_tail_over_those_answers(tmp_path):
    own = {"rate_rps": 3.0, "gap_requests": [2, 7]}
    rc, result, dump = drive(tmp_path, SEEDS[0], own=own)
    assert rc == 0 and result["correct"] is True
    gaps = [(b - a) * 1e3 for index, *_, chunks in dump["timeline"] if 2 <= index < 7
            for a, b in zip(chunks, chunks[1:])]
    assert len(gaps) > 100
    assert result["metrics"]["itl_p95_ms"]["value"] == arith.percentile(gaps, 95) == dump["e2e"]["itl_p95_ms"]
    inside = [(b - a) * 1e3 for *_, chunks in dump["timeline"] for a, b in zip(chunks, chunks[1:]) if 0 <= b <= 3]
    assert dump["e2e"]["itl_p95_window_ms"] == arith.percentile(inside, 95)
    assert set(gaps) != set(inside)
    # a window that offers fewer requests than the range names is refused, not read over another set
    with pytest.raises(SystemExit, match="gap_requests"):
        drive(tmp_path, SEEDS[0], own=dict(own, gap_requests=[2, 70]))
