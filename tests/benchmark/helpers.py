"""A throwaway benchmark in a temporary directory: one tiny configuration,
one tiny mix, one cell, the real metrics' files copied beside them."""

from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_QWEN = dict(
    model_type="qwen3", vocab_size=300, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=True)
TINY_MISTRAL = dict(
    model_type="mistral", vocab_size=300, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=1e4,
    tie_word_embeddings=False, sliding_window=8)
LIMITS = {"gap_max": 1.0, "logprob_err_mean": 1.0, "topk_err_mean": 1.0,
          "min_checked_tokens": 10, "min_probed_tokens": 5}
TINY_MIX = {
    "arrivals": {"process": "poisson"},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 60},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.4, "min": 4, "max": 12},
    "shared_prefix": {"share": 0.5, "count": 2, "tokens": 32, "zipf_s": 1.0},
    "schedule_seed": 1, "probes": {"requests": 2, "top_logprobs": 20}, "lead_in_s": 0.5, "drain_s": 60.0, "check_requests": 4,
}


def tiny_bench(tmp: Path, hf: dict = TINY_QWEN, limits: dict | None = None, *,
               name: str = "tiny", own_modules: dict | None = None) -> Path:
    """Writes the throwaway benchmark under ``tmp``; returns the path of its
    BENCHMARK.json.  Its cell is ``<name>.tinychat``.  The configuration
    names the llama-like reference and shapes, copied beside it, or, with
    ``own_modules`` (``{"reference": source, "shapes": source}``), modules
    that exist nowhere but under ``tmp``."""
    for d in ("configs", "traffic", "cells", "metrics", "reference"):
        (tmp / d).mkdir(parents=True, exist_ok=True)
    if own_modules:
        names = {"reference": f"{name}_reference", "shapes": f"{name}_shapes"}
        (tmp / "reference" / f"{name}_reference.py").write_text(own_modules["reference"])
        (tmp / f"{name}_shapes.py").write_text(own_modules["shapes"])
    else:
        names = {"reference": "llama_like", "shapes": "shapes"}
        for rel in ("reference/llama_like.py", "shapes.py"):
            (tmp / rel).write_text((ROOT / "benchmark" / rel).read_text())
    config = dict(hf, **names, reduced=[], serving={"args": [
        "--num-blocks", 128, "--max-batch-size", 4, "--context-length", 256]},
        limits=limits or dict(LIMITS))
    (tmp / "configs" / f"{name}.json").write_text(json.dumps(config))
    (tmp / "traffic" / "tinychat.json").write_text(json.dumps(TINY_MIX))
    (tmp / "cells" / f"{name}.tinychat.json").write_text(json.dumps({"rate_rps": 3.0}))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = dict(real)
    bench["configs"] = [{"name": name, "source": "none", "reduced": [], "why": "test",
                         "file": str(tmp / "configs" / f"{name}.json")}]
    bench["workloads"] = [{"name": f"{name}.tinychat", "config": name, "traffic": "tinychat",
                           "chips": 1, "why": "test"}]
    bench["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                           for m in real["end_to_end"]]
    bench["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in real["per_layer"]]
    for m in bench["per_layer"]:
        src = ROOT / "benchmark" / "metrics" / f"{m['name']}.json"
        (tmp / "metrics" / src.name).write_text(src.read_text())
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path
