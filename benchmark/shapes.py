"""Counts from a configuration's shapes: parameters, bytes, operations.

The yardstick's own arithmetic (kept here so that no later PR can move it):
everything is computed from the keys of the published ``config.json`` that a
configuration's file quotes, never from the program.

``load_peaks`` is the harness's, shared by every configuration.  The rest is
the arithmetic of ONE kind of model, a llama-like dense decoder with a
per-token key/value cache, and is reached only through a configuration's
``shapes`` key (``benchmark/modules.py``); a configuration with experts, a
latent cache or a recurrent state names a module of its own that offers the
same functions (``benchmark/README.md``).
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
BF16_BYTES = 2


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``.  A device that is not in the
    table is an error, not a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def layer_params(hf: dict) -> int:
    """Parameters of one transformer block (matrices and norms)."""
    h, i, d = hf["hidden_size"], hf["intermediate_size"], head_dim(hf)
    qd, kvd = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    n = 2 * h * qd + 2 * h * kvd + 3 * h * i + 2 * h
    if hf.get("model_type") == "qwen3":
        n += 2 * d  # per-head q/k RMSNorm
    return n


def total_params(hf: dict) -> int:
    """Every parameter held on the chip."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    embeds = v * h * (1 if hf.get("tie_word_embeddings") else 2)
    return hf["num_hidden_layers"] * layer_params(hf) + embeds + h


def matmul_params(hf: dict) -> int:
    """Parameters a token multiplies against in one forward pass: the blocks
    and the output head.  The embedding lookup is a gather, not a product."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    return hf["num_hidden_layers"] * layer_params(hf) + v * h


def weight_bytes(hf: dict) -> int:
    """Bytes a step streams from HBM for the weights (bf16): the blocks, the
    final norm and the output head; a tied head is the embedding table."""
    return BF16_BYTES * (matmul_params(hf) + hf["hidden_size"])


def kv_bytes_per_token(hf: dict) -> int:
    return 2 * hf["num_hidden_layers"] * hf["num_key_value_heads"] * head_dim(hf) * BF16_BYTES


def cache_bytes(hf: dict, serving: dict) -> int:
    """Bytes the serving arguments reserve on the device for per-token and
    per-sequence state: here, ``--num-blocks`` pages of ``--kv-block-size``
    (the program's default 16) tokens of keys and values, nothing a sequence."""
    args = serving["args"]
    block = args[args.index("--kv-block-size") + 1] if "--kv-block-size" in args else 16
    return int(args[args.index("--num-blocks") + 1]) * int(block) * kv_bytes_per_token(hf)


def flops_per_token(hf: dict) -> int:
    """2 x the parameters a token multiplies against.  Attention's own
    products (2 x 2 x context x heads x head_dim a layer) are left out, so a
    utilization built on this reads low at long contexts, never high."""
    return 2 * matmul_params(hf)
