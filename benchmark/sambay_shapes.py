"""Counts from the shapes of a ``phi4flash`` configuration (SambaY with
differential attention): parameters, bytes, operations, the two pools and
the recurrent state a lane.

Pure Python, from the keys of the configuration's file alone (the interface
is in ``benchmark/README.md``).  The sizes the published config has no key
for are the family's (``assumed`` in the configuration's file): ``d_state``
16, ``d_conv`` 4, expand 2, ``dt_rank`` ceil(hidden / 16); a key
``mamba_<name>`` overrides one.  Layer ``l`` of ``L``: even and ``<= L/2`` a
state-space layer, odd and ``< L/2`` window attention, ``L/2 + 1`` full
attention (the ONE layer whose keys and values grow with the context), even
and above a gated memory unit, odd and above a cross layer that reads layer
``L/2 + 1``'s pages and holds no keys of its own.

This module is loaded before anything is started (``run.py:load_cell``), so
it is also where a checkout whose program has no ``phi4flash`` family is
told so at once (served there, the configuration would be read as a dense
llama-like model and timed as one).
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

BF16_BYTES = 2
F32_BYTES = 4
BLOCK = 16      # the program's default --kv-block-size

for _entry in sys.path:
    _program = Path(_entry or ".") / "dynamo_tpu" / "models"
    if _program.is_dir():
        if not (_program / "phi4flash.py").is_file():
            raise SystemExit(
                f"the program under {_program.parent} has no phi4flash family "
                "(dynamo_tpu/models/phi4flash.py): this configuration cannot be served by it"
            )
        break


def sizes(hf: dict) -> dict:
    h, layers = hf["hidden_size"], hf["num_hidden_layers"]
    return {
        "h": h, "i": hf["intermediate_size"], "v": hf["vocab_size"],
        "qd": h, "kvd": hf["num_key_value_heads"] * (h // hf["num_attention_heads"]),
        "d": h // hf["num_attention_heads"],
        "n": int(hf.get("mamba_d_state", 16)), "taps": int(hf.get("mamba_d_conv", 4)),
        "di": int(hf.get("mamba_expand", 2)) * h,
        "r": int(hf.get("mamba_dt_rank") or math.ceil(h / 16)),
        "ssm": layers // 4 + 1, "window": layers // 4, "full": 1,
        "gmu": layers // 4 - 1, "cross": layers // 4 - 1,
    }


def mixer_params(hf: dict) -> dict:
    """``kind -> (matrix parameters, float32 parameters)`` of one layer's
    mixer (its biases, taps, ``A_log``, ``D``, ``lambda`` vectors and the
    pair norm are the float32 ones)."""
    s = sizes(hf)
    h, di, n, r, qd, kvd = s["h"], s["di"], s["n"], s["r"], s["qd"], s["kvd"]
    diff = 4 * s["d"] + 2 * s["d"]
    return {
        "ssm": (h * 2 * di + di * (r + 2 * n) + r * di + di * h,
                s["taps"] * di + di + di + n * di + di),
        "window": (h * (qd + 2 * kvd) + qd * h, qd + 2 * kvd + h + diff),
        "full": (h * (qd + 2 * kvd) + qd * h, qd + 2 * kvd + h + diff),
        "gmu": (2 * h * di, 0),
        "cross": (2 * h * qd, qd + h + diff),
    }


def _layers(hf: dict):
    s = sizes(hf)
    mixers = mixer_params(hf)
    return [(s[kind], *mixers[kind]) for kind in ("ssm", "window", "full", "gmu", "cross")]


def matmul_params(hf: dict) -> int:
    """Parameters one token multiplies against: every layer's mixer and MLP
    matrices and the head (the tied embedding)."""
    s = sizes(hf)
    mlp = 3 * s["h"] * s["i"]
    return sum(count * (matrix + mlp) for count, matrix, _ in _layers(hf)) + s["v"] * s["h"]


def float32_params(hf: dict) -> int:
    """The small leaves kept in float32: the mixers' own, two LayerNorms a
    layer (weight and bias) and the final one."""
    s = sizes(hf)
    return sum(count * (small + 4 * s["h"]) for count, _, small in _layers(hf)) + 2 * s["h"]


def total_params(hf: dict) -> int:
    """Every parameter held on the chip (the tied embedding once)."""
    return matmul_params(hf) + float32_params(hf)


def flops_per_token(hf: dict) -> int:
    """2 x ``matmul_params``; attention's own products and the recurrence
    are left out, so a utilization built on this reads low, never high."""
    return 2 * matmul_params(hf)


def weight_bytes(hf: dict) -> int:
    """Bytes a decode step streams for the weights: every matrix (the tied
    embedding is the head) and the float32 leaves."""
    return BF16_BYTES * matmul_params(hf) + F32_BYTES * float32_params(hf)


def kv_bytes_per_token(hf: dict) -> int:
    """Cache bytes that GROW with each token of context: ONE layer's keys
    and values.  The window layers hold a window a lane, the state-space
    layers a state a lane, the cross layers nothing."""
    return 2 * sizes(hf)["kvd"] * BF16_BYTES


def state_bytes_per_lane(hf: dict) -> int:
    """A lane's recurrent state (float32) and convolution taps, every
    state-space layer."""
    s = sizes(hf)
    return s["ssm"] * (F32_BYTES * s["n"] * s["di"] + BF16_BYTES * (s["taps"] - 1) * s["di"])


def window_pool_blocks(hf: dict, lanes: int, context: int, block: int = BLOCK) -> int:
    """Blocks of the window layers' pool, as the program sizes it
    (``models/phi4flash.py:window_pool_blocks``; a test holds the two
    together): one whole prompt, a window and two blocks a lane, a hundredth."""
    a_prompt = -(-context // block)
    a_lane = -(-hf["sliding_window"] // block) + 2
    return a_prompt + lanes * a_lane + max(1, (a_prompt + lanes * a_lane) // 100)


def cache_bytes(hf: dict, serving: dict) -> int:
    """The full pool (``--num-blocks`` pages of ONE layer), the window pool
    (its pages of the window layers) and the state of every lane."""
    args = serving["args"]
    arg = lambda name, default=None: int(args[args.index(name) + 1]) if name in args else default  # noqa: E731
    block = arg("--kv-block-size", BLOCK)
    lanes = arg("--max-batch-size", 8)
    a_page = kv_bytes_per_token(hf) * block
    window = window_pool_blocks(hf, lanes, arg("--context-length"), block)
    return (arg("--num-blocks") * a_page + window * sizes(hf)["window"] * a_page
            + lanes * state_bytes_per_lane(hf))
