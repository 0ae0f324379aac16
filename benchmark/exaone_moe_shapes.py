"""Counts from the shapes of an ``exaone_moe`` configuration that holds one
chip's share of each layer: parameters, bytes, operations, the two pools.

Pure Python, from the keys of the configuration's file alone (the interface
is in ``benchmark/README.md``).  ``num_experts`` is the experts HELD here,
``expert_parallel_size`` times that the router's width; ``layer_types`` and
``mlp_layer_types`` are read over the first ``num_hidden_layers`` layers.

A token meets, in a sparse layer, the router, the shared expert and the
experts it is routed to AND that are held here: ``num_experts_per_tok x
held / all`` of them on average (1 of its 8 at 16 of 128), not the 16 held.

This module is loaded before anything is started (``run.py:load_cell``), so
it is also where a checkout whose program has no ``exaone_moe`` family is
told so at once: served there, the configuration would be read as a dense
llama-like model (``serve.py`` falls back to ``llama`` for a ``model_type`` it
does not know) and timed as one.
"""

from __future__ import annotations

import sys
from pathlib import Path

BF16_BYTES = 2
F32_BYTES = 4
BLOCK = 16      # the program's default --kv-block-size

for _entry in sys.path:
    _program = Path(_entry or ".") / "dynamo_tpu" / "models"
    if _program.is_dir():
        if not (_program / "exaone_moe.py").is_file():
            raise SystemExit(
                f"the program under {_program.parent} has no exaone_moe family "
                "(dynamo_tpu/models/exaone_moe.py): this configuration cannot be served by it"
            )
        break


def _kinds(hf: dict):
    n = hf["num_hidden_layers"]
    return hf["layer_types"][:n], hf["mlp_layer_types"][:n]


def attention_params(hf: dict) -> int:
    """q, k, v, o and the two per-head norms of one layer."""
    h, d = hf["hidden_size"], hf["head_dim"]
    qd, kvd = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    return 2 * h * qd + 2 * h * kvd + 2 * d


def expert_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def experts_all(hf: dict) -> int:
    return hf["num_experts"] * hf.get("expert_parallel_size", 1)


def router_params(hf: dict) -> int:
    return hf["hidden_size"] * experts_all(hf)


def layer_params(hf: dict, mlp: str, *, met: bool = False) -> float:
    """Parameters of one layer held here (``met``: those ONE token multiplies
    against, on average), its two block norms left out."""
    n = attention_params(hf)
    if mlp == "dense":
        return n + 3 * hf["hidden_size"] * hf["intermediate_size"]
    routed = (hf["num_experts_per_tok"] * hf["num_experts"] / experts_all(hf)) if met else hf["num_experts"]
    return n + router_params(hf) + (hf.get("num_shared_experts", 0) + routed) * expert_params(hf)


def total_params(hf: dict) -> int:
    """Every parameter held on the chip (the selection bias and the norms
    included)."""
    h = hf["hidden_size"]
    _, mlps = _kinds(hf)
    layers = sum(layer_params(hf, m) + 2 * h + (experts_all(hf) if m == "sparse" else 0) for m in mlps)
    return int(layers) + 2 * hf["vocab_size"] * h + h


def matmul_params(hf: dict) -> int:
    """Parameters one token multiplies against in a forward pass: the
    output head and, a layer, attention, then the dense MLP or the router,
    the shared expert and the experts it is routed to that are held."""
    _, mlps = _kinds(hf)
    return int(sum(layer_params(hf, m, met=True) for m in mlps)) + hf["vocab_size"] * hf["hidden_size"]


def flops_per_token(hf: dict) -> int:
    """2 x ``matmul_params``; attention's own products are left out, so a
    utilization built on this reads low, never high."""
    return 2 * matmul_params(hf)


def weight_bytes(hf: dict) -> int:
    """Bytes a decode step streams at most: every matrix held but the
    looked-up embedding (a step of few lanes touches fewer experts)."""
    h = hf["hidden_size"]
    _, mlps = _kinds(hf)
    held = sum(layer_params(hf, m) - (router_params(hf) if m == "sparse" else 0) for m in mlps)
    routers = sum(router_params(hf) for m in mlps if m == "sparse")
    return int(BF16_BYTES * (held + hf["vocab_size"] * h) + F32_BYTES * routers)


def kv_bytes_per_token(hf: dict) -> int:
    """Cache bytes that GROW with each token of context: the full-attention
    layers' keys and values.  The window layers hold a window a lane."""
    attns, _ = _kinds(hf)
    full = sum(a == "full_attention" for a in attns)
    return 2 * full * hf["num_key_value_heads"] * hf["head_dim"] * BF16_BYTES


def window_pool_blocks(hf: dict, lanes: int, context: int, block: int = BLOCK) -> int:
    """Blocks of the window layers' pool, as the program sizes it
    (``models/exaone_moe.py:window_pool_blocks``; a test holds the two
    together): one whole prompt, a window and two blocks a lane, a hundredth."""
    a_prompt = -(-context // block)
    a_lane = -(-hf["sliding_window"] // block) + 2
    return a_prompt + lanes * a_lane + max(1, (a_prompt + lanes * a_lane) // 100)


def cache_bytes(hf: dict, serving: dict) -> int:
    """Both pools: ``--num-blocks`` pages of the full layers, and the window
    pool's pages of the window layers."""
    args = serving["args"]
    arg = lambda name, default=None: int(args[args.index(name) + 1]) if name in args else default  # noqa: E731
    block = arg("--kv-block-size", BLOCK)
    attns, _ = _kinds(hf)
    window_layers = sum(a == "sliding_attention" for a in attns)
    a_page = 2 * hf["num_key_value_heads"] * hf["head_dim"] * BF16_BYTES * block
    window = window_pool_blocks(hf, arg("--max-batch-size", 8), arg("--context-length"), block)
    return arg("--num-blocks") * block * kv_bytes_per_token(hf) + window * window_layers * a_page
