"""Counts from the shapes of an ``xing4_0`` configuration (Xing4.0-29B-A4B:
``hc_mult`` residual streams a token around latent attention with a
compressed query, fine-grained experts all held, a shared expert):
parameters, bytes, operations, the latent cache.

Pure Python, from the keys of the configuration's file alone (the interface
is in ``benchmark/README.md``).  A token meets, in a sparse layer, the
router, the shared expert and the ``num_experts_per_tok`` experts it is
routed to, not the ``n_routed_experts`` held; and in every layer the two
sublayers' ``phi`` (``hc_mult x hidden_size`` by ``2 hc_mult + hc_mult^2``,
float32), which make its mixing coefficients.

The cache is the latent family's: a token takes, a layer, the latent
(``kv_lora_rank``) and the one rotated key STORED in whole 128-lane tiles
(``ROPE_TILE``: 64 -> 128): 1,280 B a token-layer at 512 + 64 in bf16.  The
residual streams are activations and take no cache.

This module is loaded before anything is started (``run.py:load_cell``), so
it is also where a checkout whose program has no residual streams is told so
at once, before a server is started that could only die on the model's name.
"""

from __future__ import annotations

import sys
from pathlib import Path

BF16_BYTES = 2
F32_BYTES = 4
BLOCK = 16          # the program's default --kv-block-size
ROPE_TILE = 128     # the rotated key is stored in whole tiles of this many lanes

for _entry in sys.path:
    _ops = Path(_entry or ".") / "dynamo_tpu" / "ops"
    if _ops.is_dir():
        if not (_ops / "hyper_connections.py").is_file():
            raise SystemExit(
                f"the program under {_ops.parents[1]} has no residual streams "
                "(dynamo_tpu/ops/hyper_connections.py) and does not know the model family "
                "'xing4_0': this configuration cannot be served by it"
            )
        break


def attention_params(hf: dict) -> int:
    """q (direct, or through its bottleneck and that norm), the latent
    down-projection and its norm, the two up-projections, o."""
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    r, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    q_out = heads * (hf["qk_nope_head_dim"] + rope)
    q_lora = hf.get("q_lora_rank") or 0
    q = h * q_lora + q_lora + q_lora * q_out if q_lora else h * q_out
    return (q + h * (r + rope) + r + r * heads * (hf["qk_nope_head_dim"] + hf["v_head_dim"])
            + heads * hf["v_head_dim"] * h)


def expert_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def router_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["n_routed_experts"]


def sparse_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)


def layer_params(hf: dict, sparse: bool, *, met: bool = False) -> int:
    """Parameters of one layer (``met``: those ONE token multiplies against),
    its two block norms and the selection bias left out."""
    n = attention_params(hf) + stream_params(hf, met=met)
    if not sparse:
        return n + 3 * hf["hidden_size"] * hf["intermediate_size"]
    routed = hf["num_experts_per_tok"] if met else hf["n_routed_experts"]
    return n + router_params(hf) + (hf.get("n_shared_experts", 0) + routed) * expert_params(hf)


def stream_params(hf: dict, *, met: bool = False) -> int:
    """One layer's mixing parameters: two sublayers' ``phi``, ``bias`` and
    three ``alpha`` (``met``: ``phi`` alone), all float32."""
    n = hf.get("hc_mult") or 1
    if n == 1:
        return 0
    outs = 2 * n + n * n
    return 2 * (n * hf["hidden_size"] * outs + (0 if met else outs + 3))


def _layers(hf: dict, **kw) -> int:
    dense = hf.get("first_k_dense_replace", 0)
    return dense * layer_params(hf, False, **kw) + sparse_layers(hf) * layer_params(hf, True, **kw)


def total_params(hf: dict) -> int:
    """Every parameter held on the chip (the norms and, under sigmoid
    routing, the selection bias included)."""
    h = hf["hidden_size"]
    bias = hf["n_routed_experts"] if hf.get("scoring_func") == "sigmoid" else 0
    head = 0 if hf.get("tie_word_embeddings") else hf["vocab_size"] * h
    return (_layers(hf) + hf["num_hidden_layers"] * 2 * h + sparse_layers(hf) * bias
            + hf["vocab_size"] * h + head + h)


def matmul_params(hf: dict) -> int:
    """Parameters one token multiplies against in a forward pass: the output
    head and, a layer, the two ``phi``, attention (the norm's weight is no
    product), then the dense MLP or the router, the shared expert and its
    routed experts."""
    norms = hf["num_hidden_layers"] * (hf["kv_lora_rank"] + (hf.get("q_lora_rank") or 0))
    return _layers(hf, met=True) - norms + hf["vocab_size"] * hf["hidden_size"]


def flops_per_token(hf: dict) -> int:
    """2 x ``matmul_params``; attention's own products are left out, so a
    utilization built on this reads low, never high."""
    return 2 * matmul_params(hf)


def weight_bytes(hf: dict) -> int:
    """Bytes a decode step streams at most: every matrix held but the
    looked-up embedding (a step of few lanes touches fewer experts); the
    mixing leaves are float32."""
    head = hf["vocab_size"] * hf["hidden_size"]
    streams = hf["num_hidden_layers"] * stream_params(hf)
    return BF16_BYTES * (_layers(hf) + head) + (F32_BYTES - BF16_BYTES) * streams


def held_bytes(hf: dict) -> int:
    """Bytes of everything held: bf16, but the mixing leaves and the
    selection bias, which are float32."""
    f32 = hf["num_hidden_layers"] * stream_params(hf) + sparse_layers(hf) * (
        hf["n_routed_experts"] if hf.get("scoring_func") == "sigmoid" else 0)
    return BF16_BYTES * total_params(hf) + (F32_BYTES - BF16_BYTES) * f32


def page_row(hf: dict) -> int:
    """Values a token takes in one layer's pages, as stored."""
    return hf["kv_lora_rank"] + -(-hf["qk_rope_head_dim"] // ROPE_TILE) * ROPE_TILE


def kv_bytes_per_token(hf: dict) -> int:
    """Cache bytes that grow with each token of context, all layers."""
    return hf["num_hidden_layers"] * page_row(hf) * BF16_BYTES


def cache_bytes(hf: dict, serving: dict) -> int:
    """``--num-blocks`` pages of every layer (the expert layers' counters
    that ride the same pytree are 24 bytes and not counted)."""
    args = serving["args"]
    arg = lambda name, default=None: int(args[args.index(name) + 1]) if name in args else default  # noqa: E731
    return arg("--num-blocks") * arg("--kv-block-size", BLOCK) * kv_bytes_per_token(hf)
