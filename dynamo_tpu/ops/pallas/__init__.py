"""Pallas TPU kernels for the hot paths.

- ``paged_attention``: decode-step attention reading KV pages from HBM via
  scalar-prefetched block tables — no materialized gather (the pure-JAX
  fallback in ``dynamo_tpu.ops.attention`` gathers [B, max_len] into HBM).
- ``block_copy``: batched KV block gather/scatter between cache pools
  (replaces the reference's CUDA block-copy kernel,
  lib/llm/src/kernels/block_copy.cu, with a TPU-native kernel).

Kernels run in interpret mode on CPU (tests) and compiled on TPU.
"""

from dynamo_tpu.ops.pallas.paged_attention import (
    paged_attention_decode,
    paged_window_attention_decode,
)
from dynamo_tpu.ops.pallas.ragged_attention import (
    kv_step_pages,
    pack_spans,
    ragged_paged_attention,
)
from dynamo_tpu.ops.pallas.mla_attention import ragged_mla_attention
from dynamo_tpu.ops.pallas.block_copy import gather_blocks, scatter_blocks

__all__ = [
    "paged_attention_decode",
    "paged_window_attention_decode",
    "ragged_paged_attention",
    "ragged_mla_attention",
    "pack_spans",
    "kv_step_pages",
    "gather_blocks",
    "scatter_blocks",
]
