"""Paged decode attention — Pallas TPU kernel.

One grid step = (sequence, page): the page's K/V tiles are pipelined from
HBM into VMEM by the BlockSpec index_map reading the scalar-prefetched block
table (so the "gather" is just DMA addressing), and softmax is accumulated
online flash-style in VMEM scratch across a sequence's pages.

Layout notes (TPU tiling / Mosaic):
- A cache page [bs, KVH, D] is viewed flat as [bs*KVH, D] (an HBM reshape,
  free) so every matmul in the kernel is plain 2-D — Mosaic's tpu.matmul
  does not accept batched operands whose batch dims sit at different
  positions, which is exactly what a per-kv-head batched dot over
  [KVH, G, D] × [bs, KVH, D] lowers to.
- GQA head matching is done with iota masks on the score matrix
  [H, bs*KVH]: column j*KVH+c holds page position j of kv head c, and query
  head h only keeps columns with c == h // groups.  The masked entries cost
  KVH× extra MACs, but decode attention is HBM-bandwidth-bound (the page
  streams dominate) and the whole score matmul is a single MXU tile pass,
  so the "waste" is free in wall-clock terms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _window_kernel(
    block_tables_ref,   # [B, maxb] int32
    context_lens_ref,   # [B] int32 — INCLUDING the window's last token
    q_ref,              # [1, W*H, D]   (w-major fold: row = w*H + h)
    *refs,              # pps × (k_page [1, bs*KVH, D], v_page), out, scratch
    block_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    max_blocks: int,
    window: int,
    pages_per_step: int,
    sliding_window: int | None,
):
    """Online-softmax page loop over flat [bs*KVH, D] pages.  The W window
    queries (W=1 for plain decode) fold into the row axis; each query row
    masks to its own absolute position.  ``sliding_window`` (Mistral-style)
    additionally drops positions more than W_s-1 behind each query.
    ``pages_per_step`` consecutive pages ride one grid step, each as its
    own input stream (the index maps clamp past-the-end page indices to
    the last block; their compute is gated off here)."""
    pps = pages_per_step
    kv_refs = refs[: 2 * pps]
    out_ref = refs[2 * pps]
    m_ref, l_ref, acc_ref = refs[2 * pps + 1:]
    seq = pl.program_id(0)
    step = pl.program_id(1)
    ctx = context_lens_ref[seq]
    rows = block_size * num_kv_heads
    h_all = num_kv_heads * groups
    wh = window * h_all

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i in range(pps):
        page = step * pps + i
        page_start = page * block_size
        k_page_ref = kv_refs[2 * i]
        v_page_ref = kv_refs[2 * i + 1]

        # ctx <= max_blocks * block_size, so past-the-end pages (page >=
        # max_blocks when pps does not divide maxb) fail this gate too
        active = page_start < ctx
        if sliding_window is not None:
            # pages entirely below every query's window contribute
            # nothing — skip their compute (their DMA is also deduped:
            # the index_map clamps them to the first in-window page).
            # Lowest visible absolute position =
            # (ctx - window) - (sliding_window - 1).
            active &= (
                page_start + block_size > ctx - window - (sliding_window - 1)
            )

        @pl.when(active)
        def _compute(
            k_page_ref=k_page_ref, v_page_ref=v_page_ref,
            page_start=page_start,
        ):
            q = q_ref[0].astype(jnp.float32)        # [W*H, D]
            k = k_page_ref[0].astype(jnp.float32)   # [bs*KVH, D]
            v = v_page_ref[0].astype(jnp.float32)
            scale = 1.0 / (head_dim ** 0.5)
            s = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                    # [W*H, bs*KVH]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            pos = page_start + col // num_kv_heads
            kv_of_col = col % num_kv_heads
            row = jax.lax.broadcasted_iota(jnp.int32, (wh, 1), 0)
            kv_of_row = (row % h_all) // groups
            q_pos = ctx - window + row // h_all          # [W*H, 1]
            mask = (kv_of_col == kv_of_row) & (pos <= q_pos)
            if sliding_window is not None:
                mask = mask & (pos > q_pos - sliding_window)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[:, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(step == -(-max_blocks // pps) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "sliding_window", "pages_per_step"),
)
def paged_window_attention_decode(
    q: jnp.ndarray,            # [B, W, H, D]
    k_cache: jnp.ndarray,      # [N, bs, KVH, D]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, maxb] int32
    context_lens: jnp.ndarray,  # [B] int32 — INCLUDING the window's last token
    *,
    interpret: bool = False,
    sliding_window: int | None = None,
    pages_per_step: int = 1,
) -> jnp.ndarray:
    """Pallas multi-query paged attention for speculative verification
    (pure-JAX twin: ops/attention.py paged_window_attention).
    ``pages_per_step`` widens each grid step to DMA that many block-table
    pages (past-the-end indices clamp to the last block)."""
    b, w, h, d = q.shape
    n, bs, kvh, _ = k_cache.shape
    maxb = block_tables.shape[1]
    groups = h // kvh
    rows = bs * kvh
    wh = w * h
    pps = pages_per_step
    if pps < 1:
        raise ValueError(f"pages_per_step must be >= 1, got {pps}")
    if sliding_window is not None:
        # the pages a lane's queries can see: the window behind its first
        # query through its last, wherever the two fall in their pages
        seen = -(-(sliding_window + w - 1) // bs) + 1
        if seen < maxb:
            # a window far shorter than the tables (a window LAYER of a
            # long-context model): walk only those pages.  The tables are
            # cut to them and the lengths counted from the first, here in
            # XLA; the kernel's masks compare positions with positions, so
            # a common offset changes nothing, and its grid is lanes x
            # ``seen`` steps whatever the context.
            first = jnp.maximum(context_lens - w - (sliding_window - 1), 0) // bs
            block_tables = jnp.take_along_axis(
                block_tables,
                jnp.minimum(first[:, None] + jnp.arange(seen), maxb - 1),
                axis=1,
            )
            context_lens = context_lens - first * bs
            maxb = seen
    pps = min(pps, maxb)

    if sliding_window is None:
        def kv_map_at(i):
            def kv_map(s, p, bt, cl):
                return (bt[s, jnp.minimum(p * pps + i, maxb - 1)], 0, 0)
            return kv_map
    else:
        def kv_map_at(i):
            def kv_map(s, p, bt, cl):
                # clamp below-window pages to the first in-window page:
                # the pipeline then re-fetches the same block instead of
                # streaming pages whose compute is skipped
                lowest = cl[s] - w - (sliding_window - 1)
                p_min = jnp.maximum(lowest, 0) // bs
                page = jnp.minimum(p * pps + i, maxb - 1)
                return (bt[s, jnp.maximum(page, p_min)], 0, 0)
            return kv_map

    kv_specs = []
    for i in range(pps):
        m = kv_map_at(i)
        kv_specs += [
            pl.BlockSpec((1, rows, d), m),
            pl.BlockSpec((1, rows, d), m),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-maxb // pps)),
        in_specs=[
            pl.BlockSpec((1, wh, d), lambda s, p, bt, cl: (s, 0, 0)),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec((1, wh, d), lambda s, p, bt, cl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((wh, 128), jnp.float32),
            pltpu.VMEM((wh, 128), jnp.float32),
            pltpu.VMEM((wh, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _window_kernel,
        block_size=bs,
        num_kv_heads=kvh,
        groups=groups,
        head_dim=d,
        max_blocks=maxb,
        window=w,
        pages_per_step=pps,
        sliding_window=sliding_window,
    )
    k_flat = k_cache.reshape(n, rows, d)
    v_flat = v_cache.reshape(n, rows, d)
    kv_args = []
    for _ in range(pps):
        kv_args += [k_flat, v_flat]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, wh, d), q.dtype),
        interpret=interpret,
    )(
        block_tables, context_lens,
        q.reshape(b, wh, d),
        *kv_args,
    )
    return out.reshape(b, w, h, d)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "sliding_window", "pages_per_step"),
)
def paged_attention_decode(
    q: jnp.ndarray,            # [B, H, D]
    k_cache: jnp.ndarray,      # [N, bs, KVH, D]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, maxb] int32
    context_lens: jnp.ndarray,  # [B] int32
    *,
    interpret: bool = False,
    sliding_window: int | None = None,
    pages_per_step: int = 1,
) -> jnp.ndarray:
    # plain decode is the window kernel at W=1: `pos <= ctx - 1` ≡ `pos < ctx`
    out = paged_window_attention_decode(
        q[:, None], k_cache, v_cache, block_tables, context_lens,
        interpret=interpret, sliding_window=sliding_window,
        pages_per_step=pages_per_step,
    )
    return out[:, 0]
