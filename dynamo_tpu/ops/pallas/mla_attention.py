"""MLA (multi-head latent attention) paged decode — Pallas TPU kernel.

DeepSeek's absorbed-form decode attends in latent space: per sequence the
queries are ``q_lat [H, R]`` (nope-part absorbed through the K up-projection)
and ``q_rope [H, P]``; the paged cache stores compressed latents ``ck [bs, R]``
(doubling as the values) and rope keys ``kr [bs, P]`` per page.  Scores are
the two-part sum ``q_lat·ck + q_rope·kr`` and the context is accumulated in
latent space (decompression through the V up-projection happens outside).

Same pipelining scheme as ``paged_attention.py``: one grid step =
(sequence, page), page tiles DMA'd via the scalar-prefetched block table,
online-softmax accumulation in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.ragged_attention import (
    kv_step_pages,
    row_routing,
    softmax_finish,
    softmax_init,
    softmax_update,
    walk_live_pages,
)

NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    block_tables_ref,   # [B, maxb] int32
    context_lens_ref,   # [B] int32
    # inputs
    q_lat_ref,          # [1, H, R]
    q_rope_ref,         # [1, H, P]
    *refs,              # pps × (ck_page [1, bs, R], kr_page [1, bs, P]),
                        # out [1, H, R], then m/l/acc scratch
    block_size: int,
    scale: float,
    max_blocks: int,
    pages_per_step: int,
):
    pps = pages_per_step
    kv_refs = refs[: 2 * pps]
    out_ref = refs[2 * pps]
    m_ref, l_ref, acc_ref = refs[2 * pps + 1:]
    seq = pl.program_id(0)
    step = pl.program_id(1)
    ctx = context_lens_ref[seq]

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i in range(pps):
        page = step * pps + i
        page_start = page * block_size
        ck_page_ref = kv_refs[2 * i]
        kr_page_ref = kv_refs[2 * i + 1]

        @pl.when(page_start < ctx)
        def _compute(
            ck_page_ref=ck_page_ref, kr_page_ref=kr_page_ref,
            page_start=page_start,
        ):
            q_lat = q_lat_ref[0].astype(jnp.float32)    # [H, R]
            q_rope = q_rope_ref[0].astype(jnp.float32)  # [H, P]
            ck = ck_page_ref[0].astype(jnp.float32)     # [bs, R]
            kr = kr_page_ref[0].astype(jnp.float32)     # [bs, P]
            # [H, bs] two-part scores, both contractions on the MXU
            s = (
                jax.lax.dot_general(
                    q_lat, ck, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                + jax.lax.dot_general(
                    q_rope, kr, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            ) * scale
            pos = page_start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1
            )
            s = jnp.where(pos < ctx, s, NEG_INF)

            m_prev = m_ref[:, :1]                       # [H, 1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                      # [H, bs]
            l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # [H, R] context in latent space: values ARE the latents
            pv = jax.lax.dot_general(
                p, ck, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(step == -(-max_blocks // pps) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def _window_kernel(
    block_tables_ref,   # [B, maxb] int32
    context_lens_ref,   # [B] int32 — INCLUDING the window's last token
    q_lat_ref,          # [1, W*H, R]  (w-major fold: row = w*H + h)
    q_rope_ref,         # [1, W*H, P]
    ck_page_ref,        # [1, bs, R]
    kr_page_ref,        # [1, bs, P]
    out_ref,            # [1, W*H, R]
    m_ref,              # [W*H, 128] f32
    l_ref,
    acc_ref,            # [W*H, R] f32
    *,
    block_size: int,
    scale: float,
    max_blocks: int,
    window: int,
    num_heads: int,
):
    """Speculative-verification variant: W window queries fold into the
    head axis; each query row masks to its own absolute position."""
    seq = pl.program_id(0)
    page = pl.program_id(1)
    ctx = context_lens_ref[seq]
    wh = window * num_heads

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page_start = page * block_size

    @pl.when(page_start < ctx)
    def _compute():
        q_lat = q_lat_ref[0].astype(jnp.float32)    # [W*H, R]
        q_rope = q_rope_ref[0].astype(jnp.float32)
        ck = ck_page_ref[0].astype(jnp.float32)
        kr = kr_page_ref[0].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q_lat, ck, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + jax.lax.dot_general(
                q_rope, kr, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * scale                                    # [W*H, bs]
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        w_idx = jax.lax.broadcasted_iota(jnp.int32, (wh, 1), 0) // num_heads
        q_pos = ctx - window + w_idx                  # [W*H, 1]
        s = jnp.where(pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, ck, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(page == max_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_window_attention_decode(
    q_lat: jnp.ndarray,         # [B, W, H, R]
    q_rope: jnp.ndarray,        # [B, W, H, P]
    ck_cache: jnp.ndarray,      # [N, bs, R]
    kr_cache: jnp.ndarray,      # [N, bs, P]
    block_tables: jnp.ndarray,  # [B, maxb] int32
    context_lens: jnp.ndarray,  # [B] int32 — INCLUDING the window's last token
    *,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query MLA paged attention for speculative verification.
    Returns the latent-space context [B, W, H, R] (float32)."""
    b, w, h, r = q_lat.shape
    p_dim = q_rope.shape[-1]
    bs = ck_cache.shape[1]
    maxb = block_tables.shape[1]
    wh = w * h

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[
            pl.BlockSpec((1, wh, r), lambda s, p, bt, cl: (s, 0, 0)),
            pl.BlockSpec((1, wh, p_dim), lambda s, p, bt, cl: (s, 0, 0)),
            pl.BlockSpec((1, bs, r), lambda s, p, bt, cl: (bt[s, p], 0, 0)),
            pl.BlockSpec((1, bs, p_dim), lambda s, p, bt, cl: (bt[s, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, wh, r), lambda s, p, bt, cl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((wh, 128), jnp.float32),
            pltpu.VMEM((wh, 128), jnp.float32),
            pltpu.VMEM((wh, r), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _window_kernel, block_size=bs, scale=scale, max_blocks=maxb,
        window=w, num_heads=h,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, wh, r), jnp.float32),
        interpret=interpret,
    )(
        block_tables, context_lens,
        q_lat.reshape(b, wh, r), q_rope.reshape(b, wh, p_dim),
        ck_cache, kr_cache,
    )
    return out.reshape(b, w, h, r)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "pages_per_step")
)
def mla_paged_attention_decode(
    q_lat: jnp.ndarray,         # [B, H, R] f32/bf16
    q_rope: jnp.ndarray,        # [B, H, P]
    ck_cache: jnp.ndarray,      # [N, bs, R] latent cache
    kr_cache: jnp.ndarray,      # [N, bs, P] rope-key cache
    block_tables: jnp.ndarray,  # [B, maxb] int32
    context_lens: jnp.ndarray,  # [B] int32
    *,
    scale: float,
    interpret: bool = False,
    pages_per_step: int = 1,
) -> jnp.ndarray:
    """Returns the latent-space context [B, H, R] (float32).
    ``pages_per_step`` widens each grid step to DMA that many block-table
    pages (autotuned; past-the-end indices clamp to the last block)."""
    b, h, r = q_lat.shape
    p_dim = q_rope.shape[-1]
    bs = ck_cache.shape[1]
    maxb = block_tables.shape[1]
    pps = pages_per_step
    if pps < 1:
        raise ValueError(f"pages_per_step must be >= 1, got {pps}")
    pps = min(pps, maxb)

    def kv_map_at(i):
        def kv_map(s, p, bt, cl):
            return (bt[s, jnp.minimum(p * pps + i, maxb - 1)], 0, 0)
        return kv_map

    kv_specs = []
    for i in range(pps):
        m = kv_map_at(i)
        kv_specs += [
            pl.BlockSpec((1, bs, r), m),
            pl.BlockSpec((1, bs, p_dim), m),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-maxb // pps)),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda s, p, bt, cl: (s, 0, 0)),
            pl.BlockSpec((1, h, p_dim), lambda s, p, bt, cl: (s, 0, 0)),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda s, p, bt, cl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, block_size=bs, scale=scale, max_blocks=maxb,
        pages_per_step=pps,
    )
    kv_args = []
    for _ in range(pps):
        kv_args += [ck_cache, kr_cache]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        interpret=interpret,
    )(block_tables, context_lens, q_lat, q_rope, *kv_args)


def _ragged_kernel(
    token_lane_ref,     # [T] int32 — owning lane per token (OOB = pad)
    token_pos_ref,      # [T] int32 — absolute position per token (-1 = pad)
    block_tables_ref,   # [lanes, max_blocks] int32
    span_lane_ref,      # [T] int32 — pack_spans
    span_first_ref,     # [T] int32
    span_count_ref,     # [T] int32
    kv_steps_ref,       # [num_tb] int32 — KV steps per token block
    q_lat_ref,          # [1, TB*H, R]  (token-major fold: row = tok*H + h)
    q_rope_ref,         # [1, TB*H, P]
    ck_hbm,             # [N, bs, R] whole latent cache, HBM
    kr_hbm,             # [N, bs, P]
    out_ref,            # [1, TB*H, R]
    ck_buf,             # [2, pages * bs, R] VMEM double buffer
    kr_buf,             # [2, pages * bs, P]
    sems,               # DMA semaphores [2, 2]
    m_ref, l_ref, acc_ref,
    *,
    block_size: int,
    scale: float,
    tb_tokens: int,
    num_heads: int,
    pages_per_step: int,
):
    """Ragged unified-batch MLA: the live-page loop of
    ops/pallas/ragged_attention.py (walk_live_pages) applied to the latent
    cache — two-part scores, latent-space accumulation (decompression
    outside)."""
    t = pl.program_id(0)
    base = t * tb_tokens

    softmax_init(m_ref, l_ref, acc_ref)
    q_lat = q_lat_ref[0].astype(jnp.float32)    # [TB*H, R]
    q_rope = q_rope_ref[0].astype(jnp.float32)  # [TB*H, P]
    pos_in_step = jax.lax.broadcasted_iota(
        jnp.int32, (1, pages_per_step * block_size), 1
    )
    row_lane, q_pos = row_routing(
        token_lane_ref, token_pos_ref, base, tb_tokens=tb_tokens,
        heads=num_heads,
    )

    def step_body(slot, step_lane, step_ord):
        ck = ck_buf[slot].astype(jnp.float32)     # [pages * bs, R]
        kr = kr_buf[slot].astype(jnp.float32)     # [pages * bs, P]
        s = (
            jax.lax.dot_general(
                q_lat, ck, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + jax.lax.dot_general(
                q_rope, kr, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * scale                                    # [TB*H, pages * bs]
        pos = step_ord * block_size + pos_in_step
        mask = (row_lane == step_lane) & (pos <= q_pos)
        softmax_update(s, mask, ck, m_ref, l_ref, acc_ref)

    walk_live_pages(
        base, kv_steps_ref[t], span_lane_ref, span_first_ref,
        span_count_ref, block_tables_ref,
        ((ck_hbm, ck_buf), (kr_hbm, kr_buf)), sems,
        tb_tokens=tb_tokens, pages_per_step=pages_per_step,
        step_body=step_body,
    )
    softmax_finish(out_ref.at[0], l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "tb_tokens", "interpret", "pages_per_step"),
)
def ragged_mla_attention(
    q_lat: jnp.ndarray,         # [T, H, R] flat ragged token batch
    q_rope: jnp.ndarray,        # [T, H, P]
    ck_cache: jnp.ndarray,      # [N, bs, R] latent cache (keys AND values)
    kr_cache: jnp.ndarray,      # [N, bs, P] rope-key cache
    token_lane: jnp.ndarray,    # [T] int32 owning lane (OOB = pad)
    token_pos: jnp.ndarray,     # [T] int32 absolute position (-1 = pad)
    block_tables: jnp.ndarray,  # [lanes, max_blocks] int32
    span_lane: jnp.ndarray,     # [T] int32 (pack_spans)
    span_first: jnp.ndarray,    # [T] int32
    span_count: jnp.ndarray,    # [T] int32
    kv_steps: jnp.ndarray,      # [T // tb_tokens] int32
    *,
    scale: float,
    tb_tokens: int = 8,
    interpret: bool = False,
    pages_per_step: int | None = None,
) -> jnp.ndarray:
    """Ragged unified-batch MLA paged attention with packed lanes: one
    launch over mixed chunked-prefill spans + decode tokens against the
    latent cache, only live pages copied, ``pages_per_step`` of them a KV
    step.  Returns the latent-space context [T, H, R] (float32); metadata
    comes from ragged_attention.pack_spans (same ``tb_tokens`` and
    ``pages_per_step``) and the latent block tables."""
    t_pad, h, r = q_lat.shape
    p_dim = q_rope.shape[-1]
    bs = ck_cache.shape[1]
    pps = pages_per_step or kv_step_pages(bs)
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    num_tb = t_pad // tb_tokens
    tbh = tb_tokens * h
    # a page is copied whole by DMA, whose rows must fill the 128-lane
    # tile: zero-pad a narrower rope part (DeepSeek's 64), which adds
    # exact zeros to the scores
    lane_pad = -p_dim % 128
    if lane_pad:
        widen = ((0, 0), (0, 0), (0, lane_pad))
        q_rope, kr_cache = jnp.pad(q_rope, widen), jnp.pad(kr_cache, widen)
        p_dim += lane_pad
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(num_tb,),
        in_specs=[
            pl.BlockSpec((1, tbh, r), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, tbh, p_dim), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tbh, r), lambda t, *_: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pps * bs, r), ck_cache.dtype),
            pltpu.VMEM((2, pps * bs, p_dim), kr_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((tbh, 128), jnp.float32),
            pltpu.VMEM((tbh, 128), jnp.float32),
            pltpu.VMEM((tbh, r), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        block_size=bs,
        scale=scale,
        tb_tokens=tb_tokens,
        num_heads=h,
        pages_per_step=pps,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tb, tbh, r), jnp.float32),
        interpret=interpret,
    )(
        token_lane, token_pos, block_tables, span_lane, span_first,
        span_count, kv_steps,
        q_lat.reshape(num_tb, tbh, r),
        q_rope.reshape(num_tb, tbh, p_dim),
        ck_cache, kr_cache,
    )
    return out.reshape(t_pad, h, r)
