"""Ragged unified-batch paged attention — Pallas TPU kernel.

One launch consumes a RAGGED token batch: chunked-prefill spans and single
decode tokens from different sequences, flattened onto one token axis with
each token at its own absolute position (Ragged Paged Attention,
arxiv 2604.15464).  This is the kernel that lets the engine run mixed
prefill+decode as ONE dispatch — no separate prefill program, no
overlap-pipeline drain at sequence admission.

Layout (PACKED lanes — multiple sequences share one token block):

- the flat token axis is cut into fixed-size TOKEN BLOCKS of ``tb_tokens``
  rows; the host packs spans AND single decode tokens densely, so one
  block can carry up to ``tb_tokens`` different lanes (a 16-lane
  decode-heavy window fills 2 blocks of 8 instead of burning 16
  one-live-row blocks);
- per-token routing rides in scalar prefetch: ``token_lane[i]`` names
  token i's sequence lane and ``token_pos[i]`` its absolute position
  (-1 = padding row, fully masked) — the same metadata the XLA twin
  consumes;
- the KV side is a list of SPANS per token block, at most ``tb_tokens`` of
  them (one per lane present in the block, first-appearance order):
  ``(span_lane, span_first, span_count)`` = the lane, the ordinal of the
  first page its tokens can see and how many consecutive pages follow.
  Block t's span s sits at flat index ``t * tb_tokens + s``;
  ``page_total[t]`` is the sum of its counts.  The block tables ride in
  scalar prefetch too, so the kernel resolves ``(lane, ordinal)`` to a
  physical page itself;
- grid = (token blocks,).  The body walks exactly the block's LIVE pages:
  a loop of ``page_total[t]`` iterations (trip count read from scalar
  memory) over spans in order and ordinals ascending, each page fetched
  from the HBM-resident cache by a double-buffered async copy (the next
  page's copy is in flight while this one is computed) — no static
  worklist width, no dead steps;
- heads fold into the row axis like the window kernel (row = token*H + h)
  and GQA matching uses iota masks on the [TB*H, bs*KVH] score matrix;
- softmax accumulates online flash-style in VMEM scratch across a token
  block's pages; masking is per-row: a row participates in a page step iff
  its token's lane owns the page and the page position is causally visible
  (pos <= token_pos), which also confines every lane to its own pages.

Padding rows (position -1 / out-of-range lane) match no page and no
position — their l stays 0, the clamped denominator makes their output
rows zero, and the caller never reads them.

``pack_spans`` (plain numpy, host side) builds the span lists from the
per-token metadata; ``walk_live_pages`` is the loop skeleton, shared (with
the row routing and the online-softmax update) with the MLA ragged kernel
(ops/pallas/mla_attention.py), which brings its own scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def pack_spans(
    token_lane,     # [T] int — owning lane per token (OOB / pos<0 = pad)
    token_pos,      # [T] int — absolute position per token (-1 = pad)
    *,
    lanes: int,
    tb_tokens: int,
    block_size: int,
    sliding_window: int | None = None,
):
    """Host-side (numpy) span lists for the packed ragged kernels.

    For every token block: the lanes present in it (first-appearance
    order), and for each lane the run of pages holding kv positions its
    tokens can see — causally up to ``max(token_pos) // block_size`` and,
    under a sliding window, down from ``(min(token_pos) - W + 1) //
    block_size``.  Returns ``(span_lane, span_first, span_count,
    page_total)``: three int32 arrays of the flat token axis' length (block
    t's span s at ``t * tb_tokens + s``; unused entries lane -1, count 0)
    and the per-block sum of counts — the page iterations the kernel
    executes for that block."""
    token_lane = np.asarray(token_lane)
    token_pos = np.asarray(token_pos)
    t_pad = token_lane.shape[0]
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    lane = token_lane.reshape(-1, tb_tokens).astype(np.int64)
    pos = token_pos.reshape(-1, tb_tokens).astype(np.int64)
    valid = (pos >= 0) & (lane >= 0) & (lane < lanes)
    # same[b, r, r2]: rows r and r2 of block b are live tokens of one lane
    same = (
        (lane[:, :, None] == lane[:, None, :])
        & valid[:, :, None] & valid[:, None, :]
    )
    lo = np.where(same, pos[:, None, :], np.iinfo(np.int64).max).min(-1)
    hi = np.where(same, pos[:, None, :], -1).max(-1)
    earlier = np.tri(tb_tokens, k=-1, dtype=bool)  # r2 < r
    head = valid & ~(same & earlier).any(-1)        # a lane's first row
    first = np.zeros_like(lo)
    if sliding_window is not None:
        first = np.maximum(0, lo - (sliding_window - 1)) // block_size
    count = np.where(head, hi // block_size + 1 - first, 0)
    # live spans to the front of each block, first-appearance order kept
    order = np.argsort(~head, axis=1, kind="stable")

    def take(a, fill):
        packed = np.take_along_axis(np.where(head, a, fill), order, axis=1)
        return packed.reshape(-1).astype(np.int32)

    return (
        take(lane, -1), take(first, 0), take(count, 0),
        count.sum(axis=1).astype(np.int32),
    )


def walk_live_pages(
    base,               # first flat span index of this token block
    total,              # page iterations of this token block (page_total[t])
    span_lane_ref,      # [T] int32 SMEM (pack_spans)
    span_first_ref,     # [T] int32 SMEM
    span_count_ref,     # [T] int32 SMEM
    block_tables_ref,   # [lanes, max_blocks] int32 SMEM
    streams,            # ((cache ref [N, ...] in HBM, buffer ref [2, ...]), ...)
    sems,               # DMA semaphores [2, len(streams)]
    *,
    tb_tokens: int,
    page_body,          # (slot, lane, ordinal) -> None
):
    """Loop skeleton of the ragged kernels: visit every live page of one
    token block — spans in order, ordinals ascending — exactly ``total``
    iterations.  Each page of every stream is copied HBM -> VMEM buffer
    ``slot`` (double-buffered: iteration i computes slot i % 2 while the
    copy of page i + 1 flies into the other; a deeper ring bought nothing
    on a v5e, the page step is not bound by the copy), then ``page_body``
    runs on it."""

    def copies(slot, s, o):
        phys = block_tables_ref[span_lane_ref[base + s], o]
        return [
            pltpu.make_async_copy(
                cache.at[phys], buf.at[slot], sems.at[slot, i]
            )
            for i, (cache, buf) in enumerate(streams)
        ]

    @pl.when(total > 0)
    def _prologue():
        for c in copies(0, 0, span_first_ref[base]):
            c.start()

    def step(i, carry):
        s, o = carry
        slot = i % 2
        # the page after this one: the span's next, else the next span's
        # first (past the block's last page: anything, nobody copies it)
        last = o + 1 >= span_first_ref[base + s] + span_count_ref[base + s]
        s_next = jnp.where(last, s + 1, s)
        o_next = jnp.where(
            last,
            span_first_ref[base + jnp.minimum(s + 1, tb_tokens - 1)],
            o + 1,
        )

        @pl.when(i + 1 < total)
        def _prefetch():
            for c in copies(1 - slot, s_next, o_next):
                c.start()

        for c in copies(slot, s, o):
            c.wait()
        page_body(slot, span_lane_ref[base + s], o)
        return s_next, o_next

    jax.lax.fori_loop(0, total, step, (jnp.int32(0), span_first_ref[base]))


def row_routing(token_lane_ref, token_pos_ref, base, *, tb_tokens, heads):
    """Per-row lane and absolute position of one token block, [TB*H, 1]
    each: row r serves flat token base + r // H.  The scalar-prefetched
    per-token metadata is folded in as a select chain over the block's
    tokens (scalar reads broadcast against the row iota; no vector
    gather).  Pads read position -1."""
    tbh = tb_tokens * heads
    tok_of_row = jax.lax.broadcasted_iota(jnp.int32, (tbh, 1), 0) // heads
    q_pos = jnp.full((tbh, 1), -1, jnp.int32)
    row_lane = jnp.full((tbh, 1), -1, jnp.int32)
    for rr in range(tb_tokens):
        q_pos = jnp.where(tok_of_row == rr, token_pos_ref[base + rr], q_pos)
        row_lane = jnp.where(
            tok_of_row == rr, token_lane_ref[base + rr], row_lane
        )
    return row_lane, q_pos


def softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def softmax_update(s, mask, v, m_ref, l_ref, acc_ref):
    """One page of the online softmax: masked scores ``s`` [rows, kv] and
    values ``v`` [kv, D] folded into the running max / sum / accumulator."""
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


def softmax_finish(out_ref, l_ref, acc_ref):
    denom = jnp.maximum(l_ref[:, :1], 1e-20)
    out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def _ragged_kernel(
    token_lane_ref,     # [T] int32 — owning lane per token (OOB = pad)
    token_pos_ref,      # [T] int32 — absolute position per token (-1 = pad)
    block_tables_ref,   # [lanes, max_blocks] int32
    span_lane_ref,      # [T] int32 — pack_spans
    span_first_ref,     # [T] int32
    span_count_ref,     # [T] int32
    page_total_ref,     # [num_tb] int32 — page iterations per token block
    q_ref,              # [1, TB*H, D]   (token-major fold: row = tok*H + h)
    k_hbm,              # [N, bs*KVH, D] whole cache, HBM
    v_hbm,
    out_ref,            # [1, TB*H, D]
    k_buf,              # [2, bs*KVH, D] VMEM double buffer
    v_buf,
    sems,               # DMA semaphores [2, 2]
    m_ref, l_ref, acc_ref,
    *,
    block_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    tb_tokens: int,
    sliding_window: int | None,
):
    """Online-softmax loop over one packed token block's live pages."""
    t = pl.program_id(0)
    rows = block_size * num_kv_heads
    h_all = num_kv_heads * groups
    tbh = tb_tokens * h_all
    base = t * tb_tokens

    softmax_init(m_ref, l_ref, acc_ref)
    # what every page of this block shares: the queries, the per-row
    # routing and the GQA column/row match
    q = q_ref[0].astype(jnp.float32)        # [TB*H, D]
    scale = 1.0 / (head_dim ** 0.5)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    pos_in_page = col // num_kv_heads
    kv_of_col = col % num_kv_heads
    row = jax.lax.broadcasted_iota(jnp.int32, (tbh, 1), 0)
    kv_of_row = (row % h_all) // groups
    row_lane, q_pos = row_routing(
        token_lane_ref, token_pos_ref, base, tb_tokens=tb_tokens, heads=h_all
    )

    def page_body(slot, page_lane, page_ord):
        k = k_buf[slot].astype(jnp.float32)     # [bs*KVH, D]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # [TB*H, bs*KVH]
        pos = page_ord * block_size + pos_in_page
        # a row participates iff its token's lane owns this page and the
        # page position is causally visible (pads sit at q_pos = -1 and
        # match nothing; stale slots past a lane's context exceed every
        # q_pos of that lane, so causality masks them too)
        mask = (
            (kv_of_col == kv_of_row)
            & (row_lane == page_lane)
            & (pos <= q_pos)
        )
        if sliding_window is not None:
            mask = mask & (pos > q_pos - sliding_window)
        softmax_update(s, mask, v, m_ref, l_ref, acc_ref)

    walk_live_pages(
        base, page_total_ref[t], span_lane_ref, span_first_ref,
        span_count_ref, block_tables_ref,
        ((k_hbm, k_buf), (v_hbm, v_buf)), sems,
        tb_tokens=tb_tokens, page_body=page_body,
    )
    softmax_finish(out_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit, static_argnames=("tb_tokens", "interpret", "sliding_window"),
)
def ragged_paged_attention(
    q: jnp.ndarray,             # [T, H, D] flat ragged token batch
    k_cache: jnp.ndarray,       # [N, bs, KVH, D]
    v_cache: jnp.ndarray,
    token_lane: jnp.ndarray,    # [T] int32 owning lane (OOB = pad)
    token_pos: jnp.ndarray,     # [T] int32 absolute position (-1 = pad)
    block_tables: jnp.ndarray,  # [lanes, max_blocks] int32
    span_lane: jnp.ndarray,     # [T] int32 (pack_spans)
    span_first: jnp.ndarray,    # [T] int32
    span_count: jnp.ndarray,    # [T] int32
    page_total: jnp.ndarray,    # [T // tb_tokens] int32
    *,
    tb_tokens: int = 8,
    interpret: bool = False,
    sliding_window: int | None = None,
) -> jnp.ndarray:
    """Pallas ragged paged attention with PACKED decode lanes: causally
    masked paged attention over one mixed prefill+decode token batch in a
    single launch, multiple lanes per token block, page iterations = live
    pages (pure-JAX twin: ops/attention.py ragged_paged_attention; host
    metadata builder: pack_spans)."""
    t_pad, h, d = q.shape
    n, bs, kvh, _ = k_cache.shape
    groups = h // kvh
    rows = bs * kvh
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    num_tb = t_pad // tb_tokens
    tbh = tb_tokens * h
    kernel = functools.partial(
        _ragged_kernel,
        block_size=bs,
        num_kv_heads=kvh,
        groups=groups,
        head_dim=d,
        tb_tokens=tb_tokens,
        sliding_window=sliding_window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(num_tb,),
        in_specs=[
            pl.BlockSpec((1, tbh, d), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tbh, d), lambda t, *_: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, d), k_cache.dtype),
            pltpu.VMEM((2, rows, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((tbh, 128), jnp.float32),
            pltpu.VMEM((tbh, 128), jnp.float32),
            pltpu.VMEM((tbh, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tb, tbh, d), q.dtype),
        interpret=interpret,
    )(
        token_lane, token_pos, block_tables, span_lane, span_first,
        span_count, page_total,
        q.reshape(num_tb, tbh, d),
        k_cache.reshape(n, rows, d), v_cache.reshape(n, rows, d),
    )
    return out.reshape(t_pad, h, d)
