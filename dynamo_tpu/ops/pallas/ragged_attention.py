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
  block can carry up to ``tb_tokens`` different lanes.  The engine packs a
  bucket to blocks of ``gcd(default_tb_tokens(...), bucket)`` tokens: 64
  where four query heads share a KV head, so that one head's ``tokens x
  G`` score rows (256) keep their softmax state in registers and a prompt
  span's pages are copied once per 64 tokens;
- per-token routing rides in scalar prefetch: ``token_lane[i]`` names
  token i's sequence lane and ``token_pos[i]`` its absolute position
  (-1 = padding row, fully masked) — the same metadata the XLA twin
  consumes;
- the KV side is a list of SPANS per token block, at most ``tb_tokens`` of
  them (one per lane present in the block, first-appearance order):
  ``(span_lane, span_first, span_count)`` = the lane, the ordinal of the
  first page its tokens can see and how many consecutive pages follow.
  Block t's span s sits at flat index ``t * tb_tokens + s``.  The block
  tables ride in scalar prefetch too, so the kernel resolves ``(lane,
  ordinal)`` to a physical page itself;
- grid = (token blocks,).  The body walks the block's spans in KV STEPS of
  ``kv_step_pages`` (16) consecutive pages of one span — ``kv_steps[t]`` of
  them, a trip count read from scalar memory, each span's last step partly
  filled — and copies exactly the LIVE pages from the HBM-resident cache by
  double-buffered async copies (the next step's copies are in flight while
  this one is computed): no static worklist width, no dead steps, no page
  copied twice for one block;
- a KV step multiplies, for EACH KV HEAD, that head's own rows: the
  ``[G*TB, D]`` queries of the head's group (the wrapper lays the queries
  out by KV head, row = g * TB + token) against the head's ``[P*bs, D]``
  keys, and the probabilities against its values — dense, no product the
  GQA match would throw away.  A head's rows are read out of the
  ``[P*bs*KVH, D]`` step buffer (row = position * KVH + head: the cache's
  layout, untouched) by a sublane-strided load (``_head_rows``; priced on a
  v5e inside the 15.9 us a step of 8 heads and 256 x 256 scores each, the
  heads a loop; 10.7 us unrolled: PERF.md sections 5-6, PR 37);
- the MXU is fed in the queries' dtype (bf16 from every step program;
  float32 callers keep float32), both products accumulate in float32 and
  the running max, sum and accumulator are float32 in VMEM scratch, one
  set a KV head; the probabilities are rounded to the values' dtype for
  the second product.  Masking is per row: a row participates in a step
  iff its token's lane owns the step's span and the position is causally
  visible (pos <= token_pos; under a sliding window also pos > token_pos -
  W), which also confines every lane to its own pages.

Padding rows (position -1 / out-of-range lane) match no span and no
position — their l stays 0, the clamped denominator makes their output
rows zero, and the caller never reads them.

``pack_spans`` (plain numpy, host side) builds the span lists from the
per-token metadata; ``walk_live_pages`` is the loop skeleton, shared (with
the row routing and the online-softmax update) with the MLA ragged kernel
(ops/pallas/mla_attention.py), which has one latent head and brings its
own scores over the same KV steps.  The step (per-KV-head products over a
block of queries and several pages) follows the kernel that ships with JAX
as ``jax/experimental/pallas/ops/tpu/ragged_paged_attention``, whose
interleaved K/V cache layout is not this repo's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30      # where a row's running max starts
MASKED = -2e30       # a masked score: under every running max, so its exp is 0


def default_tb_tokens(rows_per_token: int, block_size: int) -> int:
    """Largest token block the ragged kernels are packed to, from the head
    geometry: the power of two that keeps one product's score rows —
    ``tokens x rows_per_token``, the query heads that share a KV head (all
    heads over a latent cache) — at 256, where its softmax state still fits
    the registers; at most 64 tokens, at least ``gcd(block_size, 8)``."""
    tokens = max(1, 256 // rows_per_token)
    tokens = 1 << (tokens.bit_length() - 1)
    return max(min(tokens, 64), math.gcd(block_size, 8))


def kv_step_pages(block_size: int) -> int:
    """Pages one KV step of the ragged kernels holds: 256 positions (two
    128-lane score tiles a row), at most 16 pages."""
    return min(16, max(1, 256 // block_size))


def pack_spans(
    token_lane,     # [T] int — owning lane per token (OOB / pos<0 = pad)
    token_pos,      # [T] int — absolute position per token (-1 = pad)
    *,
    lanes: int,
    tb_tokens: int,
    block_size: int,
    sliding_window: int | None = None,
    pages_per_step: int | None = None,
):
    """Host-side (numpy) span lists for the packed ragged kernels.

    For every token block: the lanes present in it (first-appearance
    order), and for each lane the run of pages holding kv positions its
    tokens can see — causally up to ``max(token_pos) // block_size`` and,
    under a sliding window, down from ``(min(token_pos) - W + 1) //
    block_size``.  Returns ``(span_lane, span_first, span_count,
    kv_steps)``: three int32 arrays of the flat token axis' length (block
    t's span s at ``t * tb_tokens + s``; unused entries lane -1, count 0)
    and per block the KV steps the kernel executes for it: a span of
    ``count`` pages takes ``ceil(count / pages_per_step)`` steps (the last
    partly filled), ``pages_per_step`` defaulting to what the kernels
    derive from the page size (``kv_step_pages``)."""
    if pages_per_step is None:
        pages_per_step = kv_step_pages(block_size)
    token_lane = np.asarray(token_lane)
    token_pos = np.asarray(token_pos)
    t_pad = token_lane.shape[0]
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    num_tb = t_pad // tb_tokens
    lane = token_lane.astype(np.int64)
    pos = token_pos.astype(np.int64)
    row = np.flatnonzero((pos >= 0) & (lane >= 0) & (lane < lanes))
    # one group per (token block, lane): its rows made contiguous by a
    # stable sort, so a group's first row is the lane's first appearance
    key = (row // tb_tokens) * lanes + lane[row]
    by_key = np.argsort(key, kind="stable")
    key, row = key[by_key], row[by_key]
    span_lane = np.full(t_pad, -1, np.int32)
    span_first = np.zeros(t_pad, np.int32)
    span_count = np.zeros(t_pad, np.int32)
    steps_of_block = np.zeros(num_tb, np.int64)
    if row.size:
        start = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        lo = np.minimum.reduceat(pos[row], start)
        hi = np.maximum.reduceat(pos[row], start)
        block = key[start] // lanes
        first = np.zeros_like(lo)
        if sliding_window is not None:
            first = np.maximum(0, lo - (sliding_window - 1)) // block_size
        count = hi // block_size + 1 - first
        # a block's spans in first-appearance order, from its first entry on
        order = np.lexsort((row[start], block))
        block = block[order]
        rank = np.arange(order.size) - np.searchsorted(block, block)
        at = block * tb_tokens + rank
        span_lane[at] = key[start][order] % lanes
        span_first[at] = first[order]
        span_count[at] = count[order]
        steps_of_block = np.bincount(
            block, -(-count[order] // pages_per_step), minlength=num_tb
        )
    return span_lane, span_first, span_count, steps_of_block.astype(np.int32)


def walk_live_pages(
    base,               # first flat span index of this token block
    total,              # KV steps of this token block (pack_spans' kv_steps[t])
    span_lane_ref,      # [T] int32 SMEM (pack_spans)
    span_first_ref,     # [T] int32 SMEM
    span_count_ref,     # [T] int32 SMEM
    block_tables_ref,   # [lanes, max_blocks] int32 SMEM
    streams,            # ((cache ref [N, rows, W] in HBM, buffer ref [2, P * rows, W]), ...)
    sems,               # DMA semaphores [2, len(streams)]
    *,
    tb_tokens: int,
    pages_per_step: int,
    step_body,          # (slot, lane, first ordinal of the step) -> None
    first_block=None,   # whether this is the launch's first token block
):
    """Loop skeleton of the ragged kernels: visit every live page of one
    token block — spans in order, ordinals ascending, ``pages_per_step``
    consecutive pages of one span a KV step — in exactly ``total`` steps.
    Each live page of every stream is copied HBM -> its place in VMEM buffer
    ``slot`` (double-buffered: step i computes slot i % 2 while the copies
    of step i + 1 fly into the other; a deeper ring bought nothing on a
    v5e), then ``step_body`` runs on the buffer.  A span's last step copies
    only the pages the span still has: the places behind them keep what an
    earlier step left there (zeros before the launch's first), and the
    body's causal mask hides them, since their positions lie past every
    query of the span's lane.  ``first_block``: from a caller that walks
    under a condition, where the grid position cannot be asked for."""
    pps = pages_per_step

    def copies(slot, s, o, go):
        lane = span_lane_ref[base + s]
        end = span_first_ref[base + s] + span_count_ref[base + s]

        def one(p, _):
            phys = block_tables_ref[lane, o + p]
            for i, (cache, buf) in enumerate(streams):
                rows = cache.shape[1]
                go(pltpu.make_async_copy(
                    cache.at[phys],
                    buf.at[slot, pl.ds(pl.multiple_of(p * rows, rows), rows)],
                    sems.at[slot, i],
                ))

        # the step's live pages: all of them but in a span's last step
        jax.lax.fori_loop(0, jnp.minimum(pps, end - o), one, None)

    if pps > 1:
        @pl.when(pl.program_id(0) == 0 if first_block is None else first_block)
        def _no_stale_bits():
            for _, buf in streams:
                buf[...] = jnp.zeros_like(buf)

    @pl.when(total > 0)
    def _prologue():
        copies(0, 0, span_first_ref[base], lambda c: c.start())

    def step(i, carry):
        s, o = carry
        slot = i % 2
        # the step after this one: the span's next, else the next span's
        # first (past the block's last step: anything, nobody copies it)
        last = o + pps >= span_first_ref[base + s] + span_count_ref[base + s]
        s_next = jnp.where(last, s + 1, s)
        o_next = jnp.where(
            last,
            span_first_ref[base + jnp.minimum(s + 1, tb_tokens - 1)],
            o + pps,
        )

        @pl.when(i + 1 < total)
        def _prefetch():
            copies(1 - slot, s_next, o_next, lambda c: c.start())

        copies(slot, s, o, lambda c: c.wait())
        step_body(slot, span_lane_ref[base + s], o)
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

    def select(rr, carry):
        row_lane, q_pos = carry
        here = tok_of_row == rr
        return (
            jnp.where(here, token_lane_ref[base + rr], row_lane),
            jnp.where(here, token_pos_ref[base + rr], q_pos),
        )

    never = jnp.full((tbh, 1), -1, jnp.int32)
    return jax.lax.fori_loop(0, tb_tokens, select, (never, never))


def softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def softmax_update(s, mask, v, m_ref, l_ref, acc_ref):
    """One KV step of the online softmax: masked scores ``s`` [rows, kv]
    (float32) and values ``v`` [kv, D] folded into the running max / sum /
    accumulator (float32).  The probabilities are rounded to ``v``'s dtype
    for the second product, which accumulates in float32."""
    s = jnp.where(mask, s, MASKED)
    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)      # >= NEG_INF > MASKED
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                  # exactly 0 where masked
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def softmax_finish(out_ref, l_ref, acc_ref):
    denom = jnp.maximum(l_ref[:, :1], 1e-20)
    out_ref[...] = (acc_ref[...] / denom).astype(out_ref.dtype)


def _strides(dtype, num_kv_heads: int) -> bool:
    """Whether ``_head_rows`` can read one head's rows out of pages of this
    dtype; otherwise a step's pages go through a float32 copy first."""
    dtype = jnp.dtype(dtype)
    return (
        num_kv_heads == 1
        or dtype.itemsize == 4
        or (dtype == jnp.bfloat16 and num_kv_heads % 2 == 0)
    )


def _head_rows(pages, head, num_kv_heads, dtype):
    """One KV head's rows of a step's pages ``pages`` ([positions x KVH, D],
    row = position * KVH + head) as [positions, D] in ``dtype``: a
    sublane-strided read.  Mosaic strides 32-bit rows only, so bfloat16
    pages are read as the uint32 words that pair heads 2j and 2j + 1 of a
    position, and the wanted half is shifted into a float32's high bits
    (exact: a bfloat16 is those bits)."""
    n = pages.shape[0] // num_kv_heads
    if num_kv_heads == 1:
        return pages[...].astype(dtype)
    if pages.dtype.itemsize == 4:
        return pages[pl.ds(head, n, stride=num_kv_heads), :].astype(dtype)
    words = pages.bitcast(jnp.uint32)[
        pl.ds(head // 2, n, stride=num_kv_heads // 2), :
    ]
    # an even head is the word's low half, an odd one its high half
    bits = (words >> (16 * (head % 2)).astype(jnp.uint32)) << 16
    return pltpu.bitcast(bits, jnp.float32).astype(dtype)


def _ragged_kernel(
    token_lane_ref,     # [T] int32 — owning lane per token (OOB = pad)
    token_pos_ref,      # [T] int32 — absolute position per token (-1 = pad)
    block_tables_ref,   # [lanes, max_blocks] int32
    span_lane_ref,      # [T] int32 — pack_spans
    span_first_ref,     # [T] int32
    span_count_ref,     # [T] int32
    kv_steps_ref,       # [num_tb] int32 — KV steps per token block
    q_ref,              # [1, KVH, G*TB, D]  (row = g * TB + token)
    k_hbm,              # [N, bs*KVH, D] whole cache, HBM
    v_hbm,
    out_ref,            # [1, KVH, G*TB, D]
    k_buf,              # [2, P*bs*KVH, D] VMEM double buffer
    v_buf,
    sems,               # DMA semaphores [2, 2]
    m_ref, l_ref,       # [KVH, G*TB, 128] float32
    acc_ref,            # [KVH, G*TB, D] float32
    *wide,              # K and V [P*bs*KVH, D] float32, where pages do not stride
    block_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    tb_tokens: int,
    pages_per_step: int,
    sliding_window: int | None,
):
    """Online-softmax loop over one packed token block's KV steps, one
    dense product pair per KV head and step."""
    t = pl.program_id(0)
    base = t * tb_tokens
    kv_len = pages_per_step * block_size

    softmax_init(m_ref, l_ref, acc_ref)
    scale = 1.0 / (head_dim ** 0.5)
    # what every step and head of this block share: the rows' routing (a
    # head's rows are its group's G query heads, each over the TB tokens)
    tok_lane, tok_pos = row_routing(
        token_lane_ref, token_pos_ref, base, tb_tokens=tb_tokens, heads=1
    )
    row_lane = jnp.concatenate([tok_lane] * groups, axis=0)    # [G*TB, 1]
    q_pos = jnp.concatenate([tok_pos] * groups, axis=0)
    pos_in_step = jax.lax.broadcasted_iota(jnp.int32, (1, kv_len), 1)

    def step_body(slot, step_lane, step_ord):
        pos = step_ord * block_size + pos_in_step
        # a row participates iff its token's lane owns this step's span and
        # the position is causally visible (pads sit at q_pos = -1 and
        # match nothing; stale slots past a lane's context, and the places
        # a span's last step did not fill, exceed every q_pos of that lane,
        # so causality masks them too)
        mask = (row_lane == step_lane) & (pos <= q_pos)
        if sliding_window is not None:
            mask = mask & (pos > q_pos - sliding_window)
        pages = (k_buf.at[slot], v_buf.at[slot])
        if wide:    # fp8 pages: widened once a step, then read as float32
            for narrow, w in zip(pages, wide):
                w[...] = narrow[...].astype(jnp.float32)
            pages = wide
        def head(h, _):
            q = q_ref[0, h]                                 # [G*TB, D]
            k, v = (_head_rows(p, h, num_kv_heads, q.dtype) for p in pages)
            s = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                       # [G*TB, P*bs]
            softmax_update(
                s, mask, v, m_ref.at[h], l_ref.at[h], acc_ref.at[h]
            )

        # a loop, not eight copies of the body: a step program is lowered
        # twice a start, and the copies cost its set-up 0.4 s a program
        jax.lax.fori_loop(0, num_kv_heads, head, None)

    walk_live_pages(
        base, kv_steps_ref[t], span_lane_ref, span_first_ref,
        span_count_ref, block_tables_ref,
        ((k_hbm, k_buf), (v_hbm, v_buf)), sems,
        tb_tokens=tb_tokens, pages_per_step=pages_per_step,
        step_body=step_body,
    )
    jax.lax.fori_loop(
        0, num_kv_heads,
        lambda h, _: softmax_finish(
            out_ref.at[0, h], l_ref.at[h], acc_ref.at[h]
        ),
        None,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tb_tokens", "interpret", "sliding_window", "pages_per_step",
    ),
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
    kv_steps: jnp.ndarray,      # [T // tb_tokens] int32
    *,
    tb_tokens: int = 8,
    interpret: bool = False,
    sliding_window: int | None = None,
    pages_per_step: int | None = None,
) -> jnp.ndarray:
    """Pallas ragged paged attention with PACKED decode lanes: causally
    masked paged attention over one mixed prefill+decode token batch in a
    single launch, multiple lanes per token block, only live pages copied
    (pure-JAX twin: ops/attention.py ragged_paged_attention; host metadata
    builder: pack_spans, at the same ``tb_tokens`` and ``pages_per_step``).
    The products run in ``q``'s dtype (pages are widened or narrowed to it)
    and accumulate in float32."""
    t_pad, h, d = q.shape
    n, bs, kvh, _ = k_cache.shape
    groups = h // kvh
    rows = bs * kvh
    pps = pages_per_step or kv_step_pages(bs)
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    num_tb = t_pad // tb_tokens
    gtb = groups * tb_tokens
    wide = () if _strides(k_cache.dtype, kvh) else (
        pltpu.VMEM((pps * rows, d), jnp.float32),
    ) * 2
    kernel = functools.partial(
        _ragged_kernel,
        block_size=bs,
        num_kv_heads=kvh,
        groups=groups,
        head_dim=d,
        tb_tokens=tb_tokens,
        pages_per_step=pps,
        sliding_window=sliding_window,
    )
    block = pl.BlockSpec((1, kvh, gtb, d), lambda t, *_: (t, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(num_tb,),
        in_specs=[
            block,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=block,
        scratch_shapes=[
            pltpu.VMEM((2, pps * rows, d), k_cache.dtype),
            pltpu.VMEM((2, pps * rows, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kvh, gtb, 128), jnp.float32),
            pltpu.VMEM((kvh, gtb, 128), jnp.float32),
            pltpu.VMEM((kvh, gtb, d), jnp.float32),
            *wide,
        ],
    )

    def by_kv_head(x):      # [T, H, D] -> [num_tb, KVH, G*TB, D]
        x = x.reshape(num_tb, tb_tokens, kvh, groups, d)
        return x.transpose(0, 2, 3, 1, 4).reshape(num_tb, kvh, gtb, d)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tb, kvh, gtb, d), q.dtype),
        interpret=interpret,
    )(
        token_lane, token_pos, block_tables, span_lane, span_first,
        span_count, kv_steps,
        by_kv_head(q),
        k_cache.reshape(n, rows, d), v_cache.reshape(n, rows, d),
    )
    out = out.reshape(num_tb, kvh, groups, tb_tokens, d)
    return out.transpose(0, 3, 1, 2, 4).reshape(t_pad, h, d)


def bucket_tb_tokens(rows_per_token: int, block_size: int, bucket: int) -> int:
    """Token block one token bucket's program is packed and launched with:
    the largest divisor of the bucket in ``default_tb_tokens`` (a 64-token
    block serves buckets 64 ... 4,096 whole, a 32-token bucket as one block,
    a 528-token chunk + lanes bucket as blocks of 16).  Kept below the
    kernel so that no line of its body moves."""
    return math.gcd(default_tb_tokens(rows_per_token, block_size), bucket)
