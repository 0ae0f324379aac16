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
  consumes, replacing the old one-lane-per-block ``tb_lane`` routing;
- the KV side is a host-flattened page worklist per token block:
  ``page_phys[t, j]`` is the PHYSICAL cache page the grid step (t, j)
  DMAs (the BlockSpec index map reads it directly — no block-table
  indirection in the kernel), ``page_lane[t, j]`` the lane that owns it,
  ``page_ord[t, j]`` its ordinal in that lane's sequence (kv positions
  start at ``ord * block_size``), and ``page_count[t]`` the number of
  live entries.  Pad entries REPEAT the last live physical page so the
  unchanged index map skips their DMA; their compute is gated off by
  ``j < page_count[t]`` (repeating without the gate would double-count
  that page in the softmax accumulator);
- grid = (token blocks × page slots / pages_per_step): page slots is the
  static width of the worklist — a compile-bucket choice of the caller
  (the engine uses one fixed width so there is exactly one unified
  program per token bucket); ``pages_per_step`` folds that many
  consecutive worklist slots into one grid step (each slot gets its own
  input stream + index map, so the DMAs still address single pages);
- heads fold into the row axis like the window kernel (row = token*H + h)
  and GQA matching uses iota masks on the [TB*H, bs*KVH] score matrix;
- softmax accumulates online flash-style in VMEM scratch across a token
  block's page slots; masking is per-row: a row participates in a page
  step iff its token's lane owns the page and the page position is
  causally visible (pos <= token_pos), which also confines every lane to
  its own pages.

Padding rows (position -1 / out-of-range lane) match no page and no
position — their l stays 0, the clamped denominator makes their output
rows zero, and the caller never reads them.

``pack_page_meta`` (plain numpy, host side) builds the page worklist from
the per-token metadata + block tables; the engine and the tests share it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Scalar memory of one TensorCore (v5e: 1 MiB).  Everything the kernel
# scalar-prefetches lives there for the whole call, and Mosaic pads a 2-D
# int32 operand to (8, 128) tiles — so the three [token blocks, page_slots]
# worklists are the footprint that matters.  The wrapper never hands one
# pallas_call more token blocks than fit (worklist_rows_per_call); the
# reserve is what the compiler keeps for its own scalars.
SMEM_BYTES = 1 << 20
SMEM_RESERVE_BYTES = 128 << 10


def worklist_rows_per_call(page_slots: int, tb_tokens: int) -> int:
    """Token blocks one kernel launch may carry so that its scalar-prefetched
    metadata (three [rows, page_slots] worklists + per-token lane/pos +
    per-row count) fits scalar memory.  A multiple of 8 (the SMEM row
    tile).  Raises ValueError when even one row tile does not fit: the
    engine calls this at init so such a config is a construction error,
    not a compiler refusal at first dispatch."""
    cols = -(-page_slots // 128) * 128
    per_row = 4 * (3 * cols + 2 * tb_tokens + 1)
    rows = (SMEM_BYTES - SMEM_RESERVE_BYTES) // per_row // 8 * 8
    if rows < 8:
        raise ValueError(
            f"ragged attention worklist width page_slots={page_slots} "
            f"(tb_tokens={tb_tokens}) needs {8 * per_row} bytes of scalar "
            f"memory per launch; the chip has {SMEM_BYTES - SMEM_RESERVE_BYTES}"
            " usable — lower the context length or the token-block size"
        )
    return rows


def pack_page_meta(
    token_lane,     # [T] int — owning lane per token (OOB / pos<0 = pad)
    token_pos,      # [T] int — absolute position per token (-1 = pad)
    block_tables,   # [lanes, max_blocks] int — logical->physical pages
    *,
    tb_tokens: int,
    block_size: int,
    page_slots: int | None = None,
    sliding_window: int | None = None,
):
    """Host-side (numpy) page worklist for the packed ragged kernel.

    For every token block: the lanes present in it (first-appearance
    order), then for each lane every page holding kv positions its tokens
    can see — causally up to ``max(token_pos) // block_size`` and, under a
    sliding window, down from ``(min(token_pos) - W + 1) // block_size``.
    Returns ``(page_phys, page_lane, page_ord, page_count)`` int32 arrays
    of width ``page_slots`` (default: the tightest width that fits; the
    engine passes its fixed compile-bucket width).  Pad entries repeat the
    last live physical page so their DMA is skipped by the unchanged
    BlockSpec index; blocks with no live tokens point at page 0 with
    count 0."""
    token_lane = np.asarray(token_lane)
    token_pos = np.asarray(token_pos)
    bt = np.asarray(block_tables)
    lanes = bt.shape[0]
    t_pad = token_lane.shape[0]
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    num_tb = t_pad // tb_tokens
    per_block: list[list[tuple[int, int, int]]] = []
    for t in range(num_tb):
        span: dict[int, tuple[int, int]] = {}
        for i in range(t * tb_tokens, (t + 1) * tb_tokens):
            lane, pos = int(token_lane[i]), int(token_pos[i])
            if pos < 0 or not 0 <= lane < lanes:
                continue
            lo, hi = span.get(lane, (pos, pos))
            span[lane] = (min(lo, pos), max(hi, pos))
        entries: list[tuple[int, int, int]] = []
        for lane, (lo, hi) in span.items():
            first = 0
            if sliding_window is not None:
                first = max(0, lo - (sliding_window - 1)) // block_size
            for ord_ in range(first, hi // block_size + 1):
                entries.append((int(bt[lane, ord_]), lane, ord_))
        per_block.append(entries)
    need = max((len(e) for e in per_block), default=0)
    ps = page_slots if page_slots is not None else max(1, need)
    if need > ps:
        raise ValueError(
            f"page worklist needs {need} slots but page_slots={ps}"
        )
    page_phys = np.zeros((num_tb, ps), np.int32)
    page_lane = np.full((num_tb, ps), -1, np.int32)
    page_ord = np.zeros((num_tb, ps), np.int32)
    page_count = np.zeros((num_tb,), np.int32)
    for t, entries in enumerate(per_block):
        page_count[t] = len(entries)
        for j, (phys, lane, ord_) in enumerate(entries):
            page_phys[t, j] = phys
            page_lane[t, j] = lane
            page_ord[t, j] = ord_
        if entries:
            page_phys[t, len(entries):] = entries[-1][0]
    return page_phys, page_lane, page_ord, page_count


def _ragged_kernel(
    token_lane_ref,     # [T] int32 — owning lane per token (OOB = pad)
    token_pos_ref,      # [T] int32 — absolute position per token (-1 = pad)
    page_phys_ref,      # [num_tb, PS] int32 — physical page per grid step
    page_lane_ref,      # [num_tb, PS] int32 — lane owning that page
    page_ord_ref,       # [num_tb, PS] int32 — page ordinal in its lane
    page_count_ref,     # [num_tb] int32 — live worklist entries
    q_ref,              # [1, TB*H, D]   (token-major fold: row = tok*H + h)
    *refs,              # pps × (k_page [1, bs*KVH, D], v_page), out, scratch
    block_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    page_slots: int,
    tb_tokens: int,
    pages_per_step: int,
    sliding_window: int | None,
):
    """Online-softmax page-worklist loop for one packed token block.

    Each grid step owns ``pages_per_step`` consecutive worklist slots: the
    same cache array is passed once per slot with its own BlockSpec index
    map (index maps address exactly one block, so batching arbitrary
    physical pages into one DMA is impossible — multiple inputs is the
    Pallas way to widen a step), and the kernel folds the slots into the
    running softmax sequentially."""
    pps = pages_per_step
    kv_refs = refs[: 2 * pps]
    out_ref = refs[2 * pps]
    m_ref, l_ref, acc_ref = refs[2 * pps + 1:]
    t = pl.program_id(0)
    j = pl.program_id(1)
    rows = block_size * num_kv_heads
    h_all = num_kv_heads * groups
    tbh = tb_tokens * h_all

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i in range(pps):
        slot = j * pps + i
        page_lane = page_lane_ref[t, slot]
        page_start = page_ord_ref[t, slot] * block_size
        k_page_ref = kv_refs[2 * i]
        v_page_ref = kv_refs[2 * i + 1]

        @pl.when(slot < page_count_ref[t])
        def _compute(
            k_page_ref=k_page_ref, v_page_ref=v_page_ref,
            page_lane=page_lane, page_start=page_start,
        ):
            q = q_ref[0].astype(jnp.float32)        # [TB*H, D]
            k = k_page_ref[0].astype(jnp.float32)   # [bs*KVH, D]
            v = v_page_ref[0].astype(jnp.float32)
            scale = 1.0 / (head_dim ** 0.5)
            s = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                    # [TB*H, bs*KVH]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            pos = page_start + col // num_kv_heads
            kv_of_col = col % num_kv_heads
            row = jax.lax.broadcasted_iota(jnp.int32, (tbh, 1), 0)
            kv_of_row = (row % h_all) // groups
            # per-row routing: row r serves flat token t*TB + r//H — its
            # lane and absolute position come from the scalar-prefetched
            # per-token metadata, folded in as a select chain over the
            # block's tokens (scalar reads broadcast against the row iota;
            # no vector gather)
            tok_of_row = row // h_all
            base = t * tb_tokens
            q_pos = jnp.full((tbh, 1), -1, jnp.int32)
            row_lane = jnp.full((tbh, 1), -1, jnp.int32)
            for rr in range(tb_tokens):
                q_pos = jnp.where(
                    tok_of_row == rr, token_pos_ref[base + rr], q_pos
                )
                row_lane = jnp.where(
                    tok_of_row == rr, token_lane_ref[base + rr], row_lane
                )
            # a row participates iff its token's lane owns this page and
            # the page position is causally visible (pads sit at
            # q_pos = -1 and match nothing; stale slots past a lane's
            # context exceed every q_pos of that lane, so causality masks
            # them too)
            mask = (
                (kv_of_col == kv_of_row)
                & (row_lane == page_lane)
                & (pos <= q_pos)
            )
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

    @pl.when(j == page_slots // pps - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "tb_tokens", "pages_per_step", "interpret", "sliding_window"
    ),
)
def ragged_paged_attention(
    q: jnp.ndarray,             # [T, H, D] flat ragged token batch
    k_cache: jnp.ndarray,       # [N, bs, KVH, D]
    v_cache: jnp.ndarray,
    token_lane: jnp.ndarray,    # [T] int32 owning lane (OOB = pad)
    token_pos: jnp.ndarray,     # [T] int32 absolute position (-1 = pad)
    page_phys: jnp.ndarray,     # [T // tb_tokens, PS] int32 (pack_page_meta)
    page_lane: jnp.ndarray,     # [T // tb_tokens, PS] int32
    page_ord: jnp.ndarray,      # [T // tb_tokens, PS] int32
    page_count: jnp.ndarray,    # [T // tb_tokens] int32
    *,
    tb_tokens: int = 8,
    pages_per_step: int = 1,
    interpret: bool = False,
    sliding_window: int | None = None,
) -> jnp.ndarray:
    """Pallas ragged paged attention with PACKED decode lanes: causally
    masked paged attention over one mixed prefill+decode token batch in a
    single launch, multiple lanes per token block (pure-JAX twin:
    ops/attention.py ragged_paged_attention; host metadata builder:
    pack_page_meta).  ``pages_per_step`` widens each grid step to DMA that
    many worklist pages (autotuned; ``page_slots`` must divide evenly)."""
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
    page_slots = page_phys.shape[1]
    pps = pages_per_step
    if pps < 1 or page_slots % pps:
        raise ValueError(
            f"page_slots ({page_slots}) must be a positive multiple of "
            f"pages_per_step ({pps})"
        )
    tbh = tb_tokens * h

    def kv_map_at(i):
        def kv_map(t, j, tl, tp, pp, pln, po, pc):
            return (pp[t, j * pps + i], 0, 0)
        return kv_map

    kv_specs = []
    for i in range(pps):
        m = kv_map_at(i)
        kv_specs += [
            pl.BlockSpec((1, rows, d), m),
            pl.BlockSpec((1, rows, d), m),
        ]
    kernel = functools.partial(
        _ragged_kernel,
        block_size=bs,
        num_kv_heads=kvh,
        groups=groups,
        head_dim=d,
        page_slots=page_slots,
        tb_tokens=tb_tokens,
        pages_per_step=pps,
        sliding_window=sliding_window,
    )
    k_flat = k_cache.reshape(n, rows, d)
    v_flat = v_cache.reshape(n, rows, d)
    kv_args = []
    for _ in range(pps):
        kv_args += [k_flat, v_flat]

    def launch(blocks, tl, tp, pp, pln, po, pc, q_rows):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(blocks, page_slots // pps),
            in_specs=[
                pl.BlockSpec((1, tbh, d), lambda t, j, *_: (t, 0, 0)),
                *kv_specs,
            ],
            out_specs=pl.BlockSpec((1, tbh, d), lambda t, j, *_: (t, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((tbh, 128), jnp.float32),
                pltpu.VMEM((tbh, 128), jnp.float32),
                pltpu.VMEM((tbh, d), jnp.float32),
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((blocks, tbh, d), q.dtype),
            interpret=interpret,
        )(tl, tp, pp, pln, po, pc, q_rows, *kv_args)

    # token blocks are independent (each owns its softmax state), so the
    # token axis splits into launches whose worklists fit scalar memory;
    # one launch when everything fits
    calls = -(-num_tb // worklist_rows_per_call(page_slots, tb_tokens))
    blocks = -(-num_tb // calls)
    q_rows = q.reshape(num_tb, tbh, d)
    if calls == 1:
        out = launch(
            num_tb, token_lane, token_pos, page_phys, page_lane, page_ord,
            page_count, q_rows,
        )
        return out.reshape(t_pad, h, d)
    pad = calls * blocks - num_tb  # < calls dead blocks: count 0, pos -1

    def split(x, per_row, fill=0):
        if pad:
            x = jnp.pad(
                x, ((0, pad * per_row),) + ((0, 0),) * (x.ndim - 1),
                constant_values=fill,
            )
        return x.reshape(calls, blocks * per_row, *x.shape[1:])

    out = jax.lax.map(
        lambda xs: launch(blocks, *xs),
        (
            split(token_lane, tb_tokens), split(token_pos, tb_tokens, -1),
            split(page_phys, 1), split(page_lane, 1, -1), split(page_ord, 1),
            split(page_count, 1), split(q_rows, 1),
        ),
    )
    return out.reshape(calls * blocks * tb_tokens, h, d)[:t_pad]
