"""MLA (multi-head latent attention) over latent pages — Pallas TPU kernels.

DeepSeek's absorbed form attends in latent space: a token's queries are
``q_lat [H, R]`` (the nope part folded through the K up-projection) and
``q_rope [H, P]``; a page of the cache holds, for each of its positions, the
compressed latent ``ck [R]`` (key AND value) and the rotated key ``kr [P]``
that all heads share.  Scores are ``q_lat . ck + q_rope . kr``; the context
is summed in latent space (the V up-projection is the caller's).

**The page layout** is the kernels': two leaves of flat pages ``[pages,
block_size, R]`` and ``[pages, block_size, P]`` whose last axis is a whole
number of 128-lane tiles (models/deepseek.py stores the 64-wide rope part
128 wide, zeros behind it), so that a page is copied whole, HBM to VMEM, by
one DMA a leaf, and nothing here or in the caller pads, slices or reshapes
the cache.  ``q_rope`` comes as wide as the rope page.

**One kernel body, three launches.**  The body is the ragged kernel of
ops/pallas/ragged_attention.py applied to one latent head: a token block's
live pages are walked in KV steps of ``kv_step_pages`` pages
(``walk_live_pages``), each step one ``[TB*H, R+P] x [P*bs, R+P]`` score
product and one ``[TB*H, P*bs] x [P*bs, R]`` context product.

- ``ragged_mla_attention``: the unified step's mixed spans and decode
  tokens, metadata from ``pack_spans`` on the host;
- ``mla_paged_attention_decode``: one query a lane, a token block a lane,
  its metadata (one span: the lane's pages) derived here from the context
  lengths.  (As a grid of (lane, page) steps with a page's 16 positions a
  product it was 12,288 grid steps a launch at 24 lanes of 8k context.)
- ``mla_paged_window_attention_decode``: a lane's ``W`` verify queries are
  its token block.

**And the window launch.**  A unified step's rows attend the keys that are
rows of the SAME window decompressed, from their own latents, every head
its own 192-wide key and 128-wide value (640 products a (query, key, head)
where the absorbed form takes 2,304): ``ragged_mla_attention_window``, a
flash kernel a head over the flat batch, ``same lane AND flat-causal``.  The
ragged launch is then told each lane's last RESIDENT position
(``last_resident_pos``) in place of the rows' own, walks only the pages from
before the window, and returns the rows' log-sum-exp beside the context
(``with_lse``), by which the caller merges the two parts under one softmax.

Each is a jitted function of its own so that the kernel keeps the name the
device trace shows (``benchmark/metrics/mla_*``: ``^%?ragged_mla_attention``
reads both launches of a unified step).

**Precision.**  The MXU is fed in the queries' dtype (bf16 from the step
programs; float32 callers keep float32), pages are cast to it, both products
accumulate in float32, the running max / sum / accumulator are float32 and
the probabilities are rounded to the queries' dtype for the second product:
what "bf16" means for the GQA kernel (PR 37).  The context comes back in the
queries' dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.ragged_attention import (
    NEG_INF,
    kv_step_pages,
    row_routing,
    softmax_finish,
    softmax_init,
    softmax_update,
    walk_live_pages,
)


def _kernel(
    token_lane_ref,     # [T] int32 — owning lane per token (OOB = pad)
    token_pos_ref,      # [T] int32 — absolute position per token (< 0 = pad)
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
    *rest,              # with_lse: lse_ref [1, 1, TB*H] float32; then the scratch
    block_size: int,
    scale: float,
    tb_tokens: int,
    num_heads: int,
    pages_per_step: int,
    with_lse: bool,
):
    """One token block: the live-page walk of the ragged kernels over the
    latent cache, two-part scores, the context summed in latent space."""
    lse_ref = rest[0] if with_lse else None
    # ck_buf, kr_buf [2, pages * bs, R | P] VMEM double buffers; DMA
    # semaphores [2, 2]; the running max / sum / accumulator
    ck_buf, kr_buf, sems, m_ref, l_ref, acc_ref = rest[with_lse:]
    t = pl.program_id(0)
    base = t * tb_tokens

    def walk():
        softmax_init(m_ref, l_ref, acc_ref)
        q_lat = q_lat_ref[0]        # [TB*H, R]
        q_rope = q_rope_ref[0]      # [TB*H, P]
        pos_in_step = jax.lax.broadcasted_iota(
            jnp.int32, (1, pages_per_step * block_size), 1
        )
        row_lane, q_pos = row_routing(
            token_lane_ref, token_pos_ref, base, tb_tokens=tb_tokens,
            heads=num_heads,
        )
        across = (((1,), (1,)), ((), ()))

        def step_body(slot, step_lane, step_ord):
            ck = ck_buf[slot].astype(q_lat.dtype)     # [pages * bs, R]
            kr = kr_buf[slot].astype(q_rope.dtype)    # [pages * bs, P]
            s = (
                jax.lax.dot_general(
                    q_lat, ck, across, preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    q_rope, kr, across, preferred_element_type=jnp.float32)
            ) * scale                                 # [TB*H, pages * bs]
            pos = step_ord * block_size + pos_in_step
            mask = (row_lane == step_lane) & (pos <= q_pos)
            softmax_update(s, mask, ck, m_ref, l_ref, acc_ref)

        walk_live_pages(
            base, kv_steps_ref[t], span_lane_ref, span_first_ref,
            span_count_ref, block_tables_ref,
            ((ck_hbm, ck_buf), (kr_hbm, kr_buf)), sems,
            tb_tokens=tb_tokens, pages_per_step=pages_per_step,
            step_body=step_body, first_block=(t == 0) if with_lse else None,
        )
        softmax_finish(out_ref.at[0], l_ref, acc_ref)
        if with_lse:
            lse_ref[0] = _lse_row(m_ref, l_ref)

    if not with_lse:
        walk()
        return
    # the unified step's walk: a whole prompt's token blocks have nothing
    # resident (4 to 9 us each of set-up, routing and division for zeros, 512
    # to 1,024 of them a launch); such a block writes its zeros and goes (its
    # queries are not fetched either: ``_launch``'s index map).  Block 0
    # always walks: it clears the page buffers (``walk_live_pages``).
    walks = (kv_steps_ref[t] > 0) | (t == 0)
    pl.when(walks)(walk)

    @pl.when(jnp.logical_not(walks))
    def _nothing_resident():
        out_ref[...] = jnp.zeros_like(out_ref)
        lse_ref[...] = jnp.full_like(lse_ref, NEG_INF)


def _lse_row(m_ref, l_ref):
    """The rows' log-sum-exp ``[1, rows]`` (lane-major: a row of the result
    an output block) from the running max and sum ``[rows, 128]`` the softmax
    keeps broadcast over the lanes; a row that attended nothing reads
    ``NEG_INF``."""
    l = l_ref[...]
    lse = jnp.where(l > 0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    return lse.T[:1]


def _launch(
    q_lat, q_rope,              # [T, H, R], [T, H, P]
    ck_cache, kr_cache,         # [N, bs, R], [N, bs, P]
    meta,                       # the seven scalar-prefetch arrays, in order
    *, scale, tb_tokens, pages_per_step, interpret, with_lse=False,
):
    """The ``pallas_call`` the three launches share (each makes it inside its
    own jitted function).  Returns the latent context ``[T, H, R]`` in the
    queries' dtype; ``with_lse`` (static: the launches without it compile to
    the program they were) also the rows' log-sum-exp ``[T, H]`` float32."""
    t_pad, h, r = q_lat.shape
    p_dim = kr_cache.shape[-1]
    bs = ck_cache.shape[1]
    if q_rope.shape[-1] != p_dim:
        raise ValueError(
            f"q_rope is {q_rope.shape[-1]} wide, the rope page {p_dim}: the "
            "caller widens the queries, nobody widens the cache"
        )
    if t_pad % tb_tokens:
        raise ValueError(
            f"flat token axis ({t_pad}) must pack whole token blocks of "
            f"{tb_tokens}"
        )
    num_tb = t_pad // tb_tokens
    tbh = tb_tokens * h
    pps = pages_per_step
    out_specs = [
        pl.BlockSpec((1, tbh, r), lambda t, *_: (t, 0, 0)),
        pl.BlockSpec((1, 1, tbh), lambda t, *_: (t, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((num_tb, tbh, r), q_lat.dtype),
        jax.ShapeDtypeStruct((num_tb, 1, tbh), jnp.float32),
    ]
    # (a block with nothing to walk names block 0's queries: the same block
    # as the step before it, mostly, which is then not fetched again)
    queries = (lambda t, *meta: (jnp.where(meta[6][t] > 0, t, 0), 0, 0)) if with_lse else (
        lambda t, *_: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(num_tb,),
        in_specs=[
            pl.BlockSpec((1, tbh, r), queries),
            pl.BlockSpec((1, tbh, p_dim), queries),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
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
        _kernel, block_size=bs, scale=scale, tb_tokens=tb_tokens,
        num_heads=h, pages_per_step=pps, with_lse=with_lse,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if with_lse else out_shape[0],
        interpret=interpret,
    )(
        *meta,
        q_lat.reshape(num_tb, tbh, r),
        q_rope.astype(q_lat.dtype).reshape(num_tb, tbh, p_dim),
        ck_cache, kr_cache,
    )
    if with_lse:
        return out[0].reshape(t_pad, h, r), out[1].reshape(t_pad, h)
    return out.reshape(t_pad, h, r)


def _lane_spans(block_tables, context_lens, queries: int, block_size: int, pps: int):
    """The ragged kernel's metadata for launches whose token block is ONE
    lane's ``queries`` last positions (1: decode; W: a verify window), built
    on the device: one span a block, the lane's pages from its first."""
    lanes = context_lens.shape[0]
    lane = jnp.arange(lanes, dtype=jnp.int32)
    pages = (context_lens.astype(jnp.int32) + block_size - 1) // block_size
    back = jnp.arange(queries, dtype=jnp.int32) - queries       # -W .. -1
    # an empty lane's queries sit at negative positions: pads
    token_pos = jnp.where(
        context_lens[:, None] > 0, context_lens[:, None] + back[None, :], -1
    ).reshape(-1).astype(jnp.int32)
    first_of_block = lambda a: jnp.zeros(  # noqa: E731 — block t's span 0 at t * queries
        (lanes, queries), jnp.int32).at[:, 0].set(a).reshape(-1)
    return (
        jnp.repeat(lane, queries), token_pos, block_tables.astype(jnp.int32),
        first_of_block(lane), jnp.zeros((lanes * queries,), jnp.int32),
        first_of_block(pages), (pages + pps - 1) // pps,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "tb_tokens", "interpret", "pages_per_step", "with_lse",
    ),
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
    with_lse: bool = False,
):
    """Ragged unified-batch MLA paged attention with packed lanes: one
    launch over mixed chunked-prefill spans + decode tokens against the
    latent cache, only live pages copied, ``pages_per_step`` of them a KV
    step.  Returns the latent-space context [T, H, R] in ``q_lat``'s dtype;
    metadata comes from ragged_attention.pack_spans (same ``tb_tokens`` and
    ``pages_per_step``) and the latent block tables.

    ``token_pos`` is the last position a row attends: its own, or (the
    unified step, whose window's keys ``ragged_mla_attention_window`` takes)
    its lane's last RESIDENT one, ``last_resident_pos``.  ``with_lse``: also
    the rows' log-sum-exp [T, H] float32 (``NEG_INF`` where a row attended
    nothing), what merges the two parts under one softmax."""
    return _launch(
        q_lat, q_rope, ck_cache, kr_cache,
        (token_lane, token_pos, block_tables, span_lane, span_first,
         span_count, kv_steps),
        scale=scale, tb_tokens=tb_tokens, interpret=interpret,
        pages_per_step=pages_per_step or kv_step_pages(ck_cache.shape[1]),
        with_lse=with_lse,
    )


def last_resident_pos(token_lane, token_pos, lanes: int, xp=np):
    """Per row of a unified window, the last position of its lane that is
    NOT a row of the window: ``first(lane) - 1``, the lane's first position
    in the window less one (-1: nothing resident, and every pad).  The
    engine's spans are one run of ascending positions a lane, so everything
    below the first is resident and everything from it on is in the window.
    ``xp``: numpy on the host (what ``pack_spans`` is handed), ``jax.numpy``
    inside the step program (what the page walk is handed): one rule."""
    live = (token_pos >= 0) & (token_lane >= 0) & (token_lane < lanes)
    far = np.iinfo(np.int32).max
    of_lane = token_lane[None, :] == xp.arange(lanes, dtype=token_lane.dtype)[:, None]
    first = xp.min(xp.where(of_lane & live[None, :], token_pos[None, :], far), axis=1)
    return xp.where(live, first[xp.clip(token_lane, 0, lanes - 1)] - 1, -1).astype(xp.int32)


WINDOW_BLOCK = 1024     # rows of a query block and of a key block (512: a third slower)


def _window_kernel(
    k_lo_ref,           # [T // B] int32 — first key block a query block attends
    lane_q_ref,         # [B, 1] int32 — the rows' lanes (pad: -1)
    lane_k_ref,         # [1, T] int32 — the keys' lanes (pad: -2)
    qn_ref,             # [B, N] one head's q_nope
    qr_ref,             # [B, P] its rotated part, as wide as the key's
    kn_ref,             # [T, N] the head's k_nope, the whole window
    kr_ref,             # [T, P] the one rotated key
    v_ref,              # [T, V] the head's values
    out_ref,            # [B, V]
    lse_ref,            # [1, B] float32
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    block: int,
):
    """One query block of one head against the window's keys up to its own
    diagonal block: flash attention, two-part scores, ``same lane AND
    flat-causal``.  The head's keys and values stay in VMEM over its query
    blocks; key blocks above the diagonal, and those before the first row
    of the block's earliest lane, are never visited.  (Leaving the causal
    compare to the diagonal block, and every mask out of a block pair of one
    lane, bought nothing on a v5e: 4.76 and 8.56 ms either way at 16 and 32
    heads over 7,680 rows; PERF.md section 5.)"""
    i = pl.program_id(1)
    softmax_init(m_ref, l_ref, acc_ref)
    qn, qr = qn_ref[...], qr_ref[...]
    lane_q = lane_q_ref[...]
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    col_in_block = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    across = (((1,), (1,)), ((), ()))

    def step(j, _):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        s = (
            jax.lax.dot_general(
                qn, kn_ref[at, :], across, preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                qr, kr_ref[at, :], across, preferred_element_type=jnp.float32)
        ) * scale                                   # [B, B]
        mask = (lane_q == lane_k_ref[:, at]) & (j * block + col_in_block <= row)
        softmax_update(s, mask, v_ref[at, :], m_ref, l_ref, acc_ref)

    jax.lax.fori_loop(k_lo_ref[i], i + 1, step, None)
    softmax_finish(out_ref, l_ref, acc_ref)
    lse_ref[...] = _lse_row(m_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("lanes", "scale", "interpret", "block"),
)
def ragged_mla_attention_window(
    q_nope: jnp.ndarray,        # [T, H, N] flat ragged token batch
    q_rope: jnp.ndarray,        # [T, H, P] rotated, as wide as ``k_rope``
    k_nope: jnp.ndarray,        # [T, H, N] the rows' own keys, decompressed
    k_rope: jnp.ndarray,        # [T, P] the rotated key the heads share
    v: jnp.ndarray,             # [T, H, V] the rows' own values, decompressed
    token_lane: jnp.ndarray,    # [T] int32 owning lane (OOB = pad)
    token_pos: jnp.ndarray,     # [T] int32 absolute position (-1 = pad)
    *,
    lanes: int,
    scale: float,
    interpret: bool = False,
    block: int = WINDOW_BLOCK,
):
    """A unified window's rows against the window's OWN keys, decompressed:
    row i attends the rows of its lane at flat index <= i (the engine packs
    a lane's span in ascending position, so that is "position <="), a flash
    launch a head.  Returns the normalised output [T, H x V] (head-major
    columns: what ``wo`` multiplies, and no relayout on the way there) in the
    queries' dtype and the rows' log-sum-exp [T, H] float32 (a pad: zeros
    and ``NEG_INF``), which ``ragged_mla_attention``'s merges with under one
    softmax.  Precision as the launches above: operands in the queries'
    dtype, both products and the softmax state float32."""
    t, h, n = q_nope.shape
    p_dim, v_dim = k_rope.shape[-1], v.shape[-1]
    if q_rope.shape[-1] != p_dim:
        raise ValueError(
            f"q_rope is {q_rope.shape[-1]} wide, the rotated key {p_dim}")
    # whole blocks: of ``block`` rows, or the bucket itself below that
    b = block if t >= block else -(-t // 128) * 128
    t_pad = -(-t // b) * b
    live = (token_pos >= 0) & (token_lane >= 0) & (token_lane < lanes)
    rows = lambda x: jnp.pad(  # noqa: E731
        x.reshape(t, -1), ((0, t_pad - t), (0, 0)))
    lane_q = jnp.pad(jnp.where(live, token_lane, -1), (0, t_pad - t), constant_values=-1)
    # a query block starts at the block of the first row of its earliest lane
    idx = jnp.arange(t_pad, dtype=jnp.int32)
    of_lane = lane_q[None, :] == jnp.arange(lanes, dtype=jnp.int32)[:, None]
    first_row = jnp.min(jnp.where(of_lane, idx[None, :], t_pad), axis=1)
    first_of = jnp.where(lane_q >= 0, first_row[jnp.clip(lane_q, 0, lanes - 1)], idx)
    k_lo = jnp.min(first_of.reshape(-1, b), axis=1) // b
    nq = t_pad // b
    dt = q_nope.dtype
    whole = lambda width: pl.BlockSpec((t_pad, width), lambda hh, i, *_: (0, hh))  # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, block=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, nq),
            in_specs=[
                pl.BlockSpec((b, 1), lambda hh, i, *_: (i, 0)),
                pl.BlockSpec((1, t_pad), lambda hh, i, *_: (0, 0)),
                pl.BlockSpec((b, n), lambda hh, i, *_: (i, hh)),
                pl.BlockSpec((b, p_dim), lambda hh, i, *_: (i, hh)),
                whole(n),
                pl.BlockSpec((t_pad, p_dim), lambda hh, i, *_: (0, 0)),
                whole(v_dim),
            ],
            out_specs=[
                pl.BlockSpec((b, v_dim), lambda hh, i, *_: (i, hh)),
                pl.BlockSpec((None, 1, b), lambda hh, i, *_: (hh, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((b, 128), jnp.float32),
                pltpu.VMEM((b, 128), jnp.float32),
                pltpu.VMEM((b, v_dim), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, h * v_dim), dt),
            jax.ShapeDtypeStruct((h, 1, t_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # a head's keys and values whole, double-buffered, beside the
            # blocks: 12.6 MB at 8,192 rows of bf16
            vmem_limit_bytes=int(
                2 * t_pad * (n + p_dim + v_dim) * dt.itemsize + 24 * 2**20),
        ),
        interpret=interpret,
    )(
        k_lo,
        lane_q[:, None],
        jnp.where(lane_q < 0, -2, lane_q)[None, :],
        rows(q_nope), rows(q_rope.astype(dt)),
        rows(k_nope.astype(dt)), rows(k_rope.astype(dt)), rows(v.astype(dt)),
    )
    return out[:t], lse.reshape(h, t_pad)[:, :t].T


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "pages_per_step")
)
def mla_paged_attention_decode(
    q_lat: jnp.ndarray,         # [B, H, R]
    q_rope: jnp.ndarray,        # [B, H, P]
    ck_cache: jnp.ndarray,      # [N, bs, R] latent cache
    kr_cache: jnp.ndarray,      # [N, bs, P] rope-key cache
    block_tables: jnp.ndarray,  # [B, maxb] int32
    context_lens: jnp.ndarray,  # [B] int32 INCLUDING the query's own token
    *,
    scale: float,
    interpret: bool = False,
    pages_per_step: int | None = None,
) -> jnp.ndarray:
    """One query a lane against its latent pages.  Returns the latent-space
    context [B, H, R] in ``q_lat``'s dtype (zeros for an empty lane)."""
    bs = ck_cache.shape[1]
    pps = min(pages_per_step or kv_step_pages(bs), block_tables.shape[1])
    return _launch(
        q_lat, q_rope, ck_cache, kr_cache,
        _lane_spans(block_tables, context_lens, 1, bs, pps),
        scale=scale, tb_tokens=1, interpret=interpret, pages_per_step=pps,
    )


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
    """Multi-query MLA paged attention for speculative verification: a
    lane's W window queries are one token block, each masked to its own
    position.  Returns the latent-space context [B, W, H, R]."""
    b, w, h, r = q_lat.shape
    bs = ck_cache.shape[1]
    pps = min(kv_step_pages(bs), block_tables.shape[1])
    out = _launch(
        q_lat.reshape(b * w, h, r), q_rope.reshape(b * w, h, -1),
        ck_cache, kr_cache,
        _lane_spans(block_tables, context_lens, w, bs, pps),
        scale=scale, tb_tokens=w, interpret=interpret, pages_per_step=pps,
    )
    return out.reshape(b, w, h, r)
