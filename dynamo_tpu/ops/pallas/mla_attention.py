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

Each is a jitted function of its own so that the kernel keeps the name the
device trace shows (``benchmark/metrics/mla_*``).

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
    """One token block: the live-page walk of the ragged kernels over the
    latent cache, two-part scores, the context summed in latent space."""
    t = pl.program_id(0)
    base = t * tb_tokens

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
        step_body=step_body,
    )
    softmax_finish(out_ref.at[0], l_ref, acc_ref)


def _launch(
    q_lat, q_rope,              # [T, H, R], [T, H, P]
    ck_cache, kr_cache,         # [N, bs, R], [N, bs, P]
    meta,                       # the seven scalar-prefetch arrays, in order
    *, scale, tb_tokens, pages_per_step, interpret,
):
    """The ``pallas_call`` the three launches share (each makes it inside its
    own jitted function).  Returns the latent context ``[T, H, R]`` in the
    queries' dtype."""
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
        _kernel, block_size=bs, scale=scale, tb_tokens=tb_tokens,
        num_heads=h, pages_per_step=pps,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tb, tbh, r), q_lat.dtype),
        interpret=interpret,
    )(
        *meta,
        q_lat.reshape(num_tb, tbh, r),
        q_rope.astype(q_lat.dtype).reshape(num_tb, tbh, p_dim),
        ck_cache, kr_cache,
    )
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
    step.  Returns the latent-space context [T, H, R] in ``q_lat``'s dtype;
    metadata comes from ragged_attention.pack_spans (same ``tb_tokens`` and
    ``pages_per_step``) and the latent block tables."""
    return _launch(
        q_lat, q_rope, ck_cache, kr_cache,
        (token_lane, token_pos, block_tables, span_lane, span_first,
         span_count, kv_steps),
        scale=scale, tb_tokens=tb_tokens, interpret=interpret,
        pages_per_step=pages_per_step or kv_step_pages(ck_cache.shape[1]),
    )


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
