"""State-space (Mamba-1, arXiv:2312.00752) ops over a step's flat rows.

A state-space layer keeps, for every sequence, a recurrent state
``h [d_state, d_inner]`` (float32) and the last ``d_conv - 1`` inputs of its
short causal convolution.  Both live in the cache pytree, one slot a LANE
(``[lanes, ...]``): a sequence holds its lane from admission to release, so
nothing is allocated and nothing is looked up.

Rows of a step are flat, as the unified step hands them: each row belongs to
a lane, rows of one span are consecutive and in order of position.  A row
whose span began at position 0 starts from zeros IN THE PROGRAM (the lane's
previous tenant, or a step still in flight when the lane was released, left
whatever it left); any other row continues its lane's slot.  Rows that are no
token write nothing.

The state is laid out ``[d_state, d_inner]`` (the wide axis minor): a
``[d_inner, 16]`` array would pad its 16 to a vector register's 128 lanes and
take eight times the memory and the traffic.

- ``conv_taps``: the convolution's inputs ``a[t - j]`` of every row, taken
  from the step's own rows inside a span and from the lane's kept taps before
  it; ``conv_state_out`` the taps each lane keeps after the step.
- ``selective_scan``: the recurrence over a step's rows IN ORDER, ``chunk``
  rows unrolled an iteration.  The lanes' state array is the loop's carry:
  a row reads its lane's slot, updates it and writes it back, so no
  ``[rows, d_state, d_inner]`` array exists anywhere (8,192 rows of it would
  be 2.7 GB a layer) and a span that ends inside a chunk needs no gather.
- ``selective_step``: the same for a decode step's rows, one a lane, as ONE
  fused read-modify-write of the lanes' state.

float32 inside, whatever the model's dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def span_offsets(lane: jnp.ndarray, pos: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
    """For every flat row, how many rows of its own span lie before it
    (0 for a span's first row, and for a decode row).  A span begins where
    the lane changes or the positions stop being consecutive."""
    t = jnp.arange(lane.shape[0], dtype=jnp.int32)
    before = lambda a, fill: jnp.concatenate([jnp.full((1,), fill, a.dtype), a[:-1]])  # noqa: E731
    begins = (
        (lane != before(lane, -1)) | (pos != before(pos, -2) + 1) | ~before(live, False)
    )
    first = jax.lax.cummax(jnp.where(begins | ~live, t, 0))
    return t - first


def conv_taps(a, conv_state, lane, off, fresh, taps: int):
    """``[a[t - taps + 1], ..., a[t]]`` for every row ``t`` (each ``[rows,
    d_inner]``, in ``a``'s dtype): a row ``j`` back comes from the step's own
    rows where the span reaches that far (``off >= j``), else from the lane's
    kept inputs ``conv_state [lanes, taps - 1, d_inner]`` (the last one is the
    newest), else (``fresh``: the span began at position 0) it is zero."""
    rows = a.shape[0]
    t = jnp.arange(rows, dtype=jnp.int32)
    out = [a]
    for j in range(1, taps):
        own = a[jnp.maximum(t - j, 0)]
        kept = conv_state[lane, jnp.clip(taps - 1 + off - j, 0, taps - 2)]
        kept = jnp.where(fresh[:, None], jnp.zeros_like(kept), kept.astype(a.dtype))
        out.append(jnp.where((off >= j)[:, None], own, kept))
    return out[::-1]


def conv_state_out(taps_of_rows, conv_state, lane, live):
    """The inputs each lane keeps after the step: those of its LAST live row
    (``taps_of_rows`` as ``conv_taps`` gave them, the row's own input last);
    a lane without a row keeps what it had."""
    lanes = conv_state.shape[0]
    t = jnp.arange(lane.shape[0], dtype=jnp.int32)
    last = jnp.full((lanes,), -1, jnp.int32).at[lane].max(jnp.where(live, t, -1))
    at = jnp.maximum(last, 0)
    new = jnp.stack([tap[at] for tap in taps_of_rows[1:]], axis=1)
    return jnp.where((last >= 0)[:, None, None], new.astype(conv_state.dtype), conv_state)


def _update(h, a, delta, b, c, a_neg):
    """One row's recurrence on ``h [..., d_state, d_inner]``: decay by
    ``exp(delta A)``, add ``(delta a) B^T``, read out along ``C``."""
    decay = jnp.exp(delta[..., None, :] * a_neg)
    h = decay * h + (delta * a)[..., None, :] * b[..., :, None]
    return h, jnp.sum(h * c[..., :, None], axis=-2)


def selective_scan(a, delta, b, c, a_neg, lane, live, fresh, state, *, chunk: int = 16):
    """The recurrence over flat rows in order.

    ``a``, ``delta`` ``[rows, d_inner]``; ``b``, ``c`` ``[rows, d_state]``;
    ``a_neg`` ``[d_state, d_inner]`` (``-exp(A_log)``); ``lane`` ``[rows]``
    (in range: clip it), ``live`` which rows are tokens, ``fresh`` which rows
    are themselves at position 0 and so START from zeros (the rows after one
    continue the slot it just wrote); ``state`` ``[lanes, d_state, d_inner]``
    float32.  Returns ``(y [rows, d_inner] float32, state)``: ``y`` WITHOUT
    the skip term.  ``chunk`` rows are unrolled an iteration of the loop."""
    f32 = jnp.float32
    rows = a.shape[0]
    xs = (a.astype(f32), delta.astype(f32), b.astype(f32), c.astype(f32), lane, live, fresh)

    def row(state, x):
        a_t, delta_t, b_t, c_t, lane_t, live_t, fresh_t = x
        h = jax.lax.dynamic_index_in_dim(state, lane_t, keepdims=False)
        h_new, y = _update(jnp.where(fresh_t, 0.0, h), a_t, delta_t, b_t, c_t, a_neg)
        state = jax.lax.dynamic_update_index_in_dim(
            state, jnp.where(live_t, h_new, h), lane_t, 0
        )
        return state, y

    with jax.named_scope("ssm_scan"):
        state, y = jax.lax.scan(row, state, xs, unroll=max(1, min(chunk, rows)))
    return y, state


def selective_step(a, delta, b, c, a_neg, live, fresh, state):
    """A decode step: row ``i`` is lane ``i``'s next token.  One fused
    read-modify-write of ``state [lanes, d_state, d_inner]``; rows that are
    no token leave their lane's slot as it was."""
    f32 = jnp.float32
    with jax.named_scope("ssm_step"):
        h0 = jnp.where(fresh[:, None, None], 0.0, state)
        h, y = _update(h0, a.astype(f32), delta.astype(f32), b.astype(f32), c.astype(f32), a_neg)
        return y, jnp.where(live[:, None, None], h, state)
