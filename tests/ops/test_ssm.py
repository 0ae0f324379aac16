"""ops/ssm.py: the selective scan over a step's flat rows against a plain
scan over time, one sequence at a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.norms import layer_norm
from dynamo_tpu.ops.ssm import (
    conv_state_out,
    conv_taps,
    selective_scan,
    selective_step,
    span_offsets,
)

D, N, TAPS, LANES = 24, 4, 4, 5


def _over_time(a, delta, b, c, a_neg, h):
    """One sequence, token by token: (y [t, D], the state after it)."""
    ys = []
    for t in range(a.shape[0]):
        h = np.exp(delta[t][None, :] * a_neg) * h + (delta[t] * a[t])[None, :] * b[t][:, None]
        ys.append((h * c[t][:, None]).sum(0))
    return np.array(ys).reshape(-1, a.shape[1]), h


def _step_rows(rng):
    """A step's flat rows: lane 3 decodes at position 9, lane 0 sends a prompt
    of 11 from position 0, a hole, lane 4 continues a span from position 6
    (5 rows), lane 1 decodes at position 0, padding."""
    lane = np.array([3] + [0] * 11 + [LANES] + [4] * 5 + [1] + [LANES] * 3, np.int32)
    pos = np.array([9] + list(range(11)) + [-1] + list(range(6, 11)) + [0] + [-1] * 3, np.int32)
    rows = lane.shape[0]
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    return lane, pos, draw(rows, D), np.abs(draw(rows, D)) * 0.5, draw(rows, N), draw(rows, N)


@pytest.mark.parametrize("chunk", [1, 4, 7, 16, 64])
def test_the_chunked_scan_equals_the_scan_over_time(chunk):
    """Chunks that do and do not divide the 23 rows, spans that start and end
    inside a chunk: every span's rows and every lane's state afterwards are
    what a scan over that sequence alone gives; a span from position 0 starts
    from zeros whatever its lane held; lanes without a row keep their state."""
    rng = np.random.default_rng(0)
    lane, pos, a, delta, b, c = _step_rows(rng)
    a_neg = -np.exp(rng.standard_normal((N, D)).astype(np.float32) * 0.3)
    state = rng.standard_normal((LANES, N, D)).astype(np.float32)
    live = (pos >= 0) & (lane < LANES)
    y, out = jax.jit(selective_scan, static_argnames="chunk")(
        a, delta, b, c, a_neg, np.clip(lane, 0, LANES - 1), live, pos == 0, state, chunk=chunk)
    y, out = np.asarray(y), np.asarray(out)
    for which, rows in ((3, slice(0, 1)), (0, slice(1, 12)), (4, slice(13, 18)), (1, slice(18, 19))):
        h0 = np.zeros((N, D), np.float32) if pos[rows][0] == 0 else state[which]
        want_y, want_h = _over_time(a[rows], delta[rows], b[rows], c[rows], a_neg, h0)
        np.testing.assert_allclose(y[rows], want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out[which], want_h, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(out[2], state[2])


def test_a_decode_step_is_the_scan_of_one_row_a_lane():
    rng = np.random.default_rng(1)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    a, delta, b, c = draw(LANES, D), np.abs(draw(LANES, D)), draw(LANES, N), draw(LANES, N)
    a_neg, state = -np.abs(draw(N, D)), draw(LANES, N, D)
    live = np.array([True, False, True, True, False])
    fresh = np.array([False, False, True, False, False])
    y, out = selective_step(a, delta, b, c, a_neg, live, fresh, state)
    y2, out2 = selective_scan(a, delta, b, c, a_neg, np.arange(LANES), live, fresh, state)
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y2)[live], rtol=1e-6)
    np.testing.assert_allclose(out, out2, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out)[~live], state[~live])


def test_the_convolutions_taps_cross_a_spans_start_through_the_lanes_kept_inputs():
    rng = np.random.default_rng(2)
    lane, pos, a, *_ = _step_rows(rng)
    kept = rng.standard_normal((LANES, TAPS - 1, D)).astype(np.float32)
    live = (pos >= 0) & (lane < LANES)
    lane_c = np.clip(lane, 0, LANES - 1)
    off = np.asarray(span_offsets(jnp.asarray(lane), jnp.asarray(pos), jnp.asarray(live)))
    np.testing.assert_array_equal(off[live], [0, *range(11), *range(5), 0])
    taps = conv_taps(jnp.asarray(a), jnp.asarray(kept), lane_c, off, (pos - off) == 0, TAPS)
    taps = np.stack([np.asarray(t) for t in taps], axis=1)      # [rows, TAPS, D], oldest first
    history = {3: kept[3], 0: np.zeros_like(kept[0]), 4: kept[4], 1: np.zeros_like(kept[1])}
    for which, rows in ((3, range(0, 1)), (0, range(1, 12)), (4, range(13, 18)), (1, range(18, 19))):
        seen = np.concatenate([history[which], a[list(rows)]])
        for i, row in enumerate(rows):
            np.testing.assert_array_equal(taps[row], seen[i:i + TAPS])
        out = conv_state_out(
            [jnp.asarray(taps[:, j]) for j in range(TAPS)], jnp.asarray(kept), lane_c, live)
        np.testing.assert_array_equal(np.asarray(out)[which], seen[-(TAPS - 1):])
    np.testing.assert_array_equal(np.asarray(out)[2], kept[2])


def test_layer_norm_is_float32_inside():
    """Mean and variance in float32 whatever the input's type, weight and
    bias applied, the input's type handed back."""
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((7, 64)) * 3 + 100).astype(np.float32)
    w, b = rng.standard_normal(64).astype(np.float32), rng.standard_normal(64).astype(np.float32)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b
    np.testing.assert_allclose(layer_norm(jnp.asarray(x), w, b, 1e-5), want, rtol=1e-4, atol=1e-4)
    low = layer_norm(jnp.asarray(x, jnp.bfloat16), w, b, 1e-5)
    assert low.dtype == jnp.bfloat16
    x_low = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    want_low = (x_low - x_low.mean(-1, keepdims=True)) / np.sqrt(
        x_low.var(-1, keepdims=True) + 1e-5) * w + b
    np.testing.assert_allclose(np.asarray(low, np.float32), want_low, rtol=2e-2, atol=2e-2)
