"""``ops/sampling.py:sample_tokens`` against the body it had before its
drawing path moved under a ``lax.cond`` (kept here, to the letter, as the
reference): the same tokens to the bit whatever the lanes ask for, and no
sort over the vocabulary outside the branch."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.sampling import NEG_INF, sample_tokens

LANES, VOCAB = 6, 4096


def sample_tokens_unconditional(logits, rng, temperature, top_k, top_p, greedy):
    """``sample_tokens`` as it was: every lane pays the sort, the softmax,
    the running sum and the draw, and ``jnp.where`` throws away what a
    greedy lane drew."""
    b, v = logits.shape
    logits = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    force_greedy = greedy | (temperature <= 1e-5)
    safe_temp = jnp.where(force_greedy, 1.0, temperature)
    scaled = logits / safe_temp[:, None]

    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sort_idx = jnp.argsort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    ranks = jnp.arange(v)[None, :]

    k_eff = jnp.where(top_k <= 0, v, top_k)[:, None]
    p_eff = jnp.where(top_p >= 1.0, 2.0, top_p)[:, None]
    keep = (ranks < k_eff) & (cum_excl < p_eff)
    keep = keep.at[:, 0].set(True)

    filtered_sorted = jnp.where(keep, sorted_logits, NEG_INF)
    if rng.ndim == 1:
        keys = jax.random.split(rng, b)
    else:
        keys = rng
    choice = jax.vmap(lambda k, lg: jax.random.categorical(k, lg))(keys, filtered_sorted)
    sampled_ids = jnp.take_along_axis(sort_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    return jnp.where(force_greedy, greedy_ids, sampled_ids)


def sorts_by_reach(hlo: str) -> tuple[int, int]:
    """(``sort`` instructions in an HLO module's text, those among them that
    ENTRY reaches without entering a branch of a ``conditional``).  Reads
    the text of a lowering and of a compiled executable alike."""
    bodies, entry = {}, None
    for m in re.finditer(r"^(ENTRY\s+)?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S):
        bodies[m[2]] = m[3]
        entry = m[2] if m[1] else entry

    def callees(name):
        lines = [ln for ln in bodies[name].splitlines() if " conditional(" not in ln]
        return {t for t in re.findall(r"[\w.\-]+", "\n".join(lines)) if t in bodies}

    outside, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name not in outside:
            outside.add(name)
            todo += callees(name)

    def count(names):
        return sum(len(re.findall(r" sort\(", bodies[n])) for n in names)

    return count(bodies), count(outside)


# which lanes sample: (greedy flag, temperature) a lane.  A lane with the
# flag down and a temperature of 0 is greedy by the program's own rule.
BATCHES = {
    "all_greedy": [(True, 0.0), (True, 0.9), (False, 0.0), (True, 0.0), (False, 1e-6), (True, 1.3)],
    "one_samples": [(True, 0.0), (True, 0.9), (False, 1.1), (True, 0.0), (False, 0.0), (True, 1.3)],
    "all_sample": [(False, 0.7), (False, 0.9), (False, 1.1), (False, 1.0), (False, 1.6), (False, 1.3)],
}
NO_K, SOME_K = np.zeros(LANES, np.int32), np.array([0, 5, 40, 1, 300, 17], np.int32)
NO_P, SOME_P = np.ones(LANES, np.float32), np.array([0.9, 0.5, 0.95, 1.0, 0.1, 0.7], np.float32)
FILTERS = {
    "temperature": (NO_K, NO_P),
    "top_k": (SOME_K, NO_P),
    "top_p": (NO_K, SOME_P),
    "top_k_and_top_p": (SOME_K, SOME_P),
}


@pytest.mark.parametrize("filters", sorted(FILTERS))
@pytest.mark.parametrize("keys", ["one_key", "lane_keys"])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_the_tokens_are_the_unconditional_paths_to_the_bit(batch, keys, filters):
    logits = (3.0 * jax.random.normal(jax.random.PRNGKey(7), (LANES, VOCAB))).astype(jnp.bfloat16)
    rng = jax.random.PRNGKey(11)
    if keys == "lane_keys":
        rng = jax.random.split(rng, LANES)
    greedy, temperature = map(np.array, zip(*BATCHES[batch]))
    top_k, top_p = FILTERS[filters]
    args = (logits, rng, temperature.astype(np.float32), top_k, top_p, greedy)

    got = np.asarray(jax.jit(sample_tokens)(*args))
    want = np.asarray(jax.jit(sample_tokens_unconditional)(*args))
    assert got.dtype == np.int32 and np.array_equal(got, want)

    best = np.asarray(jnp.argmax(logits.astype(jnp.float32), axis=-1))
    sampling = ~(greedy | (temperature <= 1e-5))
    assert np.array_equal(got[~sampling], best[~sampling])
    if filters == "temperature" and sampling.any():
        # the comparison is of draws, not of two argmaxes
        assert (got[sampling] != best[sampling]).any()


def test_no_sort_over_the_vocabulary_outside_the_branch():
    """Lowered at the width the benchmark's `qwen3-4b` serves (16 lanes of
    151,936): every ``sort`` sits under the ``conditional``; the
    unconditional body is the control (its sorts are all in the open)."""
    s = jax.ShapeDtypeStruct
    lanes, vocab = 16, 151936
    args = (
        s((lanes, vocab), jnp.bfloat16), s((lanes, 2), jnp.uint32), s((lanes,), jnp.float32),
        s((lanes,), jnp.int32), s((lanes,), jnp.float32), s((lanes,), jnp.bool_),
    )
    hlo = jax.jit(sample_tokens).lower(*args).as_text(dialect="hlo")
    assert " conditional(" in hlo
    total, outside = sorts_by_reach(hlo)
    assert total >= 1 and outside == 0

    control = jax.jit(sample_tokens_unconditional).lower(*args).as_text(dialect="hlo")
    total, outside = sorts_by_reach(control)
    assert total >= 1 and outside == total
