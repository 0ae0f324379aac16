"""Token sampling: vectorized greedy / temperature / top-k / top-p.

All sampling parameters are per-request arrays so one jitted call samples an
entire continuous batch with heterogeneous settings (static shapes, no
per-request branching).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def sample_tokens(
    logits: jnp.ndarray,        # [batch, vocab] (any float dtype)
    rng: jax.Array,             # one key [2] (split per lane) or per-lane keys [batch, 2]
    temperature: jnp.ndarray,   # [batch] float32; <=0 treated as greedy
    top_k: jnp.ndarray,         # [batch] int32; <=0 disables
    top_p: jnp.ndarray,         # [batch] float32; >=1 disables
    greedy: jnp.ndarray,        # [batch] bool
) -> jnp.ndarray:
    """Returns sampled token ids [batch] int32.

    Per-lane keys make sampling reproducible per request (OpenAI ``seed``):
    lane i draws only from its own key stream regardless of batch
    composition.

    Everything that exists only to draw a sample runs under one
    ``lax.cond``, taken when some lane samples: a step whose lanes all
    decode greedily sorts no vocabulary."""
    b, v = logits.shape
    logits = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    force_greedy = greedy | (temperature <= 1e-5)

    def draw():
        safe_temp = jnp.where(force_greedy, 1.0, temperature)
        scaled = logits / safe_temp[:, None]

        # sorted-space filtering: one descending sort serves both top-k and top-p
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        sort_idx = jnp.argsort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum_excl = jnp.cumsum(probs, axis=-1) - probs
        ranks = jnp.arange(v)[None, :]

        k_eff = jnp.where(top_k <= 0, v, top_k)[:, None]
        p_eff = jnp.where(top_p >= 1.0, 2.0, top_p)[:, None]
        keep = (ranks < k_eff) & (cum_excl < p_eff)
        keep = keep.at[:, 0].set(True)  # always keep the best token

        filtered_sorted = jnp.where(keep, sorted_logits, NEG_INF)
        # sample in sorted space, map back through sort_idx
        if rng.ndim == 1:
            keys = jax.random.split(rng, b)
        else:
            keys = rng
        choice = jax.vmap(lambda k, lg: jax.random.categorical(k, lg))(keys, filtered_sorted)
        return jnp.take_along_axis(sort_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    # the predicate is read on the device, from the lanes' own parameters
    sampled_ids = jax.lax.cond(jnp.any(~force_greedy), draw, lambda: greedy_ids)
    return jnp.where(force_greedy, greedy_ids, sampled_ids)


def apply_penalties(
    logits: jnp.ndarray,            # [batch, vocab]
    gen_counts: jnp.ndarray,        # [batch, vocab] int32: tokens generated so far
    prompt_counts: jnp.ndarray,     # [batch, vocab] int32: prompt token counts
    presence_penalty: jnp.ndarray,  # [batch]
    frequency_penalty: jnp.ndarray,  # [batch]
    repetition_penalty: jnp.ndarray,  # [batch]; 1.0 disables
) -> jnp.ndarray:
    """OpenAI presence/frequency penalties apply to *generated* tokens; the
    HF-style repetition penalty applies to everything seen (prompt +
    generated)."""
    logits = logits.astype(jnp.float32)
    generated = (gen_counts > 0).astype(jnp.float32)
    logits = logits - presence_penalty[:, None] * generated
    logits = logits - frequency_penalty[:, None] * gen_counts.astype(jnp.float32)
    seen = (gen_counts > 0) | (prompt_counts > 0)
    rep = repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen, penalized, logits)
    return logits


def apply_logit_bias(
    logits: jnp.ndarray,  # [batch, vocab] f32
    ids: jnp.ndarray,     # [batch, K] int32; pad entries = vocab (dropped)
    vals: jnp.ndarray,    # [batch, K] f32
) -> jnp.ndarray:
    """OpenAI ``logit_bias``: add per-token biases before sampling.  The
    sparse (ids, vals) rows are fixed-width (engine compile bucket); OOB
    pad ids drop out of the scatter."""
    if ids.shape[-1] == 0:
        return logits
    b = logits.shape[0]
    return logits.at[jnp.arange(b)[:, None], ids].add(vals, mode="drop")


def token_logprobs(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """log-softmax probability of each chosen token [batch] (float32),
    computed from the given logits (the engine passes the penalized,
    untempered distribution — vLLM's convention for reported logprobs)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, tokens.astype(jnp.int32)[:, None], axis=-1
    )[:, 0]
    return picked - lse


def topk_logprobs(logits: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k log-softmax probabilities and their token ids
    ([batch, k] f32, [batch, k] i32) from the given logits."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    vals, ids = jax.lax.top_k(logits, k)
    return vals - lse, ids.astype(jnp.int32)
