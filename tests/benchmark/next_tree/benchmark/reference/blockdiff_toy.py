"""The dry run's second reference: a decoder that generates by passes over a
block, with the FIVE functions of the interface (``benchmark/README.md``):
``init_weights``, ``hidden``, ``logits``, ``quantize`` and ``decided_by``.
Small enough to be instantiated on the CPU (``tests/benchmark/test_decided_by.py``);
no family of the program serves it.

**The model.**  A dense llama-like block (RMSNorm, rotary positions in the
split-half convention, grouped-query attention, SwiGLU, untied head), float32
activations, every product at precision ``highest``, no cache, no kernels.
What differs from a next-token decoder is the mask and the loop around it.
The mask is causal over BLOCKS of ``block_length`` positions and whole inside
one: query ``i`` sees key ``j`` iff ``j // B <= i // B``.  Blocks are counted
from position 0 of the sequence, so a prompt's last block may be part answer.

**The loop** (``generate``, for the tests; the harness never calls it).  The
answer's positions start hidden: the program's input holds ``mask_token_id``
there.  A block is passed through the model ``ceil(c / tokens_per_pass)``
times, ``c`` its answer positions; position ``i``'s logits name the token AT
``i`` (no shift); after each pass the ``tokens_per_pass`` most confident hidden
positions are fixed (fewer where fewer are left), the earlier position first
where two are as confident.  Every earlier block is seen committed, whole.  An
answer's last block is cut where the answer ends: positions past it do not
exist.  WHICH positions are hidden is kept beside the ids, never read off
them: a prompt or an answer may hold ``mask_token_id`` as a word.

**``decided_by``** rebuilds, from the pass numbers the server reported, what
the program's input held when each served token was fixed, and hands back the
trunk's row that decided it.  Position ``q`` of a block is visible at pass
``s`` iff it is a prompt position or ``served_passes[q - n] < s``.  No cache:
for each pass number ONE trunk over two streams laid end to end, the committed
ids and the ids as they stood at that pass; a pass-``s`` block attends itself
whole and the committed stream's EARLIER blocks, the committed stream attends
itself block-causally (the form such models are trained in).  Because the
committed stream is rebuilt from the ids, a program that skipped the
committing pass and left a stale key behind is caught in every later block.
It refuses a path the stated schedule cannot produce: more than
``tokens_per_pass`` tokens fixed in one pass of a block, or a pass number past
the block's last (fewer passes are a different result, not a faster one).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.check import PAD

HIGHEST = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return {"h": hf["hidden_size"], "i": hf["intermediate_size"], "l": hf["num_hidden_layers"],
            "heads": heads, "kv": hf["num_key_value_heads"], "d": hf["head_dim"], "v": hf["vocab_size"],
            "eps": hf["rms_norm_eps"], "theta": hf["rope_theta"], "block": hf["block_length"],
            "per_pass": hf["tokens_per_pass"], "mask_id": hf["mask_token_id"]}


def init_weights(hf: dict, seed: int) -> dict:
    """bfloat16, layer-stacked: ``normal / sqrt(fan_in)`` from
    ``split(PRNGKey(seed), 9)``, the embedding drawn as narrow as a matrix
    (fan-in the hidden width: at fan-in 1 a hidden position's row is its
    ``[MASK]`` embedding and little else, and every path reads alike); norms
    all ones, not kept."""
    c = dims(hf)
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    h, i, l, qd, kvd = c["h"], c["i"], c["l"], c["heads"] * c["d"], c["kv"] * c["d"]
    shapes = {"embed": ((c["v"], h), h), "wq": ((l, h, qd), h), "wk": ((l, h, kvd), h),
              "wv": ((l, h, kvd), h), "wo": ((l, qd, h), qd), "w_gate": ((l, h, i), h),
              "w_up": ((l, h, i), h), "w_down": ((l, i, h), i), "lm_head": ((h, c["v"]), h)}
    return {name: (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(jnp.bfloat16)
            for key, (name, (shape, fan_in)) in zip(keys, shapes.items())}


def _round_fp8(w):
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 240.0
    q = jax.lax.reduce_precision(w32 / jnp.maximum(scale, 1e-30), exponent_bits=4, mantissa_bits=3)
    return (q * scale).astype(jnp.bfloat16)


def quantize(leaves: dict, kind: str, hf: dict) -> dict:
    """The control: every matrix a token multiplies against through float8
    (e4m3, scaled a column) and back; the embedding is only looked up."""
    rounding = {"fp8": _round_fp8}[kind]
    return {k: (v if k == "embed" else rounding(v)) for k, v in leaves.items()}


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HIGHEST)


def _layer(x, w, cos, sin, mask, c):
    t = x.shape[0]
    a = _rms(x, c["eps"])
    q = _rope(_mm(a, w["wq"]).reshape(t, c["heads"], c["d"]), cos, sin)
    k = _rope(_mm(a, w["wk"]).reshape(t, c["kv"], c["d"]), cos, sin)
    v = _mm(a, w["wv"]).reshape(t, c["kv"], c["d"])
    qg = q.reshape(t, c["kv"], c["heads"] // c["kv"], c["d"])
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HIGHEST) / math.sqrt(c["d"])
    # a finite floor: a padded row that sees nothing stays a number, and its
    # keys and values, which nobody sees, poison nothing
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST).reshape(t, -1)
    x = x + _mm(o, w["wo"])
    m = _rms(x, c["eps"])
    return x + _mm(jax.nn.silu(_mm(m, w["w_gate"])) * _mm(m, w["w_up"]), w["w_down"])


_layer_jit = jax.jit(lambda x, w, cos, sin, mask, frozen: _layer(x, w, cos, sin, mask, dict(frozen)),
                     static_argnums=(5,))


def _trunk(weights: dict, c: dict, ids, pos, mask):
    """The last block's output for the tokens ``ids`` standing at the
    positions ``pos`` (two streams share positions), query ``i`` seeing key
    ``j`` where ``mask[i, j]``."""
    pos = jnp.asarray(pos, jnp.float32)
    half = c["d"] // 2
    freqs = 1.0 / (c["theta"] ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    frozen = tuple(sorted(c.items()))
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    for layer in range(c["l"]):
        x = _layer_jit(x, {n: weights[n][layer] for n in MATRICES}, cos, sin, jnp.asarray(mask), frozen)
    return x


def _block_causal(c: dict, length: int, live: int):
    """Positions, and who sees whom, in ONE stream of ``length`` places of
    which the first ``live`` exist: the blocks before one's own and one's
    own whole."""
    pos = np.arange(length)
    blk = pos // c["block"]
    return pos, blk, (blk[None, :] <= blk[:, None]) & (pos < live)[None, :]


def hidden(weights: dict, hf: dict, ids):
    """The trunk over ``ids`` with nothing hidden: the committed stream."""
    c = dims(hf)
    pos, _, mask = _block_causal(c, len(ids), len(ids))
    return _trunk(weights, c, ids, pos, mask)


def logits(weights: dict, hf: dict, x):
    """Final norm and the untied head over rows of a trunk's output."""
    return _mm(_rms(x, dims(hf)["eps"]), weights["lm_head"])


def _passes_of_block(c: dict, answer_positions: int) -> int:
    return -(-answer_positions // c["per_pass"])


def _checked_path(c: dict, n: int, served_passes) -> np.ndarray:
    """The reported pass numbers, held to the stated schedule block by block."""
    passes = np.asarray(served_passes, np.int64)
    end, size = n + len(passes), c["block"]
    for b in range(n // size, -(-end // size)):
        lo, hi = max(b * size, n), min((b + 1) * size, end)
        own = passes[lo - n:hi - n]
        last = _passes_of_block(c, hi - lo) - 1
        outside = own[(own < 0) | (own > last)]
        if outside.size:
            raise ValueError(f"block {b}: a token fixed at pass {int(outside[0])}, and the schedule's "
                             f"passes over its {hi - lo} answer positions are 0..{last}")
        if np.bincount(own).max() > c["per_pass"]:
            raise ValueError(f"block {b}: {int(np.bincount(own).max())} tokens fixed in one pass, "
                             f"and the configuration states {c['per_pass']} a pass")
    return passes


def decided_by(weights: dict, hf: dict, prompt_ids, served_ids, served_passes, length: int):
    """``[len(served_ids) to the next multiple of PAD, width]``: for each
    served token the trunk's row at its own position, in the pass that fixed
    it, over the input as it stood then."""
    c = dims(hf)
    n, m = len(prompt_ids), len(served_ids)
    passes = _checked_path(c, n, served_passes)
    committed = np.zeros(length, np.int64)
    committed[:n + m] = list(prompt_ids) + list(served_ids)
    pos, blk, own_stream = _block_causal(c, length, n + m)
    live = (pos < n + m)[None, :]
    earlier = (blk[None, :] < blk[:, None]) & live
    same = (blk[None, :] == blk[:, None]) & live
    # committed | as it stood: the first stream never sees the second
    mask = np.block([[own_stream, np.zeros_like(same)], [earlier, same]])
    fixed_at = np.full(length, -1)          # a prompt position is visible in every pass
    fixed_at[n:n + m] = passes
    out = jnp.zeros((m + (-m % PAD), c["h"]), jnp.float32)
    for s in range(_passes_of_block(c, c["block"])):
        stood = np.where(fixed_at < s, committed, c["mask_id"])
        x = _trunk(weights, c, np.concatenate([committed, stood]), np.concatenate([pos, pos]), mask)
        mine = np.flatnonzero(passes == s)
        out = out.at[mine].set(x[length + n + mine])
    return out


def generate(weights: dict, hf: dict, prompt_ids, max_tokens: int, top_k: int = 0) -> dict:
    """The plain loop, one stream, no cache: what a sound server returns for
    this prompt (``ids``), the pass that fixed each token (``passes``), its
    log-probability there (``logprobs``) and, with ``top_k``, the first k
    ``[token, log-probability]`` of its deciding row (``top``)."""
    c = dims(hf)
    n, end, size = len(prompt_ids), len(prompt_ids) + max_tokens, c["block"]
    length = end + (-end % 32)
    ids = np.zeros(length, np.int64)
    ids[:n] = prompt_ids
    hidden_now = np.zeros(length, bool)     # kept beside the ids, never read off them
    passes, logprobs, top = np.zeros(max_tokens, np.int64), np.zeros(max_tokens), [None] * max_tokens
    for b in range(n // size, -(-end // size)):
        lo, hi = max(b * size, n), min((b + 1) * size, end)
        ids[lo:hi], hidden_now[lo:hi] = c["mask_id"], True
        pos, _, mask = _block_causal(c, length, hi)
        for s in range(_passes_of_block(c, hi - lo)):
            rows = np.asarray(jax.nn.log_softmax(logits(weights, hf, _trunk(weights, c, ids, pos, mask)[lo:hi])))
            confidence = np.where(hidden_now[lo:hi], rows.max(axis=-1), -np.inf)
            for at in np.argsort(-confidence, kind="stable")[:min(c["per_pass"], int(hidden_now[lo:hi].sum()))]:
                q = lo + int(at)
                ids[q], hidden_now[q] = int(rows[at].argmax()), False
                passes[q - n], logprobs[q - n] = s, float(rows[at].max())
                order = np.argsort(-rows[at], kind="stable")[:top_k]
                top[q - n] = [[int(t), float(rows[at][t])] for t in order]
    return {"ids": ids[n:end].tolist(), "passes": passes.tolist(), "logprobs": logprobs.tolist(),
            "top": top if top_k else None}
