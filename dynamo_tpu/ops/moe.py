"""Mixture-of-Experts layer ops.

One expert layer for every routed family (Mixtral/Qwen3-MoE, DeepSeek,
EXAONE-MoE), told which experts it holds:

    route     over ALL the model's experts (the router keeps its width)
    keep      the (token, choice) assignments whose expert is held here
    sort      them by expert, so each held expert's rows are contiguous
    experts   a grouped matrix product over the held experts' banks
    combine   each row weighted by its router probability (normalised over
              all the token's choices), summed back per token

No token is dropped at any skew, and there is no capacity and no capacity
factor: the ORDER (``tokens x k`` int32 indices) has a place for every
assignment.  The rows themselves are walked ``CHUNK_ROWS`` at a time by a
loop whose trip count is the rows HELD, read on the device: a trip gathers
its rows of ``x``, runs the three grouped products over them and gates.  So
the time of everything here but route and sort follows the assignments held,
and no array of ``tokens x k`` rows of the EXPERT width exists on any path.

Summing the rows back per token has two prices, one by the rows HELD (a
scatter-add, 0.09-0.17 us a row on the chip) and one by the rows ROUTED (a
gather, 0.03 us a row), and the layer takes the smaller by what it is told of
its holding, which is static (PERF.md section 6, PRs 41 and 56):

- a SHARE of the router's experts held (``k-exaone-236b-l8``: 16 of 128, so
  an eighth of the routed rows): each trip adds its rows, weighted, into the
  tokens' float32 sum, the loop's carry (``[tokens, hidden / 128, 128]``,
  201 MB at 8,192 tokens of 6,144); no array of ``tokens x k`` rows of the
  hidden width exists (it would be 805 MB for 101 MB of live rows);
- EVERY expert held (``moonlight-16b-l9``, ``xing4-29b-l8``, Mixtral,
  Qwen3-MoE, any ``ep`` mesh; held and routed are then the same count): each
  trip writes its down product's rows where they lie in sorted order, into
  ONE buffer ``[tokens x k, hidden]`` in ``x``'s dtype that the loop carries
  (201 MB at 8,192 tokens x 6 of 2,048; 235 MB at x 4 of 3,584), and after
  the loop ONE gather by each assignment's place sums a token's ``k`` rows,
  weighted, in float32.  No float32 sum rides the loop, nothing is
  scatter-added.

Expert parallelism: a chip that holds experts ``[first, first + E_held)`` of
``E`` computes its own experts' part of each token's sum; the parts of all
chips add up to the whole layer (the exchange that would add them is not
here: one chip runs its share alone).  Under a GSPMD ``ep`` mesh the banks
carry ``P(None, "ep", ...)`` and every expert is "held" by the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.quant import QuantizedMatrix

# counters one expert layer adds to (``MOE_STATS`` order), int32: what the
# routing did, which only the device knows.  The engine sums them over
# layers and steps (engine.stats() "moe_*").
MOE_STATS = (
    "assignments_routed",   # valid tokens x k
    "assignments_held",     # ... whose expert is held here
    "experts_touched",      # held experts with a row, once a chunk that visits them
    "expert_rows_max",      # rows of the busiest held expert (summed over layers)
    "expert_layers",        # expert layers run (the divisor of the two above)
    "rows_walked",          # chunks walked x their rows: what the products were given
    "rows_multiplied",      # row tiles the products' kernel visits x a tile's rows
    "rows_gathered",        # held rows the one gather after the walk summed (every expert held)
)

# rows a trip of ``moe_experts``' walk holds.  A step of fewer assignments
# (decode: 16 lanes x 8 = 128) is one chunk of its own size.
CHUNK_ROWS = 2048


def moe_router(
    x: jnp.ndarray, w_router: jnp.ndarray, top_k: int,
    norm_topk_prob: bool = True,
):
    """Returns (expert_ids [T, k], probs [T, k]) — softmax routing
    (DeepSeek-V2 / Mixtral style); ``norm_topk_prob=False`` keeps the raw
    softmax weights for the selected experts (some Qwen3-MoE variants)."""
    logits = (x.astype(jnp.float32)) @ w_router.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, top_ids = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_probs = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
    return top_ids.astype(jnp.int32), top_probs


def moe_router_sigmoid_noaux(
    x: jnp.ndarray,
    w_router: jnp.ndarray,
    bias: jnp.ndarray,        # [E] e_score_correction_bias
    top_k: int,
    *,
    n_group: int = 1,
    topk_group: int = 1,
    norm_topk_prob: bool = True,
):
    """DeepSeek-V3/R1 aux-free routing: sigmoid scores, the load-balancing
    bias affects SELECTION only, group-limited top-k (pick the best
    ``topk_group`` of ``n_group`` expert groups by the sum of each group's
    top-2 biased scores, then top-k experts within), combine weights from
    the UNBIASED scores renormalized over the chosen experts.
    (Reference semantics: HF modeling_deepseek noaux_tc / vLLM
    grouped_topk with scoring_func="sigmoid".)"""
    t = x.shape[0]
    e = w_router.shape[-1]
    logits = (x.astype(jnp.float32)) @ w_router.astype(jnp.float32)  # [T, E]
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)[None, :]

    if n_group > 1:
        grouped = biased.reshape(t, n_group, e // n_group)
        top2 = jax.lax.top_k(grouped, min(2, e // n_group))[0]
        group_scores = jnp.sum(top2, axis=-1)                    # [T, G]
        _, keep_groups = jax.lax.top_k(group_scores, topk_group)  # [T, g]
        group_mask = jnp.zeros((t, n_group), jnp.float32).at[
            jnp.arange(t)[:, None], keep_groups
        ].set(1.0)
        expert_mask = jnp.repeat(group_mask, e // n_group, axis=-1)
        biased = jnp.where(expert_mask > 0, biased, -jnp.inf)

    _, top_ids = jax.lax.top_k(biased, top_k)
    top_scores = jnp.take_along_axis(scores, top_ids, axis=-1)
    if norm_topk_prob:
        top_scores = top_scores / (
            jnp.sum(top_scores, axis=-1, keepdims=True) + 1e-20
        )
    return top_ids.astype(jnp.int32), top_scores


# The scoped VMEM a Mosaic kernel is granted on a v5e unless it asks for more
# (megablox does not) is 16 MiB; the blocks ``gmm_tiling`` chooses may take this
# much of it by ``gmm_block_bytes``' count, the rest is left to the kernel's own
# temporaries (the loaded operands, the masked store): the chip's compiler
# refused 16.75 MiB where the count read 15.5 (tests/ops/test_chip_compile.py).
GMM_BLOCK_BYTES = 14 * 2**20


def gmm_block_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What the megablox kernel holds in VMEM at a tiling: two buffers each of
    the ``tm x tk`` rows, the ``tk x tn`` bank block and the ``tm x tn``
    result, and the float32 accumulator."""
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def tile_rows(m: int) -> int:
    """Rows of a row tile of the grouped products over ``m`` rows: 128 for a
    decode step's rows, 256 for a prompt's chunk.  The chip kept both at every
    width and at 1 to 4,096 rows a group (PERF.md section 5, PR 53): 128 and
    512 lose at a chunk whatever the groups, 16 to 64 gain nothing at a
    decode step, whose time is the banks' bytes."""
    return 128 if m <= 1024 else 256


def _whole_tiles(width: int) -> list[int]:
    """The multiples of 128 that divide ``width``, widest first."""
    return [d for d in range(width, 0, -128) if width % d == 0] if width % 128 == 0 else []


def gmm_tiling(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of the megablox product ``[m, k] @ [groups, k, n]``,
    from the shapes alone.  In this order:

    - no masked work: ``tk`` divides ``k`` and ``tn`` divides ``n`` in whole
      128-lane tiles (the kernel multiplies a tile's every column, and masks
      the last ``k`` tile's operands elementwise in float32);
    - a bank block at least 256 columns wide for a prompt's chunk, whose time
      is the MXU's, and at least 1,024 (rows of 2 KB) for a decode step's
      rows, whose time is the banks' bytes: narrower blocks were 12% slower
      there at 6,144 x 2,048 (PERF.md section 6, PR 53);
    - ``k`` whole where the blocks fit ``GMM_BLOCK_BYTES``: the bank block's
      index then stays put over a group's consecutive row tiles and the
      pipeline fetches a group's bank once, not once a row tile; else the
      deepest ``tk`` of at most 1,024;
    - the widest ``tn`` that fits: each narrower one is another pass over the
      rows.

    A width no whole tile divides keeps the tiling every width had before."""
    tm = tile_rows(m)
    columns = min(n, 1024 if m <= 1024 else 256)
    depths = _whole_tiles(k)
    # k whole, else its deepest tile of at most 1,024
    for tk in depths[:1] + [d for d in depths[1:] if d <= 1024][:1]:
        for tn in _whole_tiles(n):
            if tn >= columns and gmm_block_bytes(tm, tk, tn, itemsize) <= GMM_BLOCK_BYTES:
                return tm, tk, tn
    return tm, min(k, 1024), min(n, 1024)


def grouped_matmul(
    lhs: jnp.ndarray,          # [M, K] rows sorted by group
    rhs,                       # [G, K, N] one matrix a group (may be quantized),
                               # or (stacked [L, G, K, N], layer): layer's G matrices
    group_sizes: jnp.ndarray,  # [G] int32 rows of each group, in order
    *,
    impl: str = "auto",        # "auto" | "pallas" | "pallas_interpret" | "xla"
) -> jnp.ndarray:
    """``lhs[rows of group g] @ rhs[g]`` for every group; rows past
    ``sum(group_sizes)`` come back as zeros.  On a TPU the product is the
    Pallas grouped matmul that ships with JAX (megablox ``gmm``: its grid
    covers the live row tiles only); elsewhere ``jax.lax.ragged_dot``.
    ``moe_experts`` calls it a chunk of its walk at a time, so ``M`` is a
    chunk's rows and the groups are the experts' rows inside the chunk.

    A layer's matrices may come as the whole stack and the layer's index: a
    kernel's operand is a buffer of its own, so a layer sliced out of the
    stack inside a layer loop would be COPIED for it each step (1.2 GB a
    layer at 16 experts of 6144 x 2048 x 3).  The kernel takes the stack
    flat, ``[L x G, K, N]``, and group sizes that are zero but for the
    layer's own groups, and reads the matrices where they lie."""
    stacked, layer = rhs if isinstance(rhs, tuple) else (None, None)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if stacked is not None and (impl == "xla" or isinstance(stacked, QuantizedMatrix)):
        rhs, stacked = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False), stacked
        ), None
    if isinstance(rhs, QuantizedMatrix):
        rhs = rhs.q.astype(lhs.dtype) * rhs.s.astype(lhs.dtype)
    m = lhs.shape[0]
    live = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    if impl == "xla":
        out = jax.lax.ragged_dot(
            lhs, rhs.astype(lhs.dtype), group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(lhs.dtype)
        return jnp.where(live, out, 0)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    group_sizes = group_sizes.astype(jnp.int32)
    if stacked is not None:
        groups = group_sizes.shape[0]
        rhs = stacked.reshape(-1, *stacked.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), jnp.int32), group_sizes, (layer * groups,)
        )
    k, n = rhs.shape[1:]
    tiling = gmm_tiling(m, k, n, lhs.dtype.itemsize)
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(
        lhs, rhs.astype(lhs.dtype), group_sizes,
        preferred_element_type=lhs.dtype,
        tiling=tiling,
        interpret=impl == "pallas_interpret",
    )[:m]
    # the kernel leaves the tiles it never visits unwritten
    return jnp.where(live, out, 0)


def moe_experts(
    x: jnp.ndarray,           # [T, H]
    expert_ids: jnp.ndarray,  # [T, k] ids among ALL experts
    probs: jnp.ndarray,       # [T, k] f32 combine weights
    w_gate,                   # [E_held, H, I], or (stacked [L, E_held, H, I], layer)
    w_up,                     # the same
    w_down,                   # [E_held, I, H], or its stack and the layer
    *,
    first_expert: int = 0,
    experts_routed: int | None = None,  # the router's width (``moe_ffn`` knows it)
    valid: jnp.ndarray | None = None,   # [T] bool: rows that are real tokens
    impl: str = "auto",
):
    """The held experts' part of every token's weighted sum, and the layer's
    ``MOE_STATS``.  Assignments to experts outside ``[first_expert,
    first_expert + E_held)`` and those of rows that are not ``valid`` (a
    bucket's padding, an empty lane) sort behind the live rows, where the
    walk does not go.

    How the walk's rows are summed back per token follows the holding, which
    is static.  A SHARE of the router's experts held (or a caller that does
    not say how wide the router is): each chunk's rows are scatter-added,
    weighted, into the tokens' float32 sum ``[T, H / 128, 128]``, the loop's
    carry; its cost follows the rows held, and no array of ``T x k`` rows
    exists.  EVERY expert held (``first_expert`` 0 and ``E_held ==
    experts_routed``): rows held and rows routed are the same count, so the
    loop carries the down products' rows in sorted order, ``[T x k padded to
    whole chunks, H]`` in ``x``'s dtype (a chunk written in place where it
    lies), and ONE gather by each assignment's place sums a token's ``k`` rows
    in float32 after the loop: a gathered row costs a third of a row
    scatter-added (28 ns against 86 at 2,048 wide, PERF.md section 5, PR 56)."""
    t, h = x.shape
    e = w_gate[0].shape[1] if isinstance(w_gate, tuple) else w_gate.shape[0]
    k = expert_ids.shape[1]
    gathers = first_expert == 0 and e == experts_routed
    local = expert_ids.astype(jnp.int32) - first_expert
    real = jnp.ones((t, 1), bool) if valid is None else valid[:, None]
    held = (local >= 0) & (local < e) & real
    group = jnp.where(held, local, e).reshape(-1)           # [T*k], e = not here
    order = jnp.argsort(group, stable=True)                 # live rows first, by expert
    # (a count by comparison: as a scatter-add of ones it was 0.57 ms at 65,536)
    group_sizes = jnp.sum(group[:, None] == jnp.arange(e), axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts, live_rows = ends - group_sizes, ends[-1]
    c = min(t * k, CHUNK_ROWS)
    chunks = (live_rows + c - 1) // c
    weight = jnp.where(held, probs, 0.0)
    # a token's sum is kept as whole (8, 128) float32 tiles, so that adding a
    # row into it rewrites 6 tiles and not one sublane of 48 (on the chip a
    # chunk of 2,048 rows of 6,144: 0.46 ms where [T, H] took 2.8)
    lanes = 128 if h % 128 == 0 else h
    if gathers:
        # where each assignment landed in the sorted order, by choice ([k, T]):
        # the permutation's inverse as a second sort (0.08 ms at 49,152 places
        # on the chip; as a scatter of an arange 0.28, PERF.md section 5, PR 56)
        place = jnp.argsort(order).reshape(t, k).T
        # zeros: an assignment that is not live points past the live rows,
        # into a chunk's zero rows or a chunk the walk never wrote
        carry = jnp.zeros((-(-(t * k) // c) * c, h), x.dtype)
    else:
        carry = jnp.zeros((t, h // lanes, lanes), jnp.float32)
    order = jnp.pad(order, (0, -(t * k) % c))               # the last chunk's slice is whole
    flat_weight = weight.reshape(-1)

    def chunk(i, carry):
        """Rows ``[lo, lo + c)`` of the sorted order: each expert's rows
        clipped to them are the chunk's groups."""
        lo = i * c
        at = jax.lax.dynamic_slice(order, (lo,), (c,))
        token = at // k
        sizes = jnp.clip(ends, lo, lo + c) - jnp.clip(starts, lo, lo + c)
        rows = x[token]                                             # [c, H]
        hidden = jax.nn.silu(
            grouped_matmul(rows, w_gate, sizes, impl=impl)
        ) * grouped_matmul(rows, w_up, sizes, impl=impl)
        out = grouped_matmul(hidden, w_down, sizes, impl=impl)      # zeros past the live rows
        if gathers:
            return jax.lax.dynamic_update_slice(carry, out, (lo, 0))
        scale = jnp.where(lo + jnp.arange(c) < live_rows, flat_weight[at], 0.0)
        tiles = out.reshape(c, -1, lanes).astype(jnp.float32)
        return carry.at[token].add(tiles * scale[:, None, None])

    # (a chunk is a function of its own in the lowered program: JAX stamps what
    # it lowers inline in a loop's body with the loop's own location, and the
    # trace finds the grouped products by the name theirs gives them)
    carry = jax.lax.fori_loop(0, chunks, jax.jit(chunk), carry)
    if gathers:
        combined = sum(
            carry[place[j]].astype(jnp.float32) * weight[:, j, None] for j in range(k))
    else:
        combined = carry.reshape(t, h)
    combined = combined.astype(x.dtype)
    # the row tiles the products' kernel visits, chunk by chunk ([chunks,
    # experts]): a tile is multiplied once for each expert with a row in it
    tm = tile_rows(c)
    lo = jnp.arange(-(-(t * k) // c))[:, None] * c
    first, last = jnp.clip(starts, lo, lo + c) - lo, jnp.clip(ends, lo, lo + c) - lo
    tiles_visited = jnp.sum(jnp.where(last > first, (last + tm - 1) // tm - first // tm, 0))
    stats = jnp.stack([
        jnp.sum(real) * k,
        live_rows,
        # an expert whose rows straddle chunks has its banks read in each
        jnp.sum(jnp.where(group_sizes > 0, (ends - 1) // c - starts // c + 1, 0)),
        jnp.max(group_sizes),
        jnp.int32(1),
        chunks * c,
        tiles_visited * tm,
        live_rows if gathers else 0,
    ]).astype(jnp.int32)
    return combined, stats


def moe_ffn(
    x: jnp.ndarray,
    w_router: jnp.ndarray,    # [H, E] over ALL experts
    w_gate,
    w_up,
    w_down,
    *,
    top_k: int,
    router_bias: jnp.ndarray | None = None,
    scoring: str = "softmax",     # "softmax" | "sigmoid_noaux"
    n_group: int = 1,
    topk_group: int = 1,
    norm_topk_prob: bool = True,
    first_expert: int = 0,
    valid: jnp.ndarray | None = None,
    impl: str = "auto",
    with_stats: bool = False,
):
    """Route over the router's width, compute the experts held
    (``w_gate.shape[0]`` of them, from ``first_expert``).  ``with_stats``
    also returns the layer's ``MOE_STATS`` vector."""
    e_all = w_router.shape[-1]
    if scoring == "sigmoid_noaux":
        ids, probs = moe_router_sigmoid_noaux(
            x, w_router,
            router_bias if router_bias is not None else jnp.zeros((e_all,), jnp.float32),
            top_k, n_group=n_group, topk_group=topk_group,
            norm_topk_prob=norm_topk_prob,
        )
    else:
        ids, probs = moe_router(x, w_router, top_k, norm_topk_prob=norm_topk_prob)
    out, stats = moe_experts(
        x, ids, probs, w_gate, w_up, w_down,
        first_expert=first_expert, experts_routed=e_all, valid=valid, impl=impl,
    )
    return (out, stats) if with_stats else out
