"""Utilization accounting: analytical FLOPs/bytes cost model + rolling MFU.

The north-star question — "how close to the hardware are we?" — needs a
denominator.  This module supplies it analytically from model geometry (no
profiling run required):

- :func:`model_cost` derives a :class:`ModelCost` (parameter count, weight
  bytes streamed per forward, linear FLOPs per token, attention FLOPs per
  attended context token, KV-cache bytes per token) from any registered
  family's config by duck-typing the common geometry fields.  MoE families
  count ACTIVE expert FLOPs but TOTAL expert bytes (decode streams only the
  routed experts, but capacity planning cares about resident weights);
  a latent-attention family (MLA: a config with ``kv_lora_rank``) is priced
  as its kernels work: absorbed products against the latent page, the
  page's stored bytes (``_latent_cost``).
- :class:`UtilizationTracker` turns the engine device loop's per-step facts
  (prefill/decode token counts, attended context tokens, weight streams,
  emitted tokens, step wall time) into rolling-window **MFU**
  (model FLOPs utilization), **MBU** (model bandwidth utilization),
  **goodput** (emitted tokens/s — tokens a client actually received, as
  opposed to computed-then-discarded work) plus cumulative totals.

Peak hardware numbers come from ``DYN_PEAK_TFLOPS`` / ``DYN_PEAK_GBPS`` when
set, else a nominal per-device-kind table (bf16 peak, HBM bandwidth), else a
conservative CPU fallback — the point of MFU is trend and cross-worker
comparison, not spec-sheet precision.

Everything here is exported through ``JaxLlmEngine.stats()`` →
``ForwardPassMetrics`` → ``dyn_worker_*`` gauges (components/metrics_service)
and consumed by the planner and ``scripts/dyn_top.py``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass

from dynamo_tpu.observability.step_metrics import StepRecord
from dynamo_tpu.utils import knobs

# THE peak table: (bf16 peak FLOP/s, HBM bytes/s) of one chip, keyed by the
# ``device_kind`` jax reports.  Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM).  A kind that is not here has no peak: device_peaks raises,
# and the always-on tracker reports its utilizations as unknown (None).
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def device_peaks(device_kind: str) -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) of ``device_kind``; LookupError when the
    table has no such device — a device metric never gets an assumed peak."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            "observability.perf.DEVICE_PEAKS with its source"
        ) from None


_DTYPE_BYTES = {
    "float8_e4m3fn": 1, "float8_e5m2": 1, "fp8": 1, "float8": 1,
    "int8": 1, "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
    "float32": 4, "f32": 4, "float64": 8,
}


def _dtype_bytes(dtype: object, default: int = 2) -> int:
    if dtype is None:
        return default
    if isinstance(dtype, str):
        return _DTYPE_BYTES.get(dtype, default)
    name = getattr(dtype, "__name__", None) or getattr(dtype, "name", None)
    if name is not None:
        return _DTYPE_BYTES.get(str(name), default)
    try:
        import numpy as np

        return int(np.dtype(dtype).itemsize)
    except Exception:  # noqa: BLE001
        return default


@dataclass(frozen=True)
class ModelCost:
    """Analytical per-token cost of one model geometry."""

    param_count: int                # resident weight parameters
    weight_bytes: int               # bytes to stream ALL weights once
    linear_flops_per_token: int     # matmul FLOPs per token (2·active params)
    attn_flops_per_ctx_token: int   # QK^T + AV FLOPs per attended ctx token
    kv_bytes_per_token: int         # KV cache bytes written per new token
    # residual streams (a config with ``hc_mult`` > 1; else 0): sublayers a
    # row passes, and the bytes a perfect mixing moves for a row of one
    mhc_sublayers: int = 0
    mhc_stream_bytes_per_row: int = 0
    # a latent family's unified step attends the keys of its own window
    # DECOMPRESSED (ops/pallas/mla_attention.py ragged_mla_attention_window;
    # else 0): the products of one (query, key) pair there, and the bytes the
    # launch moves for one row (its q, k, v and output once), every layer
    window_attn_flops_per_ctx_token: int = 0
    window_attn_bytes_per_row: int = 0
    # state-space and memory layers (a config with ``d_state``; else 0), what
    # a PERFECT implementation moves and computes, every such layer together:
    # a decode row reads and writes its lane's state and taps; a prompt row
    # reads ``a``, ``delta``, ``B``, ``C``, ``z`` and writes ``y`` (in the
    # model's dtype); a span's last row also writes the state and taps out
    ssm_layers: int = 0
    ssm_prompt_bytes_per_row: int = 0
    ssm_state_bytes_per_lane: int = 0    # a decode row reads it and writes it
    ssm_flops_per_row: int = 0
    # attention layers that read pages they never wrote (of ``full`` kind)
    cross_layers: int = 0

    def attn_flops(self, attn_ctx_tokens: int) -> float:
        """FLOPs of attention's own products over ``attn_ctx_tokens``
        attended context positions (QK^T and attention·V, every layer)."""
        return attn_ctx_tokens * self.attn_flops_per_ctx_token

    def flops(self, tokens: int, attn_ctx_tokens: int) -> float:
        """Total FLOPs to compute ``tokens`` new positions that together
        attended ``attn_ctx_tokens`` context positions."""
        return tokens * self.linear_flops_per_token + self.attn_flops(attn_ctx_tokens)

    def bytes_moved(
        self, tokens: int, attn_ctx_tokens: int, weight_streams: float
    ) -> float:
        """HBM bytes: weights streamed ``weight_streams`` times, KV written
        per new token, KV read per attended context token."""
        return (
            weight_streams * self.weight_bytes
            + tokens * self.kv_bytes_per_token
            + attn_ctx_tokens * self.kv_bytes_per_token
        )


def model_cost(
    model, *, quantize: str | None = None, kv_cache_dtype: object = None
) -> ModelCost:
    """Derive a :class:`ModelCost` from a family config by duck-typing the
    shared geometry fields (LlamaConfig and friends).  Never raises: absent
    fields fall back to conservative defaults, so an exotic family gets an
    approximation instead of no utilization signal."""
    if getattr(model, "d_state", 0):
        return _state_space_cost(model, quantize, kv_cache_dtype)
    h = int(getattr(model, "hidden_size", 0) or 1)
    layers = int(getattr(model, "num_layers", 0) or 1)
    heads = int(getattr(model, "num_heads", 0) or 1)
    head_dim = int(getattr(model, "head_dim", 0) or max(h // heads, 1))
    kv_heads = int(getattr(model, "num_kv_heads", 0) or heads)
    inter = int(getattr(model, "intermediate_size", 0) or 4 * h)
    vocab = int(getattr(model, "vocab_size", 0) or 1)
    tied = bool(getattr(model, "tie_word_embeddings", False))

    attn_params = h * heads * head_dim + 2 * h * kv_heads * head_dim + heads * head_dim * h

    num_experts = int(getattr(model, "num_experts", 0) or 0)
    if num_experts > 1:
        expert_inter = int(
            getattr(model, "expert_intermediate_size", 0)
            or getattr(model, "moe_intermediate_size", 0)
            or inter
        )
        active_experts = int(
            getattr(model, "experts_per_token", 0)
            or getattr(model, "num_experts_per_tok", 0)
            or 2
        )
        mlp_params_total = num_experts * 3 * h * expert_inter + h * num_experts
        mlp_params_active = active_experts * 3 * h * expert_inter + h * num_experts
    else:
        mlp_params_total = mlp_params_active = 3 * h * inter

    attn_flops_per_ctx_token = 4 * layers * heads * head_dim
    kv_values_per_token = 2 * layers * kv_heads * head_dim
    window = (0, 0)     # (products a pair, bytes a row) of a latent window launch
    if getattr(model, "kv_lora_rank", 0):
        latent = _latent_cost(model)
        attn_params = latent["attn_params"]
        attn_flops_per_ctx_token = layers * latent["attn_flops_per_ctx_token"]
        kv_values_per_token = layers * latent["page_row"]
        mlp_params_total, mlp_params_active = latent["mlp_params"]
        window = (layers * latent["window_flops_per_ctx_token"],
                  layers * latent["window_values_per_row"] * _dtype_bytes(getattr(model, "dtype", None)))
    streams = _stream_cost(model)

    embed = vocab * h
    head_params = 0 if tied else vocab * h
    param_count = embed + head_params + layers * (
        attn_params + mlp_params_total + streams["params"])
    # active matmul params per token: embedding lookup is a gather (no
    # matmul), the unembedding projection always runs
    active_params = vocab * h + layers * (
        attn_params + mlp_params_active + streams["matmul_params"])

    weight_dtype_bytes = _dtype_bytes(getattr(model, "dtype", None))
    if quantize == "int8":
        weight_dtype_bytes = 1

    kv_dtype_bytes = _dtype_bytes(kv_cache_dtype, default=weight_dtype_bytes)

    return ModelCost(
        param_count=param_count,
        # (the mixing leaves are float32 whatever the model's dtype)
        weight_bytes=(param_count - layers * streams["params"]) * weight_dtype_bytes
        + layers * streams["params"] * 4,
        linear_flops_per_token=2 * active_params,
        # per attended context position per layer: 2·heads·head_dim for
        # QK^T plus the same for attention·V
        attn_flops_per_ctx_token=attn_flops_per_ctx_token,
        kv_bytes_per_token=kv_values_per_token * kv_dtype_bytes,
        mhc_sublayers=streams["sublayers"],
        mhc_stream_bytes_per_row=streams["bytes_per_row"],
        window_attn_flops_per_ctx_token=window[0],
        window_attn_bytes_per_row=window[1],
    )


def _state_space_cost(model, quantize, kv_cache_dtype) -> ModelCost:
    """The cost of ``models.phi4flash.Phi4FlashConfig``: state-space layers,
    window and full differential attention, memory units and cross layers.

    The attention numbers are over the layers that WALK pages (window, full
    and cross), as the kernels execute them (``num_heads`` queries against
    ``head_dim``-wide cache heads: the paired form, zeros included), so the
    engine's per-kind split (``_attn_layers``) prices each by its own
    context; ``kv_bytes_per_token`` is therefore what those layers READ for a
    token of context, of which only the window layers and the one full layer
    hold pages of their own."""
    from dynamo_tpu.models.phi4flash import param_counts

    act = _dtype_bytes(getattr(model, "dtype", None))
    dtype_bytes = 1 if quantize == "int8" else act
    kv_bytes = _dtype_bytes(kv_cache_dtype, default=act)
    counts = param_counts(model)
    walking = model.window_layers + model.full_layers
    di, n, taps, s = model.d_inner, model.d_state, model.d_conv, model.ssm_layers
    state = 4 * n * di + act * (taps - 1) * di       # one layer's, one lane
    return ModelCost(
        param_count=counts["params"],
        weight_bytes=counts["matrix"] * dtype_bytes + counts["float32"] * 4,
        linear_flops_per_token=2 * counts["matrix"],
        attn_flops_per_ctx_token=4 * walking * model.num_heads * model.head_dim,
        kv_bytes_per_token=2 * walking * model.num_kv_heads * model.head_dim * kv_bytes,
        ssm_layers=s,
        ssm_prompt_bytes_per_row=s * act * (4 * di + 2 * n),
        ssm_state_bytes_per_lane=s * state,
        # the recurrence (decay's product and exp, the update's two products
        # and sum, the read-out's product and sum: 7 a state element) and the
        # convolution; the projections are linear work
        ssm_flops_per_row=s * (7 * n * di + 2 * taps * di),
        cross_layers=model.cross_layers,
    )


def _stream_cost(model) -> dict:
    """The residual streams of a config with ``hc_mult`` > 1
    (ops/hyper_connections.py), all zeros otherwise: one layer's mixing
    parameters (two sublayers' ``phi``, ``alpha``, ``bias``) and those a
    token multiplies against (``phi``), the sublayers a row passes (two a
    layer), and the bytes a perfect implementation moves for one row of one
    sublayer: the streams read and written and the sublayer's input and
    output, in the model's dtype (``(2 n + 2) x hidden``: 71,680 B at four
    streams of 3,584 in bfloat16)."""
    n = int(getattr(model, "hc_mult", 1) or 1)
    if n == 1:
        return {"params": 0, "matmul_params": 0, "sublayers": 0, "bytes_per_row": 0}
    from dynamo_tpu.ops.hyper_connections import coefficient_count, stream_bytes_per_row

    outs = coefficient_count(n)
    phi = n * model.hidden_size * outs
    return {
        "params": 2 * (phi + outs + 3),
        "matmul_params": 2 * phi,
        "sublayers": 2 * model.num_layers,
        "bytes_per_row": stream_bytes_per_row(
            n, model.hidden_size, _dtype_bytes(getattr(model, "dtype", None))),
    }


def _latent_cost(model) -> dict:
    """A latent-attention (MLA) family's own numbers, one layer's, from the
    fields of ``models.deepseek.DeepseekConfig``: what the kernels of
    ops/pallas/mla_attention.py do for a (query, key) pair, every head
    against the ONE latent row (absorbed: ``2 x heads x (latent + rope)`` for
    the scores, ``2 x heads x latent`` for the context; the zeros the rope
    part is stored with are not counted as work), the values a cached token
    takes as STORED (``rope_page_width``: what a page copy moves), the
    attention's matrices, and the MLP's parameters averaged over the leading
    dense layers and the sparse ones (held, and met by one token: its routed
    experts, the shared ones, the router)."""
    from dynamo_tpu.models.deepseek import rope_page_width

    h, heads, layers = model.hidden_size, model.num_heads, model.num_layers
    r, rope = model.kv_lora_rank, model.qk_rope_head_dim
    nope, v_dim = model.qk_nope_head_dim, model.v_head_dim
    q_out = heads * (nope + rope)
    q_params = (
        h * model.q_lora_rank + model.q_lora_rank * q_out if model.q_lora_rank else h * q_out
    )
    expert = 3 * h * model.moe_intermediate_size
    dense = 3 * h * model.intermediate_size
    always = h * model.num_experts + model.n_shared_experts * expert
    sparse_layers = layers - model.first_k_dense
    mean = lambda routed: (  # noqa: E731
        model.first_k_dense * dense + sparse_layers * (always + routed * expert)
    ) // layers
    return {
        "attn_params": q_params + h * (r + rope)
        + r * heads * (model.qk_nope_head_dim + model.v_head_dim)
        + heads * model.v_head_dim * h,
        "attn_flops_per_ctx_token": 2 * heads * (r + rope) + 2 * heads * r,
        # a key of the row's own window, decompressed (unpadded widths: the
        # floor is the model's): every head's own 192-wide key and 128-wide
        # value; and what that launch reads and writes for a row: the heads'
        # queries, keys less the one rotated part, values and outputs
        "window_flops_per_ctx_token": 2 * heads * (nope + rope) + 2 * heads * v_dim,
        "window_values_per_row": heads * (2 * nope + rope + 2 * v_dim) + rope,
        "page_row": r + rope_page_width(model),
        "mlp_params": (mean(model.num_experts), mean(model.experts_per_token)),
    }


def detect_peaks() -> tuple[float, float] | None:
    """(peak FLOP/s, peak bytes/s) for this host: DYN_PEAK_TFLOPS /
    DYN_PEAK_GBPS override the table entry of jax's device_kind.  None when
    the device is not in the table and the operator named no peaks."""
    env_tflops = knobs.get("DYN_PEAK_TFLOPS")
    env_gbps = knobs.get("DYN_PEAK_GBPS")
    if env_tflops and env_gbps:
        return env_tflops * 1e12, env_gbps * 1e9
    import jax

    known = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    if known is None:
        return None
    flops, gbps = known
    if env_tflops:
        flops = env_tflops * 1e12
    if env_gbps:
        gbps = env_gbps * 1e9
    return flops, gbps


@dataclass
class _Sample:
    t: float
    duration_s: float
    flops: float
    bytes_moved: float
    emitted_tokens: int
    prefill_tokens: int
    decode_tokens: int


class UtilizationTracker:
    """Rolling MFU / MBU / goodput over the engine's step stream.

    Called once per scheduler iteration from the device thread; the asyncio
    stats reader calls :meth:`rates`/:meth:`stats` concurrently, so sample
    mutation and iteration share a lock (uncontended in the common case —
    one writer, ~1Hz readers).  ``window_s`` (``DYN_UTIL_WINDOW_S``) bounds
    both staleness and memory."""

    def __init__(
        self,
        cost: ModelCost,
        *,
        peak_flops: float | None = None,
        peak_bytes_per_s: float | None = None,
        window_s: float | None = None,
    ):
        self.cost = cost
        if peak_flops is None or peak_bytes_per_s is None:
            detected_f, detected_b = detect_peaks() or (None, None)
            peak_flops = peak_flops if peak_flops is not None else detected_f
            peak_bytes_per_s = (
                peak_bytes_per_s if peak_bytes_per_s is not None else detected_b
            )
        # None = a device with no published peak: mfu/bandwidth utilization
        # are then reported as None (unknown), never against a guess
        self.peak_flops = peak_flops and max(float(peak_flops), 1.0)
        self.peak_bytes_per_s = peak_bytes_per_s and max(
            float(peak_bytes_per_s), 1.0
        )
        if window_s is None:
            window_s = knobs.get("DYN_UTIL_WINDOW_S")
        self.window_s = max(window_s, 0.1)
        self._samples: deque[_Sample] = deque()
        self._lock = threading.Lock()
        # cumulative totals (monotone; exported as *_total mirrors)
        self.prefill_tokens_total = 0
        self.decode_tokens_total = 0
        self.emitted_tokens_total = 0
        self.flops_total = 0.0
        self.mhc_rows_total = 0

    def observe(self, rec: StepRecord, now: float | None = None) -> None:
        """Book one engine step; the step's own FLOPs are written back onto
        ``rec`` for whoever reads the record next (the flight ring's
        per-step ``mfu``)."""
        tokens = rec.prefill_tokens + rec.decode_tokens
        flops = self.cost.flops(tokens, rec.attn_ctx_tokens) if tokens else 0.0
        moved = (
            self.cost.bytes_moved(tokens, rec.attn_ctx_tokens, rec.weight_streams)
            if (tokens or rec.weight_streams)
            else 0.0
        )
        rec.flops = flops
        t = time.monotonic() if now is None else now
        with self._lock:
            self.prefill_tokens_total += rec.prefill_tokens
            self.decode_tokens_total += rec.decode_tokens
            self.emitted_tokens_total += rec.emitted_tokens
            self.flops_total += flops
            self.mhc_rows_total += tokens * self.cost.mhc_sublayers
            self._samples.append(
                _Sample(
                    t=t, duration_s=rec.duration_s, flops=flops, bytes_moved=moved,
                    emitted_tokens=rec.emitted_tokens,
                    prefill_tokens=rec.prefill_tokens,
                    decode_tokens=rec.decode_tokens,
                )
            )
            self._prune(t)

    def step_mfu(self, rec: StepRecord) -> float | None:
        """MFU of ONE observed step from its own FLOPs and duration (None
        when the device has no published peak)."""
        if not self.peak_flops:
            return None
        if rec.duration_s <= 0.0:
            return 0.0
        return min(rec.flops / rec.duration_s / self.peak_flops, 1.0)

    def _prune(self, now: float) -> None:
        horizon = now - self.window_s
        samples = self._samples
        while samples and samples[0].t < horizon:
            samples.popleft()

    def rates(self, now: float | None = None) -> dict:
        """Windowed rates.  The denominator is wall time spanned by the
        window (not summed step time): idle gaps correctly drag MFU down —
        an engine that computes brilliantly 10% of the time is 10% utilized."""
        t = time.monotonic() if now is None else now
        with self._lock:
            self._prune(t)
            samples = list(self._samples)
        unknown_f, unknown_b = not self.peak_flops, not self.peak_bytes_per_s
        if not samples:
            return {
                "mfu_perc": None if unknown_f else 0.0,
                "bandwidth_util_perc": None if unknown_b else 0.0,
                "goodput_tokens_per_second": 0.0,
                "prefill_tokens_per_second": 0.0,
                "tokens_per_second": 0.0,
            }
        span = max(t - samples[0].t, sum(s.duration_s for s in samples), 1e-6)
        flops = sum(s.flops for s in samples)
        moved = sum(s.bytes_moved for s in samples)
        emitted = sum(s.emitted_tokens for s in samples)
        computed = sum(s.prefill_tokens + s.decode_tokens for s in samples)
        return {
            "mfu_perc": (
                None if unknown_f else min(flops / span / self.peak_flops, 1.0)
            ),
            "bandwidth_util_perc": (
                None if unknown_b
                else min(moved / span / self.peak_bytes_per_s, 1.0)
            ),
            "goodput_tokens_per_second": emitted / span,
            "prefill_tokens_per_second": sum(
                s.prefill_tokens for s in samples
            ) / span,
            "tokens_per_second": computed / span,
        }

    def stats(self) -> dict:
        """Merged into ``JaxLlmEngine.stats()`` — names are wire-stable
        (ForwardPassMetrics and the Prometheus exporter key off them)."""
        out = self.rates()
        out.update(
            prefill_tokens_total=self.prefill_tokens_total,
            decode_tokens_total=self.decode_tokens_total,
            tokens_emitted_total=self.emitted_tokens_total,
            model_flops_total=self.flops_total,
        )
        if self.cost.mhc_sublayers:
            # a model with residual streams: live rows x the sublayers each
            # passed, and what a perfect mixing would have moved for them
            out.update(
                mhc_rows_total=self.mhc_rows_total,
                mhc_stream_bytes_total=self.mhc_rows_total * self.cost.mhc_stream_bytes_per_row,
            )
        return out
