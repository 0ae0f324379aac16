"""JaxLlmEngine — the native TPU inference engine.

Architecture:
- a dedicated **device thread** runs the synchronous scheduler/step loop
  (prefill + batched decode through jitted SPMD functions), keeping the
  asyncio event loop free for network I/O;
- requests enter via the standard streaming-engine interface
  (``generate(Context[dict]) -> ResponseStream[dict]`` speaking
  PreprocessedRequest / Annotated[LLMEngineOutput] wire dicts), so the engine
  drops into the same pipelines as any remote engine;
- static shapes throughout: prompt lengths round up to buckets (one compiled
  prefill per bucket), decode runs a fixed ``max_batch_size`` lane array;
- KV cache is donated through every step and carried through the layer
  loop as flat pages (models/llama.py ``_scan_layers``): a step program
  writes the donated buffer in place and returns it, its temporaries are
  activations only (``stats()["program_temp_bytes_max"]``);
- the allocator publishes stored/removed block events and load metrics for
  the KV-aware router.
"""

from __future__ import annotations

import asyncio
import os
import queue as thread_queue
import threading
import time
import uuid
import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import AsyncIterator, Callable

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.kv_manager import (
    BlockAllocator,
    KvEvent,
    WindowPool,
    compute_block_hashes,
)
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.engine.sequence import Sequence, SeqStatus
from dynamo_tpu.llm.protocols.common import (
    Annotated,
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.models.llama import KvPools, LlamaConfig
from dynamo_tpu.models.registry import get_family
from dynamo_tpu.observability import FlightRecorder, StepTelemetry, get_recorder
from dynamo_tpu.observability.perf import UtilizationTracker, model_cost
from dynamo_tpu.observability.step_metrics import (
    KIND_DECODE,
    KIND_PROMPT,
    LoopAccount,
    StepRecord,
)
from dynamo_tpu.robustness.faults import ENGINE_STEP, FAULTS
from dynamo_tpu.ops.sampling import (
    apply_logit_bias,
    apply_penalties,
    sample_tokens,
    token_logprobs,
    topk_logprobs,
)
from dynamo_tpu.parallel.mesh import MeshConfig, device_summary, make_mesh
from dynamo_tpu.runtime.engine import Context, ResponseStream
from dynamo_tpu.utils.logging import get_logger
from dynamo_tpu.utils.tasks import spawn_logged
from dynamo_tpu.utils import knobs
from dynamo_tpu.utils.compile_cache import compile_counts, ensure_compile_cache

logger = get_logger("engine")

# Host phases of one served window, in order; every branch of the step loop
# books its time under these names (stats()["phase_ms"], "dyn.<phase>" in a
# profiler trace).
STEP_PHASES = ("schedule", "pack", "upload", "dispatch", "readback", "post")
# The named parts of the phases that are large (stats()["phase_ms"][<phase>]
# ["parts"], "dyn.<phase>.<part>" in a profiler trace).  What a phase spends
# outside its parts is its own bookkeeping.  `emit` runs once a token and is
# two clock reads around the call, never an annotation.
PHASE_PARTS = {
    # the scheduler's decision and the route to a step program; the lanes'
    # slot growth; the window's host arrays; the block-table rows
    "schedule": ("admit", "slots", "build", "tables"),
    "pack": ("spans", "window_spans"),
    # the lanes' sampling parameters (compared, uploaded when they changed);
    # the window's arrays handed to the device
    "upload": ("sampling", "arrays"),
    # blocks behind a window, the expert counters, deferred finishes; the
    # stop, guided and length rules a token; the output built and handed to
    # the event loop a token; completed blocks published
    "post": ("release", "tokens", "emit", "publish"),
}

# Attention-kernel work counters (cumulative, in stats() from engine start).
KERNEL_WORK_KEYS = (
    "ragged_live_pages_total",      # pages the ragged kernel copied (pack_spans' span counts)
    "ragged_page_slots_total",      # page places of the KV steps it executed: steps x pages a step
    "ragged_kv_steps_total",        # KV steps it executed (pack_spans' kv_steps)
    "ragged_token_blocks_total",    # token blocks launched with at least one KV step
    "ragged_live_rows_total",       # live tokens (each all its heads' query rows) in those blocks
    "ragged_attn_flops_total",      # QK^T + attention·V over the attended context, all layers
    "ragged_kv_read_bytes_total",   # pages copied x page bytes, all layers
    # a latent family's unified steps (a decode step books neither): the
    # positions their rows attended, and those of them that were rows of the
    # same window, taken decompressed (the rest: absorbed, by the page walk)
    "mla_attended_ctx_total",
    "mla_window_ctx_total",
    "decode_attn_flops_total",
    "decode_kv_read_bytes_total",
    # window layers (a model with a window pool; 0 otherwise): pages their
    # launches visit, and pages the same launches would visit were the
    # layers full (ragged spans and decode walks alike, one layer's count)
    "window_pages_visited_total",
    "window_pages_full_total",
    # the part of the two ``*_kv_read_bytes_total`` that layers read from
    # pages they never wrote (a cross-decoder's; 0 otherwise)
    "decode_cross_kv_read_bytes_total",
    "ragged_cross_kv_read_bytes_total",
    # state-space and memory layers (0 for a model without them): live rows
    # through them (each row passes every such layer), and what a perfect
    # implementation moves and computes for those rows (perf.ModelCost)
    "ssm_rows_total",
    "ssm_state_bytes_total",
    "ssm_flops_total",
    "gmu_rows_total",
)

# cache leaves that are not pages: the expert layers' counters, and a
# state-space family's recurrent state and taps a LANE (models/phi4flash.py)
_NOT_PAGES = ("moe_stats", "ssm", "conv")


def _pages(cache: dict) -> dict:
    """The cache's page leaves (``[layers, blocks, ...]``: what block ids
    index, what extract / inject / offload move), without what else rides
    the same pytree (``_NOT_PAGES``)."""
    return {name: leaf for name, leaf in cache.items() if name not in _NOT_PAGES}


# The expert layers' counters (a routed model; absent otherwise): what the
# step programs add up on the device (ops/moe.py MOE_STATS), taken by the
# device thread between steps and summed here, then priced in stats().
MOE_STAT_KEYS = (
    "moe_assignments_routed_total",
    "moe_assignments_held_total",
    "moe_experts_touched_total",
    "moe_expert_rows_max_total",
    "moe_expert_layers_total",
    "moe_rows_walked_total",
    "moe_rows_multiplied_total",
    "moe_rows_gathered_total",
)


def _named(fn, name: str):
    """``fn`` under the function name a profiler trace (``jit_<name>``) and
    the compile log will show."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _ProgramsByBucket:
    """``fn`` jitted once per token bucket, each copy under the function
    name ``<prefix><bucket>``: a profiler trace (and the compile log) then
    names a program by what it is and its shape, ``jit_dyn_unified_t256``,
    not ``jit_step``.  Same programs as one shape-polymorphic jit would
    compile; only the names differ."""

    def __init__(self, fn, prefix: str, token_arg: int, **jit_kwargs):
        self.__wrapped__ = fn
        self._prefix = prefix
        self._token_arg = token_arg
        self._jit_kwargs = jit_kwargs
        self._programs: dict[int, Callable] = {}

    def _program(self, args):
        bucket = args[self._token_arg].shape[0]
        program = self._programs.get(bucket)
        if program is None:
            fn = self.__wrapped__
            program = self._programs[bucket] = jax.jit(
                _named(lambda *a: fn(*a), f"{self._prefix}{bucket}"),
                **self._jit_kwargs,
            )
        return program

    def __call__(self, *args):
        return self._program(args)(*args)

    def lower(self, *args):
        return self._program(args).lower(*args)


def _round_chunk_tokens(chunk_tokens: int, block_size: int) -> int:
    """Chunk windows round UP to whole blocks (one definition: the sp
    validation and the serving bucket must agree on the number)."""
    return max(1, (chunk_tokens + block_size - 1) // block_size) * block_size


def _token_buckets(configured: tuple[int, ...], max_len: int) -> list[int]:
    """The step programs' token buckets: the configured ones clipped to the
    context, then, above the last of them (B), half-octave steps 1.5 B, 2 B,
    3 B, 4 B, 6 B, ... while below the context, and the context itself.  So a
    window above B pads by at most a third of its rows whatever the context
    (with the context alone above B, a 5,000-token prompt runs 8,192 rows at
    a context of 8,192 and 262,144 at 262,144), for two programs a bucket."""
    buckets = {min(b, max_len) for b in configured}
    octave = max(buckets)
    while 0 < octave < max_len:
        buckets |= {octave * 3 // 2, octave * 2}
        octave *= 2
    return sorted(b for b in buckets if b < max_len) + [max_len]


@dataclass
class EngineConfig:
    model: LlamaConfig                 # any registered family's config
    model_family: str = "llama"        # registry key (llama/qwen2/mixtral)
    num_blocks: int = 256
    block_size: int = 16
    max_batch_size: int = 8
    max_model_len: int | None = None
    prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048, 4096)
    mesh: MeshConfig | None = None
    seed: int = 0
    # KV cache storage dtype: None = model dtype; a jnp dtype, or a string
    # ("fp8" → float8_e4m3fn, "bf16", "f32").  fp8 halves KV bytes — the
    # cache is upcast at every use (attention ops and kernels read through
    # .astype) — doubling the context a chip holds and the decode batch it
    # can run (vLLM's --kv-cache-dtype fp8 equivalent).
    kv_cache_dtype: object = None
    # "auto": Pallas paged-attention kernel on single-chip TPU, gather-based
    # XLA fallback otherwise.  "jax" | "pallas" | "pallas_interpret" force.
    attention_impl: str = "auto"
    # Prefix-cache reuse: completed KV blocks stay resident and matching
    # prompts prefill only the uncached tail (auto-disabled for families
    # without a continued-prefill forward).
    enable_prefix_caching: bool = True
    # Chunked prefill: prompts longer than this prefill in chunks of this
    # many tokens, interleaved with decode steps (None = whole-prompt
    # prefill; rounded up to a block multiple; needs a continued-prefill
    # forward).  Keeps ITL bounded under long-ISL load — the reference
    # relies on engine chunked prefill + disagg offload (SURVEY.md §5).
    prefill_chunk_tokens: int | None = None
    # G2 host-DRAM tier: registered blocks evicted from HBM offload here and
    # restore on a later prefix hit instead of recomputing (0 = off).
    # Reference: block manager G1→G2 offload, lib/llm/src/block_manager/
    # offload.rs:77-80.
    host_offload_blocks: int = 0
    # G3 SSD tier: host-LRU evictions cascade to an np.memmap disk pool and
    # restore from there (0 = off; needs host_offload_blocks > 0).
    disk_offload_blocks: int = 0
    disk_offload_path: str | None = None
    # G4 remote tier: "host:port" of a BlockStoreServer
    # (llm/block_manager/remote.py) — bottom-tier evictions cascade there
    # over DCN and prefix hits restore from it (None = off; needs
    # host_offload_blocks > 0).  Reference: the remote tier of the block
    # manager, lib/llm/src/block_manager.rs:68-81.
    remote_store_addr: str | None = None
    # Predictive prefetch over the offload tiers (prefetch/): hinted
    # prefixes page disk→host→HBM between engine steps, bounded by an HBM
    # headroom reservation so prefetch can never preempt running work, and
    # hot prefixes pin host-resident.  None = DYN_PREFETCH env (default
    # on); only effective when an offload tier is mounted.  DYN_PREFETCH=0
    # restores fully demand-driven paging.
    prefetch: bool | None = None
    # Compile-time K for per-token top-k alternatives (OpenAI
    # top_logprobs caps at 20).  K>0 adds one lax.top_k over [lanes, vocab]
    # to every step (the host transfer of the rows is skipped unless a
    # sequence asked); K=0 removes the compute entirely (top_logprobs
    # requests then get empty alternative rows).
    top_logprobs_k: int = 20
    # Decode iterations fused into one jit launch (lax.scan with device-side
    # token feedback + slot derivation).  >1 amortizes per-step dispatch and
    # host↔device roundtrips — the dominant cost at small batch — at the
    # price of emitting tokens in bursts of this size and wasting up to
    # decode_steps-1 iterations on sequences that hit a stop mid-window.
    decode_steps: int = 1
    # Weight-only quantization ("int8" | None).  The TPU analog of the
    # reference's FP8 headline model (examples/llm/benchmarks/README.md:66):
    # named projection matrices become int8 + per-channel scale
    # (ops/quant.py), halving the HBM bytes every decode step streams.
    # Requires a family with quant_leaves (all registered families).
    quantize: str | None = None
    # Compile-time width of the per-lane OpenAI logit_bias rows (sparse
    # {token: bias} scattered onto the logits each step).  Requests with
    # more entries keep the largest-magnitude ones; 0 disables the scatter.
    logit_bias_k: int = 64
    # Speculative decoding ("ngram" = prompt-lookup self-drafting: the last
    # spec_ngram tokens are matched against the sequence's history and the
    # continuation proposed).  One verify pass scores spec_tokens+1
    # positions per weight stream from HBM — decode is bandwidth-bound, so
    # accepted drafts are nearly free tokens.  Verification is exact: a
    # lane emits beyond one token only while drafts match what plain
    # greedy decode would have produced (sampled/penalized lanes fall back
    # to one token per step).  Composes with decode_steps > 1 (iterations
    # without enough drafts run the fused multi-step program — measured in
    # docs/SPEC_VS_FUSED.json); incompatible with pp.
    speculative: str | None = None
    spec_tokens: int = 4
    spec_ngram: int = 2
    # Overlapped decode pipeline: dispatch the next decode window with
    # ON-DEVICE token feedback (step N+1's input tokens are step N's output
    # array, never a host round-trip) and retire the previous window's
    # results by asynchronous readback while the new one runs — the device
    # never idles waiting on the host half of the loop (double buffering,
    # in-flight depth 1).  The pipeline synchronizes wherever host state
    # genuinely gates the device: batch-composition changes (prefill
    # admission, finishes), preemption, aborts, speculative verify; guided
    # and top_logprobs lanes fall back to the synchronous path per window
    # (their per-token host processing cannot lag the device).  None =
    # DYN_DECODE_OVERLAP env (default on; "0" disables).
    decode_overlap: bool | None = None
    # Ragged unified-batch step: one jitted launch consumes a MIXED token
    # batch — chunked-prefill spans and decode tokens from different
    # sequences, flattened onto one ragged token axis through the ragged
    # paged-attention kernel (ops/pallas/ragged_attention.py, arxiv
    # 2604.15464).  Prefill admission stops being a separate dispatch, so
    # the overlap pipeline no longer drains when a new sequence joins: its
    # first chunk simply rides the next window.  None = DYN_UNIFIED_BATCH
    # env (default ON; "0" disables).  The split prefill/decode path remains
    # compiled
    # and serves as fallback — speculative/guided/multimodal/disagg-prefill
    # lanes keep their current routes, and engines whose geometry the
    # unified step cannot serve (fused decode_steps>1, multi-chip meshes,
    # narrowed KV dtypes, families without a unified forward) auto-disable.
    unified_batch: bool | None = None
    # Minimum fraction of running lanes that must have a draft for the
    # w-wide verify program to run; below it, plain decode serves the step.
    # Cost model (decode is weight-bandwidth-bound): one verify launch
    # streams the weights ONCE (plus the w-wide logits/sampling tax) while
    # a fused plain launch streams them decode_steps times — so a
    # non-drafting lane advances ~1 token per weight stream under EITHER
    # program, and choosing verify costs that lane only the w-wide
    # logits/sampling overhead and per-launch dispatch, not a decode_steps×
    # slowdown.  The fraction gate bounds exactly that overhead: one
    # self-drafting chat request must not tax a whole mixed batch.
    spec_min_fraction: float = 0.25

    def resolved_max_len(self) -> int:
        hard = self.num_blocks * self.block_size
        soft = self.max_model_len or self.model.max_position_embeddings
        return min(soft, self.model.max_position_embeddings, hard)


_KV_DTYPE_NAMES = {
    "fp8": "float8_e4m3fn",
    "float8": "float8_e4m3fn",
    "float8_e4m3fn": "float8_e4m3fn",
    "float8_e5m2": "float8_e5m2",
    "bf16": "bfloat16",
    "bfloat16": "bfloat16",
    "f32": "float32",
    "float32": "float32",
    "f16": "float16",
    "float16": "float16",
}


@dataclass
class _InflightWindow:
    """One dispatched-but-unretired decode window (the overlap pipeline's
    in-flight slot).  Everything device-side stays a jax.Array until
    ``_retire_window`` reads it back; ``feedback`` is the final-step token
    array that seeds the NEXT window's input without a host round-trip."""
    tokens: object            # [steps, lanes] (or [lanes] when steps == 1)
    lps: object
    feedback: object          # [lanes] last sampled token per lane
    active: list              # sequences RUNNING at dispatch, lane order
    lane_ids: list            # their lanes (composition fingerprint)
    steps: int
    # "prompt" when the window carried a prompt token, else "decode": the
    # iteration that waits for this window books its time under this kind
    kind: str = KIND_DECODE
    # a lane of it samples, so its program sorts the vocabulary
    # (``ops/sampling.py``); booked like ``kind``
    samples: bool = False
    # open ``engine.prefill`` spans (``_open_prefill_span``) of the prefill
    # windows this window's readback covers: closed when it retires
    prefills: list = field(default_factory=list)
    # sequences whose finish was detected while THIS window was in flight:
    # emitted already, but their lane/blocks are only released when this
    # window retires (a lagged device step may still write into them)
    deferred: list = field(default_factory=list)


def resolve_kv_cache_dtype(spec):
    """None | jnp dtype | string name → dtype usable for cache init."""
    if spec is None or not isinstance(spec, str):
        return spec
    name = _KV_DTYPE_NAMES.get(spec.lower())
    if name is None:
        raise ValueError(
            f"unknown kv_cache_dtype {spec!r} (want one of {sorted(set(_KV_DTYPE_NAMES))})"
        )
    return jnp.dtype(name)


class JaxLlmEngine:
    def __init__(
        self,
        config: EngineConfig,
        params: dict | None = None,
        *,
        event_sink: Callable[[KvEvent], None] | None = None,
    ):
        self.config = config
        cfg = config.model
        ensure_compile_cache()
        self._device_info = device_summary()
        self.family = get_family(config.model_family)
        self.max_len = config.resolved_max_len()
        self.max_blocks_per_seq = (self.max_len + config.block_size - 1) // config.block_size
        self.buckets = _token_buckets(config.prefill_buckets, self.max_len)

        self.mesh = None
        if config.mesh is not None and (
            config.mesh.total() > 1 or config.mesh.device_offset
        ):
            # a 1-device mesh with a device_offset still matters: it pins
            # this engine to a specific device partition (disagg with one
            # chip per role) instead of silently landing on device 0
            self.mesh = make_mesh(config.mesh)
            # static-shape constraints: fail at init, not at first jit
            # trace mid-serving
            if config.mesh.dp > 1:
                # data parallelism in this architecture is worker
                # REPLICATION behind the (KV-aware) router, like the
                # reference — the engine's jits never shard their batch
                # over dp, so a dp axis on an engine mesh would silently
                # replicate compute on every dp shard.  The dp axis exists
                # for model-level callers only (pipeline_layer_stack, the
                # dryrun).
                raise ValueError(
                    f"dp={config.mesh.dp} is not an engine mesh axis: "
                    "scale decode throughput by replicating workers behind "
                    "the router (components/router_service.py), not by "
                    "adding dp to one engine's mesh"
                )
            pp = config.mesh.pp
            if pp > 1:
                # pp composes with the AUTOMATIC GSPMD axes (partial-manual
                # shard_map: pp is the manual stage axis; tp — and ep for
                # MoE families with a pipelined decode — stay automatic
                # inside each stage, parallel/pipeline.py).  sp is
                # prefill-only and has no pipelined variant; dp is never an
                # engine axis (rejected above).
                ep_ok = (
                    config.mesh.ep == 1
                    or (
                        self.family.forward_decode_pp is not None
                        and getattr(cfg, "num_experts", 0) > 1
                    )
                )
                if config.mesh.sp > 1 or not ep_ok:
                    # name only the axes actually at fault (a valid ep on a
                    # MoE family must not appear in the complaint)
                    offending = {}
                    if not ep_ok:
                        offending["ep"] = config.mesh.ep
                    if config.mesh.sp > 1:
                        offending["sp"] = config.mesh.sp
                    raise ValueError(
                        f"pp={pp} composes with tp (all families) and ep "
                        f"(MoE families with a pipelined decode); got "
                        f"{offending} for family {config.model_family!r}"
                    )
                if config.max_batch_size % pp:
                    raise ValueError(
                        f"max_batch_size={config.max_batch_size} must be divisible "
                        f"by the pp axis ({pp}): pipeline microbatches split the "
                        "decode batch evenly"
                    )
                if cfg.num_layers % pp:
                    raise ValueError(
                        f"num_layers={cfg.num_layers} must be divisible by the "
                        f"pp axis ({pp}): layers split evenly into stages"
                    )
            sp = config.mesh.sp
            if sp > 1 and getattr(cfg, "sliding_window", None):
                raise ValueError(
                    "sliding-window attention is incompatible with an sp "
                    "mesh: the ring path has no window mask yet"
                )
            if sp > 1 and not self.family.prefix_prefill_accepts_sp:
                # this family's continued-prefill jit (chunked prefill,
                # prefix hits) runs dense attention only: those modes must
                # not silently bypass the sequence parallelism the mesh
                # was configured for.  (llama-family composes: its prefix
                # forward rings the tail and merges the resident prefix.)
                if config.prefill_chunk_tokens is not None:
                    raise ValueError(
                        "prefill_chunk_tokens is incompatible with an sp "
                        f"mesh for family {config.model_family!r}: its "
                        "continued-prefill path has no ring attention"
                    )
                if config.enable_prefix_caching:
                    logger.warning(
                        "sp mesh: disabling prefix caching (family %r's "
                        "continued-prefill path does not run ring attention)",
                        config.model_family,
                    )
                    config = self.config = dataclasses.replace(
                        config, enable_prefix_caching=False
                    )
            if sp > 1:
                # every sp mesh (chunked or not) rings over padded bucket
                # lengths — fail at construction, not at first jit trace
                bad = [b for b in self.buckets if b % sp]
                if bad:
                    raise ValueError(
                        f"prefill buckets {bad} not divisible by the sp axis "
                        f"({sp}): ring attention shards the sequence evenly"
                    )
                if config.prefill_chunk_tokens is not None:
                    rounded = _round_chunk_tokens(
                        config.prefill_chunk_tokens, config.block_size
                    )
                    if rounded % sp:
                        raise ValueError(
                            f"prefill_chunk_tokens (block-rounded to {rounded}) "
                            f"must be divisible by the sp axis ({sp}): chunk "
                            "windows ring-shard the sequence evenly"
                        )

        if self.mesh is not None and hasattr(cfg, "grouped_matmul"):
            # the Pallas grouped matmul is one chip's; under a mesh the
            # expert layer runs XLA's ragged dot, which GSPMD partitions
            cfg = dataclasses.replace(cfg, grouped_matmul="xla")
            config = self.config = dataclasses.replace(config, model=cfg)
        if self.family.lane_state and self.mesh is not None:
            raise ValueError(
                f"family {config.model_family!r} keeps a recurrent state a "
                "lane and is built for one chip: no mesh is served for it"
            )
        # a model with window layers: their second pool (models/registry.py)
        self._window_blocks = 0
        if self.family.window_pool_blocks is not None:
            if config.decode_steps > 1:
                raise ValueError(
                    "fused multi-step decode derives its slots from ONE block "
                    f"table; family {config.model_family!r} has a window pool"
                )
            self._window_blocks = int(self.family.window_pool_blocks(
                cfg, config.max_batch_size, self.max_len, config.block_size
            ))
            if config.enable_prefix_caching:
                config = self.config = dataclasses.replace(
                    config, enable_prefix_caching=False
                )
            logger.info(
                "family %r: prefix caching off (a window layer's prefix is "
                "gone once a sequence has passed it); window pool %d blocks "
                "x %d window layers beside %d blocks x %d full layers",
                config.model_family, self._window_blocks, cfg.window_layers,
                config.num_blocks, cfg.full_layers,
            )

        if config.attention_impl == "auto":
            mesh_ok = self.mesh is None or (
                self.family.decode_accepts_tp_mesh
                and all(
                    getattr(config.mesh, a) == 1 for a in ("ep", "sp", "pp")
                )
                # shard_map needs even head sharding; the GSPMD gather path
                # handles uneven tp fine, so fall back there
                and getattr(cfg, "num_kv_heads", 0) % config.mesh.tp == 0
                and getattr(cfg, "num_heads", 0) % config.mesh.tp == 0
            )
            # on a TPU the Pallas kernels ARE the attention path; a backend
            # that cannot initialise raises here, at construction
            self.attention_impl = (
                "pallas" if jax.default_backend() == "tpu" and mesh_ok
                else "jax"
            )
        else:
            self.attention_impl = config.attention_impl

        # All eager init work (param RNG, quantization, cache zeros, rope
        # tables) runs on the host CPU backend, then moves to the accelerator
        # with one device_put per leaf: eager on-device init would hold the
        # unquantized tree in HBM.  No CPU backend (JAX_PLATFORMS=tpu) is an
        # error — say JAX_PLATFORMS=tpu,cpu.
        host_ctx = jax.default_device(jax.local_devices(backend="cpu")[0])
        with host_ctx:
            rng = jax.random.PRNGKey(config.seed)
            raw_params = params if params is not None else self.family.init_params(cfg, rng)
            raw_params = self._maybe_quantize(raw_params)
            # sharding specs follow the params tree's CONTENT (a caller may
            # hand in a pre-quantized artifact without setting
            # config.quantize — the spec twin must still match)
            from dynamo_tpu.ops.quant import is_quantized

            self._params_quantized = is_quantized(raw_params)
            raw_cache = self.family.cache_init(
                cfg, config.num_blocks, config.block_size,
                resolve_kv_cache_dtype(config.kv_cache_dtype),
                **({"window_blocks": self._window_blocks} if self._window_blocks else {}),
                **({"lanes": config.max_batch_size} if self.family.lane_state else {}),
            )
            cos, sin = self.family.rope_tables(cfg)
            # families build tables out to max_position_embeddings (131k for
            # llama3); the engine only ever indexes positions < max_len.
            # Slice before upload — with the full table, every compiled
            # program would carry tens of MB of trig constants.
            cos, sin = cos[: self.max_len], sin[: self.max_len]
            lanes = config.max_batch_size
            gen_counts = jnp.zeros((lanes, cfg.vocab_size), jnp.int32)
            prompt_counts = jnp.zeros((lanes, cfg.vocab_size), jnp.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            param_specs = self.family.param_specs(cfg)
            if self._params_quantized:
                from dynamo_tpu.ops.quant import quantize_specs

                param_specs = quantize_specs(param_specs, self.family.quant_leaves)
            self._param_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), param_specs
            )
            self._cache_sharding = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.family.cache_specs(cfg)
            )
            self.params = jax.tree.map(jax.device_put, raw_params, self._param_shardings)
            self.cache = jax.tree.map(jax.device_put, raw_cache, self._cache_sharding)
            repl = NamedSharding(self.mesh, PartitionSpec())
            self.cos = jax.device_put(cos, repl)
            self.sin = jax.device_put(sin, repl)
        else:
            self._param_shardings = None
            self._cache_sharding = None
            self.params = jax.tree.map(self._to_default_device, raw_params)
            self.cache = jax.tree.map(self._to_default_device, raw_cache)
            self.cos = self._to_default_device(cos)
            self.sin = self._to_default_device(sin)

        # guided decoding: disabled until enable_guided_json() installs a
        # compiled mask table.  The dummy one-row all-true table keeps the
        # jit signatures stable so enabling guidance never recompiles the
        # unguided programs' SHAPES for lanes that stay unguided (it does
        # change the table aval — enable before warmup).
        self.guided_masks = None
        self._guided_strings: list[str] | None = None
        self._guided_eos: list[int] = []
        self._guided_requests = 0     # guided sequences admitted
        self._guided_completions = 0  # finished with a COMPLETE document
        vocab = cfg.vocab_size
        self._guided_table = jnp.ones((1, vocab), jnp.bool_)
        self._guided_true_row = jnp.ones((vocab,), jnp.bool_)
        if self.mesh is not None:
            self._guided_table = jax.device_put(self._guided_table, repl)
            self._guided_true_row = jax.device_put(self._guided_true_row, repl)

        # per-lane sampling state: generated-token counts (presence/frequency
        # penalties), prompt-token counts (repetition penalty scope), and
        # per-lane PRNG keys (OpenAI `seed` reproducibility).  Lane keys are
        # produced host-side (no device RNG in the request path).
        self._host_rng = np.random.Generator(np.random.PCG64(config.seed))
        self._lane_keys = np.zeros((lanes, 2), np.uint32)

        # Host-phase accounting, always on (observability/step_metrics.py
        # LoopAccount): wall and thread-CPU seconds a phase of the step
        # loop and the parts of the large ones, how long the chip stood
        # empty while the loop had work, and the loop's time with none;
        # surfaced via stats()["phase_ms"] and mirrored as jax.profiler
        # TraceAnnotations ("dyn.<phase>", "dyn.<phase>.<part>") so a device
        # trace shows them on the profiler's clock.  One set of names for
        # every branch that serves a window; `readback` is the host blocked
        # on the device.  Two clock reads, at most one is_ready() and one
        # TraceMe a boundary.  The newest result dispatched, which the
        # account asks is_ready(), is `_gen_counts`: every step program
        # returns it.
        self.loop_account = LoopAccount(
            STEP_PHASES, PHASE_PARTS,
            observe=lambda s: get_recorder().observe(
                "engine.starved", s, component="engine"),
            newest=lambda: self._gen_counts,
            annotate=jax.profiler.TraceAnnotation,
        )
        # `_phase(name, **attrs)` closes the open host phase and opens
        # `name` (None: just close), `_part(name)` the same for a part of
        # the open phase that runs once a step: the account's own methods,
        # called at every boundary of the loop
        self._phase = self.loop_account.phase
        self._part = self.loop_account.part
        self._emit_row = self.loop_account.part_row("post", "emit")
        self._publish_row = self.loop_account.part_row("post", "publish")
        self._sliding_window = getattr(cfg, "sliding_window", None)
        # expert-layer counters: device leaves taken out of the cache, not
        # yet read (device thread only), and their sum (stats() reads it)
        self._moe_pending: list = []
        self._moe_totals = [0] * len(MOE_STAT_KEYS)
        self._moe_zero = None
        self._moe_taken_at = 0.0
        # Step telemetry: batch occupancy / queue depth / KV pool usage per
        # scheduler iteration, merged into stats() → load-metrics publisher
        # → dyn_worker_* Prometheus gauges (observability.step_metrics).
        self.step_telemetry = StepTelemetry(config.max_batch_size)
        # Utilization accounting (observability/perf.py): the device loop
        # feeds per-step token/context/weight-stream facts; stats() exports
        # rolling MFU / bandwidth-utilization / goodput plus token totals.
        self.utilization = UtilizationTracker(
            model_cost(
                cfg, quantize=config.quantize, kv_cache_dtype=config.kv_cache_dtype
            )
        )
        # Perf flight recorder (observability/flight.py): bounded ring of
        # per-step telemetry + discrete events, dumped to JSONL on demand
        # (dynctl flight dump) or automatically on burn breach / crash /
        # drain.  DYN_FLIGHT=0 makes every hook below a no-op.
        self.flight = FlightRecorder(source="engine")
        self._flight_preemptions = 0    # last preemption total seen, for deltas
        self._tokens_emitted = 0        # tokens that reached a caller's stream
        self._step_prefill_tokens = 0   # per-iteration scratch, reset each step
        self._step_decode_tokens = 0
        self._step_attn_ctx = 0         # sum of attended context positions
        self._step_weight_streams = 0.0 # full weight passes dispatched
        self._step_lane_steps = 0       # decode lanes x device steps dispatched
        # kind of the window this iteration waited for / dispatched (the
        # iteration's time is booked to the first that is set)
        self._step_waited_kind: str | None = None
        self._step_dispatched_kind: str | None = None
        # the same of whether a lane of that window samples; `_packed_samples`
        # is of the lanes packed since a window last took it (_take_unwaited)
        self._step_waited_samples = False
        self._step_dispatched_samples = False
        self._packed_samples = False
        # open engine.prefill spans of prompt work dispatched that nothing
        # waits for; the next window dispatched inherits them (_take_unwaited)
        self._unwaited_prefills: list = []
        # Attention-kernel work, counted where the page spans are packed
        # (cumulative; stats() carries every key from engine start)
        self._kernel_work = dict.fromkeys(KERNEL_WORK_KEYS, 0)
        # DYN_PROFILER_TRACE_DIR: set when start() opened a device trace
        self._profiler_trace_dir: str | None = None
        # Sampling-tail upload cache: the per-window device copies of the
        # (lane_keys, temp, top_k, ...) arrays are reused while their host
        # values are unchanged — at steady-state decode the batch
        # composition changes rarely.  Equality-checked
        # against fresh host arrays every window (cheap), so there is no
        # invalidation bookkeeping to miss.
        self._tail_cache: tuple | None = None
        # Overlapped decode pipeline (see EngineConfig.decode_overlap): the
        # single in-flight window plus counters for stats()/A-B profiling.
        env_overlap = knobs.get("DYN_DECODE_OVERLAP")  # tri-state bool
        if config.decode_overlap is not None:
            self.decode_overlap = bool(config.decode_overlap)
        elif env_overlap is not None:
            self.decode_overlap = env_overlap
        else:
            self.decode_overlap = True
        if self.decode_overlap and config.speculative:
            # drafts are proposed from HOST token history; with windows in
            # flight that history lags the device by a window, so drafts
            # would be mispositioned and verify acceptance would collapse —
            # while every drafting iteration also paid a pipeline drain.
            # The verify program already fuses its own multi-token window;
            # run speculative engines synchronous.
            logger.info("decode overlap disabled: speculative decoding "
                        "drafts from host token history")
            self.decode_overlap = False
        self._inflight: _InflightWindow | None = None
        self._overlap_windows = 0   # windows dispatched with token feedback
        self._sync_windows = 0      # windows served by the synchronous path
        self._decode_steps_total = 0
        # Ragged unified-batch step (EngineConfig.unified_batch): mixed
        # prefill+decode in one launch.  Auto-disables loudly when the
        # engine's geometry cannot serve it — the split path is always the
        # fallback, never a silent behavior change.
        env_unified = knobs.get("DYN_UNIFIED_BATCH")  # tri-state bool
        if config.unified_batch is not None:
            unified = bool(config.unified_batch)
        elif env_unified is not None:
            unified = env_unified
        else:
            # default ON: every registered family with a unified forward
            # serves mixed windows; the auto-disable matrix below downgrades
            # unsupported configs to the split step loudly, never silently
            unified = True
        # unified-batch fallback bookkeeping: reason-slug → count, surfaced
        # in stats() as dyn_worker_unified_fallbacks_total{reason}; each
        # reason logs once per engine (_unified_skip) — the per-step route
        # checks fire every iteration and must not spam
        self._unified_fallbacks: dict[str, int] = {}
        self._unified_fallback_logged: set[str] = set()
        # aot_precompile's job name → that program's temporary bytes
        self.program_temp_bytes: dict[tuple, int] = {}
        if unified:
            reason = slug = None
            if self.family.forward_unified is None:
                reason = f"family {config.model_family!r} has no unified forward"
                slug = "no_family_forward"
            elif config.speculative:
                reason = "speculative lanes keep their verify route"
                slug = "speculative"
            elif config.decode_steps > 1:
                reason = "fused multi-step decode windows cannot carry chunks"
                slug = "multi_step_decode"
            elif self.mesh is not None:
                reason = "multi-chip meshes keep the split step"
                slug = "mesh"
            else:
                resolved = resolve_kv_cache_dtype(config.kv_cache_dtype)
                if resolved is not None and jnp.dtype(resolved) != jnp.dtype(
                    cfg.dtype
                ) and not jnp.issubdtype(jnp.dtype(resolved), jnp.floating):
                    # float narrowings (fp8/bf16/f16) flow through unified:
                    # every ragged kernel and XLA twin upcasts cache reads
                    # to f32 and write_decode_kv casts on write.  The
                    # parity contract with the split path is tolerance-
                    # level there (split prefill attends full-precision
                    # activations, unified reads its freshly-written
                    # quantized cache) — tests/engine/test_quantized_unified
                    # pins it.  Non-float cache dtypes have no kernel read
                    # path: keep them on the split step, reason-slugged.
                    reason = (
                        f"kv_cache_dtype {config.kv_cache_dtype!r} has no "
                        "unified kernel read path"
                    )
                    slug = "unsupported_kv_dtype"
            if reason is not None:
                self._unified_skip(slug, reason)
                unified = False
        self.unified_batch = unified
        self._unified_windows = 0     # mixed windows served by one dispatch
        # how well the token buckets fit the prompt windows dispatched: the
        # tokens a window carried (its spans + the decode lanes packed beside
        # them) and the rows of the bucket it ran
        self._prompt_window_live_tokens = 0
        self._prompt_window_bucket_tokens = 0
        self._admission_drains = 0    # pipeline drains forced by admission
        # query rows of one token in one of the ragged kernel's products: the
        # heads that share a KV head (every head, over a latent cache).  A
        # bucket's flat token axis is cut into blocks of bucket_tb_tokens
        # (_tb_for), so every bucket packs whole blocks and there is ONE
        # program per token bucket whatever the batch composition.
        from dynamo_tpu.ops.pallas.ragged_attention import default_tb_tokens

        heads = int(getattr(cfg, "num_heads", 0) or 1)
        self._rows_per_token = heads if getattr(cfg, "kv_lora_rank", 0) else max(
            1, heads // int(getattr(cfg, "num_kv_heads", 0) or heads)
        )
        self._unified_tb = default_tb_tokens(
            self._rows_per_token, config.block_size
        )
        self._fb_zero = None          # resident all-zero feedback tokens
        self._seed_none = None        # resident no-op seed scatter args
        # Per-lane block-table host rows, rewritten only for lanes whose
        # block list changed since the last window; the device copy is
        # reused untouched while every row is clean.  At steady-state
        # decode a lane's table changes once per block_size tokens, so the
        # (lanes × max_blocks_per_seq) rebuild+upload the old loop paid
        # every step (decode.upload in the profile) collapses to nothing.
        lanes_n = config.max_batch_size
        self._bt_host = np.zeros((lanes_n, self.max_blocks_per_seq), np.int32)
        self._bt_lane_key: list = [None] * lanes_n
        self._bt_dev = None
        # the window pool's tables, kept the same way (a model without the
        # pool never touches them)
        self._wbt_host = np.zeros_like(self._bt_host)
        self._wbt_lane_key: list = [None] * lanes_n
        self._wbt_dev = None
        # overlap windows carry no guided lanes (they fall back to sync):
        # one resident all-unguided mode row, uploaded once
        self._gmodes_unguided = None
        if self.mesh is not None:
            self._gen_counts = jax.device_put(gen_counts, repl)
            self._prompt_counts = jax.device_put(prompt_counts, repl)
        else:
            self._gen_counts = self._to_default_device(gen_counts)
            self._prompt_counts = self._to_default_device(prompt_counts)

        self.prefix_caching = (
            config.enable_prefix_caching
            and self.family.forward_prefill_with_prefix is not None
        )
        self.chunk_tokens = None
        if (
            config.prefill_chunk_tokens is not None
            and self.family.forward_prefill_with_prefix is not None
        ):
            self.chunk_tokens = _round_chunk_tokens(
                config.prefill_chunk_tokens, config.block_size
            )
            # chunks run as their own compile bucket (otherwise every chunk
            # pads up to the next full-prompt bucket)
            if self.chunk_tokens < self.max_len:
                self.buckets = sorted(set(self.buckets) | {self.chunk_tokens})
                if self.unified_batch:
                    # the steady-state MIXED window is a full chunk plus one
                    # decode token per lane: give it its own bucket too, or
                    # every unified window pads up to the next prompt bucket.
                    # Decode lanes PACK into shared kernel token blocks on
                    # both attention paths, so each costs exactly one slot.
                    mixed = -(-(
                        self.chunk_tokens + self.config.max_batch_size
                    ) // 8) * 8
                    if mixed < self.max_len:
                        self.buckets = sorted(set(self.buckets) | {mixed})
        self.host_tier = None
        self._host_evictions: list[int] | None = None
        offload_sink = None
        if config.host_offload_blocks and self.prefix_caching:
            from dynamo_tpu.engine.offload import HostOffloadTier

            leaves = _pages(self.cache)
            self.host_tier = HostOffloadTier(
                config.host_offload_blocks,
                {k: (v.shape[0], *v.shape[2:]) for k, v in leaves.items()},
                {k: np.dtype(v.dtype) for k, v in leaves.items()},
                disk_blocks=config.disk_offload_blocks,
                disk_path=config.disk_offload_path,
                remote_addr=config.remote_store_addr,
            )
            offload_sink = self._offload_blocks
            # a hash that left EVERY tier (fell off the bottom of the
            # G2→G3→G4 cascade) while no longer device-resident: routers
            # must forget it
            self.host_tier.evict_observer = self._host_evicted
        elif (
            config.host_offload_blocks
            or config.disk_offload_blocks
            or config.remote_store_addr
        ):
            # a silently-ignored tier config is worse than a loud one: the
            # operator believes offload is on while nothing mounts
            raise ValueError(
                "KV offload tiers configured but unusable: "
                + (
                    "disk/remote tiers need host_offload_blocks > 0"
                    if not config.host_offload_blocks
                    else "this model family/config has no prefix caching"
                )
            )
        self.allocator = BlockAllocator(
            config.num_blocks, config.block_size, event_sink=self._sink_event,
            enable_prefix_caching=self.prefix_caching,
            offload_sink=offload_sink, host_tier=self.host_tier,
            window_pool=WindowPool(
                self._window_blocks, config.block_size, cfg.window
            ) if self._window_blocks else None,
        )
        # predictive prefetch: pager + HBM headroom reservation (only with
        # an offload tier mounted — with nothing below HBM there is nothing
        # to page in ahead of time)
        self.prefetch_pager = None
        self._prefetch_headroom_blocks = 0
        if self.host_tier is not None:
            from dynamo_tpu.prefetch.hints import prefetch_enabled
            from dynamo_tpu.prefetch.pager import PrefetchPager

            enabled = (
                config.prefetch if config.prefetch is not None
                else prefetch_enabled()
            )
            if enabled:
                from dynamo_tpu.observability import TraceContext

                self.prefetch_pager = PrefetchPager(
                    ttl_s=knobs.get("DYN_PREFETCH_TTL"),
                    blocks_per_step=knobs.get("DYN_PREFETCH_BLOCKS"),
                )
                self._prefetch_trace = TraceContext.new_root()
                self.allocator.prefetch_tracker = self.prefetch_pager
                headroom_frac = knobs.get("DYN_PREFETCH_HEADROOM")
                self._prefetch_headroom_blocks = max(
                    self.allocator.watermark_blocks,
                    int(config.num_blocks * headroom_frac),
                )
            # nothing drains pin candidates without the pager, and
            # DYN_PREFETCH=0 must be bookkeeping-free demand paging
            self.host_tier.pin_enabled = self.prefetch_pager is not None
        self.scheduler = Scheduler(
            self.allocator, max_batch_size=config.max_batch_size,
            prefill_chunk_tokens=self.chunk_tokens,
            bucket_cost=self._bucket_len,
            unified_batch=self.unified_batch,
        )
        self.scheduler.on_preempt = self._on_preempt
        self._event_sink = event_sink
        self._iterations = 0

        # thread plumbing
        self._submit_q: thread_queue.Queue = thread_queue.Queue()
        self._wake = threading.Event()
        self._stop = False
        self._thread: threading.Thread | None = None
        self._jit_prefill = self._build_prefill()
        self._jit_prefill_prefix = (
            self._build_prefill_prefix()
            if (self.prefix_caching or self.chunk_tokens is not None)
            else None
        )
        self._jit_prefill_mm = (
            self._build_prefill_mm()
            if self.family.forward_prefill_embeds is not None
            else None
        )
        self._jit_decode = self._build_decode()
        # unified window seed capacity: only NEWLY-ADMITTED prefills need
        # their penalty-count rows (re)seeded, and admission is bounded by
        # the scheduler's per-step cap
        self._unified_seed_slots = max(1, self.scheduler.max_prefills_per_step)
        self._jit_unified = self._build_unified() if self.unified_batch else None
        self.spec_enabled = bool(config.speculative)
        if self.spec_enabled:
            if config.speculative != "ngram":
                raise ValueError(
                    f"unknown speculative mode {config.speculative!r} (want 'ngram')"
                )
            if self.family.forward_verify is None:
                raise ValueError(
                    f"model family {config.model_family!r} has no verification "
                    "forward (speculative decoding unsupported)"
                )
            # decode_steps > 1 COMPOSES with speculation: iterations where
            # enough lanes drafted run the verify program (its window
            # already fuses up to spec_tokens+1 tokens per launch); the
            # rest — sampled/penalized lanes, draft misses — run the fused
            # multi-step decode program instead of single-token launches.
            # Measured on both regimes: scripts/spec_vs_fused.py →
            # docs/SPEC_VS_FUSED.json.
            if config.mesh is not None and config.mesh.pp > 1:
                raise ValueError("speculative decoding does not support pp meshes")
            if config.spec_tokens < 1:
                raise ValueError("spec_tokens must be >= 1")
            if config.spec_ngram < 1:
                raise ValueError("spec_ngram must be >= 1")
        self._jit_verify = self._build_verify() if self.spec_enabled else None
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._jit_extract = self._build_extract()
        # block-table compile buckets (id-array lengths for extract/inject/
        # restore/prefix paths — no full-size pad buffers)
        self._table_buckets = sorted(
            {self.allocator.blocks_needed(b) for b in self.buckets}
            | {self.max_blocks_per_seq}
        )
        self._jit_inject = self._build_inject()
        set_row_kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            set_row_kwargs["out_shardings"] = NamedSharding(self.mesh, PartitionSpec())
        self._jit_set_row = jax.jit(
            lambda counts, lane, row: counts.at[lane].set(row),
            donate_argnums=(0,), **set_row_kwargs,
        )

    @property
    def _attn_layers(self) -> tuple[int, int, int | None]:
        """Layers by attention kind, for the kernels' work counters: (full
        layers, window layers, the window layers' window).  A llama-like
        model's layers are all of its one kind."""
        cfg = self.config.model
        if self._window_blocks:
            return cfg.full_layers, cfg.window_layers, cfg.window
        layers = int(getattr(cfg, "num_layers", 0) or 1)
        if self._sliding_window is None:
            return layers, 0, None
        return 0, layers, self._sliding_window

    @staticmethod
    def _to_default_device(x):
        """Host-built array → the default device, UNCOMMITTED.  Through a
        host ndarray on purpose: ``device_put`` of a CPU-backend jax.Array
        without a target leaves it on the CPU (every step then uploads the
        model again — 7.6 s per step on the chip), and with a target it
        COMMITS the array, which marks the argument in every lowered module
        (``sdy.sharding``): resident arrays would then give each program as
        many compiled variants as there are committed/uncommitted argument
        patterns, none of them the AOT twin."""
        return jax.device_put(np.asarray(x))

    def _maybe_quantize(self, raw_params: dict) -> dict:
        """Apply EngineConfig.quantize to a (host-resident) param tree.
        Pre-quantized trees (e.g. loaded from a quantized artifact) pass
        through untouched."""
        if not self.config.quantize:
            return raw_params
        if self.config.quantize != "int8":
            raise ValueError(
                f"unknown quantize mode {self.config.quantize!r} (want 'int8')"
            )
        if not self.family.quant_leaves:
            raise ValueError(
                f"model family {self.config.model_family!r} does not support "
                "weight-only quantization (no quant_leaves)"
            )
        from dynamo_tpu.ops.quant import is_quantized, quantize_params

        if is_quantized(raw_params):
            return raw_params
        return quantize_params(raw_params, self.family.quant_leaves)

    def _tb_for(self, bucket: int) -> int:
        """Token block of one unified bucket's program and packing."""
        from dynamo_tpu.ops.pallas.ragged_attention import bucket_tb_tokens

        return bucket_tb_tokens(
            self._rows_per_token, self.config.block_size, bucket
        )

    # -- guided decoding ---------------------------------------------------
    def enable_guided_json(self, tokenizer) -> None:
        """Install the compiled JSON admissible-token table for guided
        requests (``output_format="json"``).  Call before warmup so the
        table's aval is part of the AOT-compiled programs.

        Vocab-size note: model vocabs are often padded past the tokenizer
        vocab; padding columns are masked False (a padded id is never a
        valid JSON continuation)."""
        from dynamo_tpu.llm.guided import build_for_tokenizer

        masks, strings = build_for_tokenizer(tokenizer)
        self.set_guided(masks, strings, tokenizer.eos_token_ids)

    def set_guided(self, masks, strings: list[str], eos_ids: list[int]) -> None:
        """Lower-level install (tests / pre-built tables)."""
        vocab = self.config.model.vocab_size
        table = np.zeros((masks.mask.shape[0], vocab), bool)
        table[:, : masks.mask.shape[1]] = masks.mask[:, :vocab]
        self.guided_masks = masks
        self._guided_strings = strings
        self._guided_eos = list(eos_ids)
        table_j = jnp.asarray(table)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            table_j = jax.device_put(
                table_j, NamedSharding(self.mesh, PartitionSpec())
            )
        self._guided_table = table_j

    def _guided_row(self, seq) -> jnp.ndarray:
        """The prefill-time mask row for one sequence (all-true when the
        sequence is unguided or its cursor bailed out)."""
        if seq.guided is None or seq.guided.mode_id < 0:
            return self._guided_true_row
        return self._guided_table[seq.guided.mode_id]

    # -- jitted steps ------------------------------------------------------
    def _build_prefill(self):
        cfg = self.config.model
        vocab = cfg.vocab_size
        topk_k = self.config.top_logprobs_k

        # sequence parallelism: prefill attention rides the ring kernel when
        # the mesh has an sp axis and the family supports it
        prefill_kwargs = {}
        if (
            self.mesh is not None
            and self.mesh.shape.get("sp", 1) > 1
            and self.family.supports_sp
        ):
            prefill_kwargs["sp_mesh"] = self.mesh
        lane_state = self.family.lane_state

        # cos/sin ride as arguments, not closure constants: a closed-over
        # concrete array is baked into the HLO as a constant (observed:
        # 350MB of trig tables inside one compiled prefill program)
        def step(params, cache, gen_counts, prompt_counts, lane, token_ids,
                 block_ids, seq_len, start_pos, gen_row, key, temp, top_k, top_p,
                 greedy, pres, freq, rep, bias_ids, bias_vals, grow, cos, sin):
            logits, cache = self.family.forward_prefill(
                params, cfg, token_ids, cache, block_ids, seq_len, start_pos,
                cos, sin, **prefill_kwargs, **({"lane": lane} if lane_state else {}),
            )
            # (re)seed this lane's sampling state.  ``gen_row`` is the count
            # of already-generated tokens (nonzero only on preemption
            # recompute, where token_ids = prompt + generated): subtracting
            # it keeps prompt vs generated counts exact, so presence/
            # frequency penalties and seeded sampling survive preemption.
            seq_pad = token_ids.shape[0]
            valid = (jnp.arange(seq_pad) < seq_len).astype(jnp.int32)
            full_row = jnp.zeros((vocab,), jnp.int32).at[token_ids].add(valid, mode="drop")
            prompt_row = full_row - gen_row
            prompt_counts = prompt_counts.at[lane].set(prompt_row)
            gen_counts = gen_counts.at[lane].set(gen_row)
            with jax.named_scope("sample"):
                plogits = apply_penalties(
                    logits[None], gen_row[None], prompt_row[None], pres, freq, rep
                )
                plogits = apply_logit_bias(plogits, bias_ids, bias_vals)
                # guided decoding: inadmissible tokens → -inf (all-true row for
                # unguided sequences)
                plogits = jnp.where(grow[None], plogits, -jnp.inf)
                step_key = jax.random.fold_in(key, seq_len)
                token = sample_tokens(plogits, step_key[None], temp, top_k, top_p, greedy)[0]
                lp = token_logprobs(plogits, token[None])[0]
                tk_vals, tk_ids = topk_logprobs(plogits, topk_k)
            gen_counts = gen_counts.at[lane, token].add(1)
            return token, lp, tk_vals[0], tk_ids[0], cache, gen_counts, prompt_counts

        kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
            kwargs["out_shardings"] = (repl, repl, repl, repl, self._cache_sharding, repl, repl)
        return _ProgramsByBucket(
            step, "dyn_prefill_t", 5, donate_argnums=(1, 2, 3), **kwargs
        )

    def _build_prefill_prefix(self):
        """Continued prefill over a resident prefix (prefix-cache hit or a
        later chunk of a chunked prefill).  Penalty rows come in from the
        host (the full prompt is not on device here) and the sampling key
        folds with the total context length so seeded sampling matches the
        uncached path exactly."""
        cfg = self.config.model
        topk_k = self.config.top_logprobs_k

        # sequence parallelism: the tail rings over the sp axis with the
        # resident prefix merged per shard (same gate as _build_prefill)
        prefix_kwargs = {}
        if (
            self.mesh is not None
            and self.mesh.shape.get("sp", 1) > 1
            and self.family.prefix_prefill_accepts_sp
        ):
            prefix_kwargs["sp_mesh"] = self.mesh

        def step(params, cache, gen_counts, prompt_counts, lane, token_ids,
                 full_block_ids, tail_block_ids, tail_len, start_pos, total_len,
                 prompt_row, gen_row, sample_gate, key, temp, top_k, top_p,
                 greedy, pres, freq, rep, bias_ids, bias_vals, grow, cos, sin):
            logits, cache = self.family.forward_prefill_with_prefix(
                params, cfg, token_ids, cache, full_block_ids, tail_block_ids,
                tail_len, start_pos, cos, sin, **prefix_kwargs,
            )
            prompt_counts = prompt_counts.at[lane].set(prompt_row)
            gen_counts = gen_counts.at[lane].set(gen_row)
            with jax.named_scope("sample"):
                plogits = apply_penalties(
                    logits[None], gen_row[None], prompt_row[None], pres, freq, rep
                )
                plogits = apply_logit_bias(plogits, bias_ids, bias_vals)
                plogits = jnp.where(grow[None], plogits, -jnp.inf)
                step_key = jax.random.fold_in(key, total_len)
                token = sample_tokens(plogits, step_key[None], temp, top_k, top_p, greedy)[0]
                lp = token_logprobs(plogits, token[None])[0]
                tk_vals, tk_ids = topk_logprobs(plogits, topk_k)
            # sample_gate=0 for non-final chunks of a chunked prefill: the
            # logits are discarded and no generated count is recorded
            gen_counts = gen_counts.at[lane, token].add(sample_gate)
            return token, lp, tk_vals[0], tk_ids[0], cache, gen_counts, prompt_counts

        kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
            kwargs["out_shardings"] = (repl, repl, repl, repl, self._cache_sharding, repl, repl)
        return _ProgramsByBucket(
            step, "dyn_prefill_prefix_t", 5, donate_argnums=(1, 2, 3), **kwargs
        )

    def _build_prefill_mm(self):
        """Multimodal prefill: input embeddings are vision patch embeddings
        (positions < n_patch) spliced before text token embeddings looked up
        in-jit.  (Reference: multimodal encode→prefill flow,
        examples/multimodal/components/encode_worker.py:61.)"""
        cfg = self.config.model
        vocab = cfg.vocab_size
        topk_k = self.config.top_logprobs_k

        def step(params, cache, gen_counts, prompt_counts, lane, embeds,
                 token_ids, n_patch, block_ids, seq_len, gen_row, key, temp,
                 top_k, top_p, greedy, pres, freq, rep, bias_ids, bias_vals,
                 grow, cos, sin):
            s = token_ids.shape[0]
            pos = jnp.arange(s)
            # the family's embed hook carries input-embedding quirks (gemma
            # scales by sqrt(hidden)) so this generic splice code never
            # copies family math inline
            if self.family.embed is not None:
                x_text = self.family.embed(params, cfg, token_ids)
            else:
                x_text = params["embed"][token_ids].astype(cfg.dtype)
            x = jnp.where((pos < n_patch)[:, None], embeds.astype(cfg.dtype), x_text)
            logits, cache = self.family.forward_prefill_embeds(
                params, cfg, x, cache, block_ids, seq_len, jnp.int32(0),
                cos, sin,
            )
            # penalty rows count TEXT tokens only (patch positions masked)
            valid = ((pos >= n_patch) & (pos < seq_len)).astype(jnp.int32)
            full_row = jnp.zeros((vocab,), jnp.int32).at[token_ids].add(valid, mode="drop")
            prompt_row = full_row - gen_row
            prompt_counts = prompt_counts.at[lane].set(prompt_row)
            gen_counts = gen_counts.at[lane].set(gen_row)
            with jax.named_scope("sample"):
                plogits = apply_penalties(
                    logits[None], gen_row[None], prompt_row[None], pres, freq, rep
                )
                plogits = apply_logit_bias(plogits, bias_ids, bias_vals)
                plogits = jnp.where(grow[None], plogits, -jnp.inf)
                step_key = jax.random.fold_in(key, seq_len)
                token = sample_tokens(plogits, step_key[None], temp, top_k, top_p, greedy)[0]
                lp = token_logprobs(plogits, token[None])[0]
                tk_vals, tk_ids = topk_logprobs(plogits, topk_k)
            gen_counts = gen_counts.at[lane, token].add(1)
            return token, lp, tk_vals[0], tk_ids[0], cache, gen_counts, prompt_counts

        kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
            kwargs["out_shardings"] = (repl, repl, repl, repl, self._cache_sharding, repl, repl)
        return jax.jit(_named(step, "dyn_prefill_mm"), donate_argnums=(1, 2, 3), **kwargs)

    def _build_decode(self):
        cfg = self.config.model
        steps = self.config.decode_steps
        topk_k = self.config.top_logprobs_k

        # pipeline parallelism: when the mesh has a pp axis and the family
        # ships a pipelined decode, the layer stack runs as GPipe-style
        # stages over ICI instead of a plain scan (parallel/pipeline.py)
        use_pp = (
            self.mesh is not None
            and self.mesh.shape.get("pp", 1) > 1
            and self.family.forward_decode_pp is not None
        )

        def fwd_decode(params, cache, tokens, tables, lens, slots, cos, sin):
            if use_pp:
                return self.family.forward_decode_pp(
                    params, cfg, tokens, cache, tables, lens, slots,
                    cos, sin, pp_mesh=self.mesh,
                )
            kwargs = {"attention": self.attention_impl}
            if (
                self.mesh is not None
                and self.attention_impl.startswith("pallas")
                and self.family.decode_accepts_tp_mesh
            ):
                # the pallas kernel runs per tp shard under shard_map
                kwargs["tp_mesh"] = self.mesh
            return self.family.forward_decode(
                params, cfg, tokens, cache, tables, lens, slots,
                cos, sin, **kwargs,
            )

        lanes = self.config.max_batch_size
        lane_idx = jnp.arange(lanes)

        kwargs = {}
        repl = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())

        if steps <= 1:
            if repl is not None:
                kwargs["out_shardings"] = (
                    repl, repl, repl, repl, self._cache_sharding, repl
                )
            def step(params, cache, gen_counts, prompt_counts, token_ids,
                     block_tables, context_lens, slot_ids, keys, temp, top_k,
                     top_p, greedy, pres, freq, rep, bias_ids, bias_vals,
                     gtable, gmodes, cos, sin):
                logits, cache = fwd_decode(
                    params, cache, token_ids, block_tables, context_lens,
                    slot_ids, cos, sin,
                )
                with jax.named_scope("sample"):
                    logits = apply_penalties(logits, gen_counts, prompt_counts, pres, freq, rep)
                    logits = apply_logit_bias(logits, bias_ids, bias_vals)
                    # guided decoding: each lane's mode id selects its
                    # admissible-token row from the resident table; mode -1 =
                    # unguided (all tokens allowed)
                    rows = gtable[jnp.clip(gmodes, 0, gtable.shape[0] - 1)]
                    allowed = jnp.where((gmodes < 0)[:, None], True, rows)
                    logits = jnp.where(allowed, logits, -jnp.inf)
                    step_keys = jax.vmap(jax.random.fold_in)(keys, context_lens)
                    tokens = sample_tokens(logits, step_keys, temp, top_k, top_p, greedy)
                    lps = token_logprobs(logits, tokens)
                    tk_vals, tk_ids = topk_logprobs(logits, topk_k)
                active = (context_lens > 0).astype(jnp.int32)
                gen_counts = gen_counts.at[lane_idx, tokens].add(active)
                return tokens, lps, tk_vals, tk_ids, cache, gen_counts

            return jax.jit(_named(step, "dyn_decode_w1"), donate_argnums=(1, 2), **kwargs)

        # Fused multi-step decode: scan `steps` iterations on-device.  The
        # sampled token feeds back without a host roundtrip; per-iteration
        # cache slots are derived from the (pre-extended) block tables.
        block_size = self.config.block_size
        oob = self.config.num_blocks * block_size
        max_pos = self.max_len - 1

        def multi(params, cache, gen_counts, prompt_counts, token_ids,
                  block_tables, context_lens, keys, temp, top_k, top_p, greedy,
                  pres, freq, rep, bias_ids, bias_vals, cos, sin):
            active = context_lens > 0
            active_i = active.astype(jnp.int32)

            def body(carry, _):
                tokens, cache, gen_counts, lens = carry
                # block tables cover the window; overflow past max_len is
                # clamped (garbage written to the final slot is discarded by
                # the host's LENGTH finish)
                pos = jnp.clip(lens - 1, 0, max_pos)
                blk = jnp.take_along_axis(block_tables, (pos // block_size)[:, None], axis=1)[:, 0]
                slots = jnp.where(active, blk * block_size + pos % block_size, oob)
                logits, cache = fwd_decode(
                    params, cache, tokens, block_tables, lens, slots, cos, sin
                )
                with jax.named_scope("sample"):
                    logits = apply_penalties(logits, gen_counts, prompt_counts, pres, freq, rep)
                    logits = apply_logit_bias(logits, bias_ids, bias_vals)
                    step_keys = jax.vmap(jax.random.fold_in)(keys, lens)
                    tokens = sample_tokens(logits, step_keys, temp, top_k, top_p, greedy)
                    lps = token_logprobs(logits, tokens)
                    tk_vals, tk_ids = topk_logprobs(logits, topk_k)
                gen_counts = gen_counts.at[lane_idx, tokens].add(active_i)
                lens = jnp.where(active, lens + 1, lens)
                return (tokens, cache, gen_counts, lens), (tokens, lps, tk_vals, tk_ids)

            (tokens_last, cache, gen_counts, _), (tokens_seq, lp_seq, tkv_seq, tki_seq) = jax.lax.scan(
                body, (token_ids, cache, gen_counts, context_lens), None, length=steps
            )
            # the carry tokens ride out as a dedicated output: the overlap
            # pipeline feeds them straight back as the next window's input
            # (one extra output handle beats a separate slice launch)
            return tokens_seq, lp_seq, tkv_seq, tki_seq, tokens_last, cache, gen_counts

        if repl is not None:
            # one extra leading repl vs the single-step tuple: the
            # dedicated feedback-tokens output
            kwargs["out_shardings"] = (
                repl, repl, repl, repl, repl, self._cache_sharding, repl
            )
        return jax.jit(_named(multi, f"dyn_decode_w{steps}"), donate_argnums=(1, 2), **kwargs)

    def _build_unified(self):
        """Ragged unified-batch step: ONE launch computes chunked-prefill
        spans and decode tokens from different sequences (flat token axis +
        per-token lane/pos metadata + per-block page spans, forward_unified
        → ragged paged attention), then samples one token per lane.  Key-fold, penalty, bias and
        guided-free logits math mirror the split programs bit-for-bit so
        the two paths keep byte-identical outputs:

        - ``context_lens[lane]`` doubles as the attention context AND the
          per-lane key fold value (split prefill folds with the total
          length, split decode with the context including the new token —
          both equal the lane's span end);
        - newly-admitted prefills (re)seed their penalty-count rows in-jit
          via the ``seed_*`` scatter, exactly what the split prefill
          programs compute from the prompt;
        - ``sample_gate`` drops intermediate-chunk samples from the
          generated counts, like the continued-prefill program's gate.

        Single-device only (the engine auto-disables unified on meshes)."""
        cfg = self.config.model
        topk_k = self.config.top_logprobs_k
        lanes = self.config.max_batch_size
        lane_idx = jnp.arange(lanes)

        def step(params, cache, gen_counts, prompt_counts, token_ids,
                 feedback, use_fb, block_tables, context_lens, token_pos,
                 token_slot, token_lane, span_lane, span_first, span_count,
                 kv_steps, sample_rows, sample_gate, seed_lanes,
                 seed_prompt, seed_gen, keys, temp, top_k, top_p, greedy,
                 pres, freq, rep, bias_ids, bias_vals, cos, sin):
            lane_c = jnp.clip(token_lane, 0, lanes - 1)
            # on-device token feedback: a decode token whose lane has an
            # unretired window reads the previous window's output array —
            # the host never waits for (or sees) the token it dispatches
            tok = jnp.where(use_fb, feedback[lane_c], token_ids)
            logits, cache = self.family.forward_unified(
                params, cfg, tok, cache, block_tables, context_lens,
                token_pos, token_slot, token_lane, span_lane, span_first,
                span_count, kv_steps, sample_rows, cos, sin,
                attention=self.attention_impl,
                tb_tokens=self._tb_for(token_ids.shape[0]),
            )  # [lanes, vocab]
            prompt_counts = prompt_counts.at[seed_lanes].set(
                seed_prompt, mode="drop"
            )
            gen_counts = gen_counts.at[seed_lanes].set(seed_gen, mode="drop")
            with jax.named_scope("sample"):
                plogits = apply_penalties(
                    logits, gen_counts, prompt_counts, pres, freq, rep
                )
                plogits = apply_logit_bias(plogits, bias_ids, bias_vals)
                step_keys = jax.vmap(jax.random.fold_in)(keys, context_lens)
                tokens = sample_tokens(plogits, step_keys, temp, top_k, top_p, greedy)
                lps = token_logprobs(plogits, tokens)
                tk_vals, tk_ids = topk_logprobs(plogits, topk_k)
            gen_counts = gen_counts.at[lane_idx, tokens].add(sample_gate)
            return tokens, lps, tk_vals, tk_ids, cache, gen_counts, prompt_counts

        return _ProgramsByBucket(step, "dyn_unified_t", 4, donate_argnums=(1, 2, 3))

    def _build_verify(self):
        """Speculative verification step: one forward over the [lanes, w]
        window (w = spec_tokens + 1), position 0 through the full sampling
        machinery, later positions greedy.  Lanes verify drafts with the
        leading-match rule; ``spec_ok`` gates lanes whose sampling config
        makes greedy verification exact (greedy, no penalties)."""
        cfg = self.config.model
        topk_k = self.config.top_logprobs_k
        w_len = self.config.spec_tokens + 1
        lanes = self.config.max_batch_size
        lane_idx = jnp.arange(lanes)

        def step(params, cache, gen_counts, prompt_counts, token_ids,
                 block_tables, context_lens, slot_ids, spec_ok, keys, temp,
                 top_k, top_p, greedy, pres, freq, rep, bias_ids, bias_vals,
                 cos, sin):
            # the pallas window kernel runs single-device only (the tp
            # shard_map wrapper exists just for the 1-query kernel)
            impl = self.attention_impl if self.mesh is None else "jax"
            logits, cache = self.family.forward_verify(
                params, cfg, token_ids, cache, block_tables, context_lens,
                slot_ids, cos, sin, attention=impl,
            )  # [lanes, w, vocab]
            active = context_lens > 0
            base_lens = jnp.maximum(context_lens - (w_len - 1), 0)
            step_keys = jax.vmap(jax.random.fold_in)(keys, base_lens)

            outs, lps, tkvs, tkis = [], [], [], []
            for i in range(w_len):
                li = apply_penalties(
                    logits[:, i], gen_counts, prompt_counts, pres, freq, rep
                )
                li = apply_logit_bias(li, bias_ids, bias_vals)
                if i == 0:
                    ti = sample_tokens(li, step_keys, temp, top_k, top_p, greedy)
                else:
                    ti = jnp.argmax(li, axis=-1).astype(jnp.int32)
                outs.append(ti)
                lps.append(token_logprobs(li, ti))
                tv, tk_ = topk_logprobs(li, topk_k)
                tkvs.append(tv)
                tkis.append(tk_)
            tokens_out = jnp.stack(outs, axis=1)       # [lanes, w]
            lp_out = jnp.stack(lps, axis=1)
            tkv_out = jnp.stack(tkvs, axis=1)
            tki_out = jnp.stack(tkis, axis=1)

            # leading-match acceptance: draft i (window token i) is kept iff
            # every earlier draft matched and it equals the model's output
            # at position i-1
            acc = spec_ok & active
            n_accept = jnp.where(active, 1, 0)
            for i in range(1, w_len):
                acc = acc & (token_ids[:, i] == tokens_out[:, i - 1])
                n_accept = n_accept + acc.astype(jnp.int32)

            # penalty bookkeeping for accepted tokens only (spec_ok lanes
            # have no penalties, but counts must stay exact for later
            # requests reusing the lane and for stats)
            pos = jnp.arange(w_len)[None, :]
            take = (pos < n_accept[:, None]) & active[:, None]
            gen_counts = gen_counts.at[
                lane_idx[:, None], tokens_out
            ].add(take.astype(jnp.int32))
            return tokens_out, n_accept, lp_out, tkv_out, tki_out, cache, gen_counts

        kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
            kwargs["out_shardings"] = (
                repl, repl, repl, repl, repl, self._cache_sharding, repl
            )
        return jax.jit(_named(step, f"dyn_verify_w{w_len}"), donate_argnums=(1, 2), **kwargs)

    def _build_extract(self):
        """Gather a sequence's KV blocks (padded to max_blocks_per_seq) for
        cross-worker transfer — the TPU-native replacement for NIXL reads
        (SURVEY.md §2.5 KV transfer plane).  Generic over the family's cache
        pytree (llama {"k","v"} symmetric; DeepSeek MLA latent + rope-key
        leaves with different widths)."""

        def fn(cache, block_ids):
            return jax.tree.map(lambda c: c[:, block_ids], _pages(cache))

        return jax.jit(_named(fn, "dyn_kv_extract"))

    def _build_inject(self):
        """Scatter transferred KV blocks into this engine's cache, per cache
        leaf (so asymmetric-layout families inject correctly)."""
        num_blocks = self.config.num_blocks

        def fn(cache, new, block_ids, n):
            maxb = block_ids.shape[0]
            ids = jnp.where(jnp.arange(maxb) < n, block_ids, num_blocks)
            written = jax.tree.map(
                lambda c, x: c.at[:, ids].set(x.astype(c.dtype), mode="drop"),
                _pages(cache), new,
            )
            return {**cache, **written}

        kwargs = {}
        if self.mesh is not None:
            kwargs["out_shardings"] = self._cache_sharding
        return jax.jit(_named(fn, "dyn_kv_inject"), donate_argnums=(0,), **kwargs)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        # DYN_PROFILER_TRACE_DIR: capture a device trace of the whole serve
        # window (stopped in stop() by whichever engine started it)
        from dynamo_tpu.utils import profiling

        self._profiler_trace_dir = profiling.maybe_start_trace_from_env()
        self._stop = False
        self._thread = threading.Thread(target=self._device_loop, name="jax-engine", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._profiler_trace_dir is not None:
            # before the device thread goes: the profiler drops the host
            # events (the dyn.* phases, the program launches) of a thread
            # that has exited by the time the trace is collected
            from dynamo_tpu.utils import profiling

            profiling.maybe_stop_trace()
            self._profiler_trace_dir = None
        self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
            import json

            final = self.stats()
            # per-device HBM in use (None on backends that do not report)
            final["device_bytes_in_use"] = [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()
            ]
            # (the span histograms stay in stats(); a log line is no place
            # for them)
            del final["spans"]
            logger.info("engine stopped: %s", json.dumps(final, default=str))
        if self.host_tier is not None:
            self.host_tier.close()  # release + delete the G3 memmap

    # -- async engine interface -------------------------------------------
    async def generate(self, request: Context[dict]) -> ResponseStream[dict]:
        if request.data.get("image") is not None or request.data.get("video") is not None:
            # modality payloads are consumed by a MultimodalEngine wrapper
            # BEFORE delegation (examples/multimodal/pipeline.py); reaching
            # the text engine with one still attached means this deployment
            # has no encoder — refuse rather than silently answer from the
            # text alone
            raise ValueError(
                "this model deployment does not accept image/video input"
            )
        pre = PreprocessedRequest.from_wire(request.data)
        ctx = request.ctx
        if len(pre.token_ids) >= self.max_len:
            raise ValueError(
                f"prompt length {len(pre.token_ids)} exceeds engine max length {self.max_len}"
            )
        seq = Sequence(seq_id=ctx.id or uuid.uuid4().hex, request=pre)
        seq.trace = getattr(ctx, "trace", None)
        if pre.output_format is not None:
            seq.guided = self._make_guided_cursor(pre.output_format)
        return self._start_sequence(seq, ctx)

    def _make_guided_cursor(self, output_format: str):
        """Validate a guided request against this deployment and return a
        fresh cursor — loud 400-class errors beat silently-unconstrained
        output the client believes is schema-guaranteed."""
        if output_format not in ("json", "json_object"):
            raise ValueError(
                f"unsupported output_format {output_format!r} (want 'json')"
            )
        if self.guided_masks is None:
            raise ValueError(
                "guided JSON decoding is not enabled on this worker "
                "(engine.enable_guided_json(tokenizer) at serve time)"
            )
        if self.config.decode_steps > 1:
            # the fused scan feeds tokens back on-device; the automaton
            # advances on the host between launches, so the mask would lag
            # the generated text by up to decode_steps-1 tokens
            raise ValueError(
                "guided JSON decoding requires decode_steps=1 "
                f"(engine runs fused decode_steps={self.config.decode_steps})"
            )
        if self.spec_enabled:
            # the verify program samples the whole draft window with one
            # mask state; drafts would need per-position automaton advances
            raise ValueError(
                "guided JSON decoding does not compose with speculative "
                "decoding on this engine"
            )
        from dynamo_tpu.llm.guided import JsonCursor

        # count AFTER validation: rejected requests are not "admitted"
        self._guided_requests += 1
        return JsonCursor(
            self.guided_masks, self._guided_strings, eos_ids=self._guided_eos
        )

    def _start_sequence(self, seq: Sequence, ctx) -> ResponseStream[dict]:
        """Shared streaming tail for every entry point: wire the emit
        callback, submit to the device thread, watch for cancellation."""
        loop = asyncio.get_running_loop()
        out_q: asyncio.Queue = asyncio.Queue()

        def emit(tokens: list[int], finish: FinishReason | None,
                 error: str | None = None,
                 logprobs: list[float] | None = None,
                 top_logprobs: list[list[list]] | None = None) -> None:
            # runs on the device thread; the stamp lets the frontend see
            # how long the chunk took from here to the socket
            out = LLMEngineOutput(
                token_ids=tokens, finish_reason=finish, error=error,
                logprobs=logprobs, top_logprobs=top_logprobs,
                emitted_ts=time.time(),
            )
            wire = Annotated.from_data(out).to_wire(LLMEngineOutput.to_wire)
            loop.call_soon_threadsafe(out_q.put_nowait, wire)
            if finish is not None:
                loop.call_soon_threadsafe(out_q.put_nowait, None)

        seq.emit = emit
        self._submit_q.put(("add", seq))
        self._wake.set()

        cancel_task = spawn_logged(self._watch_cancel(ctx, seq))

        async def gen() -> AsyncIterator[dict]:
            try:
                while True:
                    item = await out_q.get()
                    if item is None:
                        break
                    yield item
            finally:
                cancel_task.cancel()

        return ResponseStream(gen(), ctx)

    async def generate_multimodal(
        self, request: Context[dict], embeds
    ) -> ResponseStream[dict]:
        """Generate with vision patch embeddings spliced before the text
        prompt (LLaVA-style).  ``embeds``: [n_patches, hidden] float array
        from the vision encoder's projector."""
        if self._jit_prefill_mm is None:
            raise ValueError(
                f"model family {self.config.model_family!r} has no multimodal prefill"
            )
        pre = PreprocessedRequest.from_wire(request.data)
        ctx = request.ctx
        embeds = np.asarray(embeds, np.float32)
        if embeds.ndim != 2 or embeds.shape[1] != self.config.model.hidden_size:
            raise ValueError(
                f"embeds shape {embeds.shape} != [n, {self.config.model.hidden_size}]"
            )
        if len(pre.token_ids) + len(embeds) >= self.max_len:
            raise ValueError(
                f"prompt ({len(pre.token_ids)} text + {len(embeds)} patches) "
                f"exceeds engine max length {self.max_len}"
            )
        seq = Sequence(seq_id=ctx.id or uuid.uuid4().hex, request=pre, mm_embeds=embeds)
        seq.trace = getattr(ctx, "trace", None)
        if pre.output_format is not None:
            # same contract as generate(): a guided multimodal request on a
            # deployment that cannot constrain it must fail loudly (the mm
            # prefill program already threads the mask row)
            seq.guided = self._make_guided_cursor(pre.output_format)
        return self._start_sequence(seq, ctx)

    async def _watch_cancel(self, ctx, seq: Sequence) -> None:
        await ctx.stopped()
        self._submit_q.put(("abort", seq))
        self._wake.set()

    # -- disaggregation API ------------------------------------------------
    async def prefill_extract(
        self, pre: PreprocessedRequest, *, device: bool = False,
        on_chunk=None,
    ) -> tuple[int, float, list | None, dict, int]:
        """Prefill-worker side: run prefill only, return (first_token,
        first_token_logprob, first_token_top_logprobs, blocks, n_blocks).  ``blocks`` is the cache pytree restricted to the
        sequence's blocks, e.g. llama ``{"k": [L, n, bs, kvh, d], "v": ...}``
        — host numpy by default, device arrays with ``device=True`` (the
        same-process/ICI transfer path: no host staging).

        ``on_chunk`` (streamed disagg transfer): called from the DEVICE
        thread as ``on_chunk(start_block, leaves, count)`` for each run of
        fully-written blocks after an intermediate prefill chunk, while
        later chunks still compute.  The final return then carries only the
        TAIL blocks past the streamed watermark (``n_blocks`` stays the
        sequence total).  Requires chunked prefill to fire; without it the
        call degenerates to the single-shot contract."""
        self.allocator.single_pool_only("KV extraction for a decode worker")
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        seq = Sequence(
            seq_id=uuid.uuid4().hex, request=pre, prefill_only=True,
            extract_device=device,
        )
        seq.on_chunk_done = on_chunk
        if pre.output_format is not None:
            # constrain the FIRST sampled token on the prefill side so the
            # decode worker's cursor (generate_prefilled) accepts it — this
            # is what makes guided decoding compose with disaggregation
            seq.guided = self._make_guided_cursor(pre.output_format)

        def on_done(result) -> None:
            def resolve() -> None:
                if fut.done():
                    return
                if isinstance(result, BaseException):
                    fut.set_exception(result)
                else:
                    fut.set_result(result)

            loop.call_soon_threadsafe(resolve)

        seq.on_prefill_done = on_done
        self._submit_q.put(("add", seq))
        self._wake.set()
        return await fut

    def reserve_blocks(self, num_tokens: int) -> list[int] | None:
        return self.allocator.reserve_blocks(num_tokens)

    def release_blocks(self, block_ids: list[int]) -> None:
        self.allocator.release_blocks(block_ids)

    async def inject_blocks(self, block_ids: list[int], blocks: dict) -> None:
        """Decode-worker side: write transferred KV blocks (cache pytree of
        host or device arrays) into the cache (runs on the device thread to
        serialize with step functions)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def done(exc: BaseException | None = None) -> None:
            def resolve() -> None:
                if fut.done():
                    return
                if exc is not None:
                    fut.set_exception(exc)
                else:
                    fut.set_result(None)

            loop.call_soon_threadsafe(resolve)

        self._submit_q.put(("inject", (list(block_ids), blocks, done)))
        self._wake.set()
        await fut

    async def generate_prefilled(
        self, request: Context[dict], block_ids: list[int], first_token: int,
        first_token_logprob: float | None = None,
        first_token_top_logprobs: list | None = None,
    ) -> ResponseStream[dict]:
        """Decode-worker side: start decoding a sequence whose prompt KV was
        injected into ``block_ids`` and whose first token was already sampled
        by the prefill worker."""
        pre = PreprocessedRequest.from_wire(request.data)
        ctx = request.ctx
        loop = asyncio.get_running_loop()
        out_q: asyncio.Queue = asyncio.Queue()
        seq = Sequence(seq_id=ctx.id or uuid.uuid4().hex, request=pre, remote_prefilled=True)
        seq.trace = getattr(ctx, "trace", None)
        if pre.output_format is not None:
            # disagg split: the remote prefill worker sampled first_token —
            # advance a fresh cursor over it.  A guided-enabled prefill
            # worker (prefill_extract builds its own cursor) always hands
            # over an admissible token; an unconstrained one can hand over
            # anything, including an early EOS — refuse loudly instead of
            # silently dropping the constraint.  On refusal the caller's
            # reserved landing blocks must not leak (the sole production
            # caller, llm/disagg.py, calls this outside its try/except):
            # adopt + free returns them to the pool before raising.
            cursor = None
            try:
                cursor = self._make_guided_cursor(pre.output_format)
                cursor.advance(first_token)
                if cursor.failed or (
                    first_token in self._guided_eos and not cursor.complete
                ):
                    raise ValueError(
                        "guided JSON decoding over disaggregated prefill "
                        "needs a guided-enabled prefill worker: the "
                        "remotely sampled first token is not a valid JSON "
                        "start"
                    )
            except ValueError:
                if cursor is not None:
                    # the cursor was admitted-counted, then rejected
                    self._guided_requests -= 1
                self.allocator.adopt_sequence(seq.seq_id, block_ids)
                self.allocator.free_sequence(seq.seq_id)
                raise
            seq.guided = cursor
            if cursor.complete:
                # a single token closed the whole document (e.g. a "{}"
                # token): count it here — the transition happened outside
                # _process_token, which only sees later tokens
                self._guided_completions += 1
        seq.output_ids.append(first_token)
        self.allocator.adopt_sequence(seq.seq_id, block_ids)

        def emit(tokens: list[int], finish: FinishReason | None,
                 error: str | None = None,
                 logprobs: list[float] | None = None,
                 top_logprobs: list[list[list]] | None = None) -> None:
            wire = Annotated.from_data(
                LLMEngineOutput(
                    token_ids=tokens, finish_reason=finish, error=error,
                    logprobs=logprobs, top_logprobs=top_logprobs,
                    emitted_ts=time.time(),
                )
            ).to_wire(LLMEngineOutput.to_wire)
            loop.call_soon_threadsafe(out_q.put_nowait, wire)
            if finish is not None:
                loop.call_soon_threadsafe(out_q.put_nowait, None)

        seq.emit = emit
        # surface the prefill worker's token as the first stream item
        finish = seq.hit_stop(first_token)
        emit(
            [first_token], finish,
            logprobs=None if first_token_logprob is None else [first_token_logprob],
            top_logprobs=(
                None if first_token_top_logprobs is None
                else [first_token_top_logprobs]
            ),
        )
        if finish is None:
            self._submit_q.put(("add", seq))
            self._wake.set()
        else:
            self.allocator.free_sequence(seq.seq_id)

        cancel_task = spawn_logged(self._watch_cancel(ctx, seq))

        async def gen() -> AsyncIterator[dict]:
            try:
                while True:
                    item = await out_q.get()
                    if item is None:
                        break
                    yield item
            finally:
                cancel_task.cancel()

        return ResponseStream(gen(), ctx)

    async def warmup(self) -> None:
        """Compile every serving program up front: one throwaway greedy
        request per prefill bucket (which also compiles the decode program
        on its first window), then a full cache flush so warmup blocks
        never pollute prefix-reuse state or router indexes.  Production
        cold-start pays compiles here instead of on the first user
        request."""
        rng = np.random.default_rng(0x5EED)
        # the prefill jit emits the first token itself, so compiling the
        # decode program needs at least one full decode window on top
        want_tokens = self.config.decode_steps + 1

        async def drive(n: int, max_toks: int) -> None:
            # distinct tokens per call: identical prompts would prefix-hit
            # and compile the continued-prefill jit instead of the target
            tokens = rng.integers(
                2, max(3, self.config.model.vocab_size - 2), size=n
            ).tolist()
            req = PreprocessedRequest(
                token_ids=tokens,
                stop=StopConditions(max_tokens=max_toks, ignore_eos=True),
                eos_token_ids=[],
            )
            req.sampling.use_greedy = True
            stream = await self.generate(Context(req.to_wire()))
            async for item in stream:
                out = Annotated.from_wire(item, LLMEngineOutput.from_wire).data
                if out is not None and out.finish_reason == FinishReason.ERROR:
                    # a serving program that does not compile or run is a
                    # start-up failure, not something to find out mid-serve
                    raise RuntimeError(
                        f"warmup request of {n} tokens failed: {out.error}"
                    )

        plans: list[tuple[int, int]] = []
        prev = 0
        for bucket in self.buckets:
            if self.chunk_tokens is not None and bucket > self.chunk_tokens:
                # chunked serving never runs full-prompt programs above the
                # chunk budget; the chunk pipeline warms below
                prev = bucket
                continue
            # prompt must land IN this bucket (> prev), preferring room for
            # a full decode window under max_len (shrink max_tokens only
            # when the bucket itself touches max_len)
            n = min(bucket, self.max_len - want_tokens)
            if n <= prev:
                n = min(bucket, self.max_len - 1)
            if n <= prev or n < 2:
                logger.debug("warmup: bucket %d unreachable under max_len", bucket)
                prev = bucket
                continue
            prev = bucket
            plans.append((n, min(want_tokens, self.max_len - n)))
        if self.chunk_tokens is not None and self.max_len > self.chunk_tokens + 1:
            # one longer prompt compiles the chunk + continued-prefill jits
            n = min(2 * self.chunk_tokens, self.max_len - want_tokens)
            if n > self.chunk_tokens:
                plans.append((n, min(want_tokens, self.max_len - n)))
        if self.mesh is None:
            # compile the planned programs concurrently first; the drives
            # below then hit the persistent cache instead of compiling
            # one-by-one on the device thread.  A program the compiler
            # refuses fails warmup.
            await asyncio.get_running_loop().run_in_executor(
                None, partial(self.aot_precompile, [n for n, _ in plans])
            )
        for n, toks in plans:
            t0 = time.monotonic()
            await drive(n, toks)
            logger.info(
                "warmup: %d-token prompt + %d tokens in %.1fs",
                n, toks, time.monotonic() - t0,
            )
        if self.spec_enabled:
            # warmup's random prompts never draft, so the verify program
            # would otherwise pay its compile on the first real accepting
            # step: run it once with every lane inactive (writes all drop,
            # nothing emitted) on the device thread
            loop = asyncio.get_running_loop()
            fut: asyncio.Future = loop.create_future()

            def done(exc) -> None:
                def resolve() -> None:
                    if fut.done():
                        return
                    if exc is not None:
                        fut.set_exception(exc)
                    else:
                        fut.set_result(None)

                loop.call_soon_threadsafe(resolve)

            self._submit_q.put(("warm_verify", done))
            self._wake.set()
            await fut
        await self.clear_kv_blocks()

    def aot_precompile(self, prompt_lens, parallel: int = 8, on_program=None) -> int:
        """Compile the serving programs for the given prompt lengths
        CONCURRENTLY, ahead of first use.

        The device loop compiles lazily — one program per first dispatch,
        strictly serially.  Against a remote compile service (or any
        multi-core compiler) that serializes what could run in parallel:
        each program is independent.  This lowers every program the
        serving loop will need for ``prompt_lens`` with exact argument
        avals (on the calling thread) and compiles them in a thread pool
        (XLA releases the GIL during compilation).

        The compiled results reach the real dispatch path through JAX's
        persistent compilation cache (utils/compile_cache.py).  An aval
        mismatch would silently compile a useless twin program, so
        tests/engine/test_aot_precompile.py asserts the real serving path
        produces ZERO new cache entries after this ran.

        Single-device engines only (the sharded path's out_shardings need
        device-committed avals; multi-chip engines keep lazy compiles).
        Returns the number of programs compiled.
        """
        if self.mesh is not None:
            return 0
        jobs = self._aot_jobs(prompt_lens)

        import concurrent.futures as cf

        t0 = time.monotonic()
        # Lower HERE, one program after another, always in this order; only
        # the compiles (where XLA drops the GIL) fan out.  A kernel's
        # bytecode carries its MLIR locations, and jitted jnp helpers
        # (where, maximum, ...) are traced once per process and shared: the
        # kernel that traces one first leaves ITS source line in every
        # kernel that reuses it.  So a Pallas program's cache key depends on
        # the order of first traces — racing threads made it random; a
        # fixed order makes every --warmup start produce the same keys.
        lowered = [
            (name, jit_fn.lower(*avals)) for name, (jit_fn, avals) in jobs.items()
        ]
        logger.info(
            "aot_precompile: %d programs lowered in %.1fs",
            len(lowered), time.monotonic() - t0,
        )

        def compile_one(item):
            name, program = item
            t = time.monotonic()
            # what the program allocates beside its arguments and results: a
            # step program that copies the cache holds a whole cache here
            analysis = program.compile().memory_analysis()
            if analysis is not None:  # a backend may report none
                self.program_temp_bytes[name] = int(analysis.temp_size_in_bytes)
            logger.info("aot_precompile: %s in %.1fs", name, time.monotonic() - t)
            if on_program is not None:
                on_program(name)

        with cf.ThreadPoolExecutor(max_workers=max(1, parallel)) as ex:
            list(ex.map(compile_one, lowered))
        logger.info(
            "aot_precompile: %d programs in %.1fs", len(jobs), time.monotonic() - t0
        )
        return len(jobs)

    def _aot_jobs(self, prompt_lens) -> dict[tuple, tuple]:
        """The serving programs for ``prompt_lens`` as {name: (jit, avals)},
        avals exactly as the dispatch paths ship them."""
        sds = jax.ShapeDtypeStruct
        cfg = self.config
        vocab = cfg.model.vocab_size
        lanes = cfg.max_batch_size
        kb = cfg.logit_bias_k
        aval = lambda t: jax.tree.map(  # noqa: E731
            lambda x: sds(x.shape, x.dtype), t
        )
        params_a, cache_a = aval(self.params), aval(self.cache)
        counts_a = sds((lanes, vocab), jnp.int32)
        i32, row_a = sds((), jnp.int32), sds((vocab,), jnp.int32)
        key_a = sds((2,), jnp.uint32)
        keys_a = sds((lanes, 2), jnp.uint32)
        cos_a, sin_a = aval(self.cos), aval(self.sin)
        grow_a = aval(self._guided_true_row)
        gtable_a = aval(self._guided_table)
        gmodes_a = sds((lanes,), jnp.int32)

        def tail(n):
            f32 = lambda: sds((n,), jnp.float32)  # noqa: E731
            return (f32(), sds((n,), jnp.int32), f32(), sds((n,), jnp.bool_),
                    f32(), f32(), f32(), sds((n, kb), jnp.int32),
                    sds((n, kb), jnp.float32))

        jobs: dict[tuple, tuple] = {}  # dedup key -> (jit_fn, avals)
        # a value a pool where the model has a window pool (``KvPools``)
        pools = (lambda a: KvPools(a, a)) if self._window_blocks else (lambda a: a)
        blocks_fixed = pools(sds((self.max_blocks_per_seq,), jnp.int32))
        for n in prompt_lens:
            n = min(int(n), self.max_len - 1)
            if self.chunk_tokens is not None:
                # chunked serving runs the continued-prefill program for
                # every window; shapes depend only on (window bucket,
                # table bucket for the full prompt) — mirror _run_prefill's
                # table sizing exactly
                table_len = self.allocator.blocks_needed(
                    self._bucket_len(min(n + 1, self.max_len))
                )
                table_a = sds((table_len,), jnp.int32)
                # reachable window buckets: under concurrent prefills the
                # scheduler's _plan_chunk shrinks windows block-aligned to
                # fit the shared budget, so ANY bucket up to the largest
                # window's bucket can appear — including for prompts
                # shorter than the chunk budget (they chunk too when
                # admitted with leftover budget).  The bucket set is
                # small; compiling them all keeps the concurrent-load
                # path off the lazy device-thread compiler.
                cap = self._bucket_len(min(n, self.chunk_tokens))
                for b in (x for x in self.buckets if x <= cap):
                    jobs[("prefix", b, table_len)] = (
                        self._jit_prefill_prefix,
                        (params_a, cache_a, counts_a, counts_a, i32,
                         sds((b,), jnp.int32), table_a, table_a, i32, i32, i32,
                         row_a, row_a, i32, key_a, *tail(1), grow_a,
                         cos_a, sin_a),
                    )
            if self.chunk_tokens is None or n <= self.chunk_tokens:
                # whole-prompt program: the only path when chunking is off,
                # and still the uncontended path for prompts within the
                # chunk budget
                b = self._bucket_len(n)
                jobs[("prefill", b)] = (
                    self._jit_prefill,
                    (params_a, cache_a, counts_a, counts_a, i32,
                     sds((b,), jnp.int32), blocks_fixed, i32, i32, row_a,
                     key_a, *tail(1), grow_a, cos_a, sin_a),
                )
        tables_a = pools(sds((lanes, self.max_blocks_per_seq), jnp.int32))
        lanes_i = sds((lanes,), jnp.int32)
        if cfg.decode_steps > 1:
            jobs[("decode",)] = (
                self._jit_decode,
                (params_a, cache_a, counts_a, counts_a, lanes_i, tables_a,
                 lanes_i, keys_a, *tail(lanes), cos_a, sin_a),
            )
        else:
            jobs[("decode",)] = (
                self._jit_decode,
                (params_a, cache_a, counts_a, counts_a, lanes_i, tables_a,
                 lanes_i, lanes_i, keys_a, *tail(lanes), gtable_a, gmodes_a,
                 cos_a, sin_a),
            )
        if self._jit_verify is not None:
            w = cfg.spec_tokens + 1
            win_a = sds((lanes, w), jnp.int32)
            jobs[("verify",)] = (
                self._jit_verify,
                (params_a, cache_a, counts_a, counts_a, win_a, tables_a,
                 lanes_i, win_a, sds((lanes,), jnp.bool_), keys_a,
                 *tail(lanes), cos_a, sin_a),
            )
        if self.unified_batch and self._jit_unified is not None:
            # unified compile buckets: every reachable token-axis bucket —
            # bounded by one chunk window plus a full complement of packed
            # decode lanes — gets its mixed program warmed with the exact
            # avals _run_unified ships (the page spans' shapes included), so
            # the first mixed window after a cold start never compiles on
            # the device thread
            nseed = self._unified_seed_slots
            if self.chunk_tokens is not None:
                ucap = self._bucket_len(
                    min(self.chunk_tokens + lanes, self.max_len)
                )
            else:
                ucap = self.buckets[-1]
            for b in (x for x in self.buckets if x <= ucap):
                ntb = b // self._tb_for(b)
                tok_a = sds((b,), jnp.int32)
                jobs[("unified", b)] = (
                    self._jit_unified,
                    (params_a, cache_a, counts_a, counts_a, tok_a, lanes_i,
                     sds((b,), jnp.bool_), tables_a, lanes_i, tok_a, tok_a,
                     tok_a, pools(tok_a), pools(tok_a), pools(tok_a),
                     pools(sds((ntb,), jnp.int32)), lanes_i, lanes_i,
                     sds((nseed,), jnp.int32), sds((nseed, vocab), jnp.int32),
                     sds((nseed, vocab), jnp.int32), keys_a, *tail(lanes),
                     cos_a, sin_a),
                )

        return jobs

    async def clear_kv_blocks(self) -> None:
        """Admin flush: drop published prefix-cache state (runs on the device
        thread to serialize with the allocator)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def done() -> None:
            loop.call_soon_threadsafe(lambda: fut.set_result(None) if not fut.done() else None)

        self._submit_q.put(("clear_kv", done))
        self._wake.set()
        await fut

    # -- predictive prefetch ------------------------------------------------
    def prefetch_hint(
        self, block_hashes: list[int], *, source: str = "arrival"
    ) -> bool:
        """Announce a prefix expected to be requested soon (thread-safe;
        called by the worker's PrefetchListener from the asyncio thread).
        The device loop pages the hinted blocks disk→host→HBM between
        steps.  Returns False when prefetch is disabled or there is
        nothing new to queue."""
        if self.prefetch_pager is None:
            return False
        queued = self.prefetch_pager.submit(block_hashes, source=source)
        if queued:
            self._wake.set()
        return queued

    # -- stats / events ----------------------------------------------------
    def _sink_event(self, event: KvEvent) -> None:
        if self._event_sink is not None:
            self._event_sink(event)

    def stats(self) -> dict:
        """ForwardPassMetrics (reference: lib/llm/src/kv_router/protocols.rs:43-59)."""
        out = {
            "kv_active_blocks": self.allocator.used_blocks,
            "kv_total_blocks": self.allocator.num_blocks,
            "kv_cached_blocks": self.allocator.cached_blocks,
            "gpu_cache_usage_perc": self.allocator.usage,
            "num_requests_waiting": self.scheduler.num_waiting,
            "num_requests_running": self.scheduler.num_running,
            "request_total_slots": self.config.max_batch_size,
            "iterations_total": self._iterations,
            "prefix_hits_total": self.allocator.prefix_hits_total,
            "prefix_cached_tokens_total": self.allocator.prefix_cached_tokens_total,
            "kv_publish_blocks_hashed_total": self.allocator.publish_blocks_hashed_total,
            "kv_publish_blocks_stored_total": self.allocator.publish_blocks_stored_total,
            "spec_drafted_tokens_total": self._spec_drafted,
            "spec_accepted_tokens_total": self._spec_accepted,
            "decode_windows_overlapped_total": self._overlap_windows,
            "decode_windows_sync_total": self._sync_windows,
            "decode_windows_unified_total": self._unified_windows,
            "prompt_window_live_tokens_total": self._prompt_window_live_tokens,
            "prompt_window_bucket_tokens_total": self._prompt_window_bucket_tokens,
            "admission_drains_total": self._admission_drains,
            # reason-slug → count of windows (or the engine init) that fell
            # back from the unified step; each reason also logged once
            "unified_fallbacks": dict(self._unified_fallbacks),
            # the ragged kernel's largest token block (default_tb_tokens)
            "kernel_config": {"tb_tokens": self._unified_tb},
            "attention_impl": self.attention_impl,
            "device": dict(self._device_info),
            # programs requested from the compiler / answered by the
            # persistent cache since engine init (utils/compile_cache.py)
            **compile_counts(),
            # what each step program aot_precompile compiled allocates
            # beside its arguments and results ("unified_4096": bytes), and
            # the largest of them (0 before it ran)
            "program_temp_bytes": {
                "_".join(map(str, name)): temp
                for name, temp in sorted(self.program_temp_bytes.items())
            },
            "program_temp_bytes_max": max(
                self.program_temp_bytes.values(), default=0
            ),
            "decode_steps_total": self._decode_steps_total,
            "guided_requests_total": self._guided_requests,
            "guided_completions_total": self._guided_completions,
            "num_preemptions_total": self.scheduler.preemptions_total,
            **self.step_telemetry.stats(),
            # utilization accounting (observability/perf.py): rolling MFU /
            # bandwidth-utilization / goodput + cumulative token totals
            **self.utilization.stats(),
            # flight-recorder summary (ring occupancy + dump bookkeeping),
            # mirrored as dyn_flight_* worker gauges by the metrics service
            **self.flight.stats(),
        }
        # emitted count from the engine's own synchronous counter: the
        # tracker's copy updates at end-of-iteration, and a caller that just
        # consumed its stream may read stats() inside that sub-ms gap
        out["tokens_emitted_total"] = self._tokens_emitted
        # wasted-work evidence: tokens whose compute bought nothing a client
        # received (preemption recompute, rejected speculative drafts)
        spec_rejected = max(0, self._spec_drafted - self._spec_accepted)
        out["preempted_tokens_total"] = self.scheduler.preempted_tokens_total
        out["spec_rejected_tokens_total"] = spec_rejected
        out["wasted_tokens_total"] = (
            self.scheduler.preempted_tokens_total + spec_rejected
        )
        if self.host_tier is not None:
            out.update(self.host_tier.stats())
            out["offload_tiers"] = self.host_tier.tiers_snapshot()
        if self.prefetch_pager is not None:
            out.update(self.prefetch_pager.stats())
        out.update(self._kernel_work)
        wp = self.allocator.window_pool
        if wp is not None:
            out["window_pool_blocks_in_use"] = wp.used_blocks
            out["window_pool_blocks_total"] = wp.num_blocks
            out["window_blocks_released_total"] = wp.released_behind_total
        if "moe_stats" in self.cache:
            out.update(self._moe_stats())
        out["phase_ms"] = self.loop_account.snapshot()
        # flat, for a reader of two counters: the `post` phase and the part
        # of it that hands tokens to the event loop
        out["engine_post_time_total_s"] = self.loop_account.rows["post"][0]
        out["engine_post_emit_time_total_s"] = self._emit_row[0]
        out["engine_post_publish_time_total_s"] = self._publish_row[0]
        # request-path spans of this process, aggregated (count / total /
        # max / duration histogram per component and name): recorder.py
        out["spans"] = get_recorder().aggregate()
        return out

    def _moe_stats(self) -> dict:
        """The expert layers' counters as taken so far (a step or two
        behind the device) and the grouped products' work they imply: 2 x 3
        x hidden x expert width operations a row held; bytes = the banks of
        the experts TOUCHED (each read once a chunk of the layer's walk that
        visits it) and every row in and out of the three products."""
        cfg = self.config.model
        totals = dict(zip(MOE_STAT_KEYS, self._moe_totals))
        h, mi = cfg.hidden_size, cfg.moe_intermediate_size
        rows = totals["moe_assignments_held_total"]
        item = jnp.dtype(cfg.dtype).itemsize
        totals["moe_gmm_flops_total"] = 2 * 3 * h * mi * rows
        totals["moe_gmm_bytes_total"] = item * (
            totals["moe_experts_touched_total"] * 3 * h * mi
            + rows * 3 * (h + mi)
        )
        return totals

    # -- device thread -----------------------------------------------------
    def _device_loop(self) -> None:
        logger.info(
            "engine loop started (max_len=%d blocks=%d bs=%d buckets=%s)",
            self.max_len, self.config.num_blocks, self.config.max_batch_size, self.buckets,
        )
        self.loop_account.loop_started()
        while not self._stop:
            try:
                # chaos seam: an injected step failure exercises the loop's
                # keep-alive catch below (thread survives, requests continue)
                FAULTS.check(ENGINE_STEP)
                # evictions queued by asyncio-thread mutators (disagg
                # reserve_blocks) offload here, before anything can write
                # into the evicted blocks
                self.allocator.flush_offloads()
                self._drain_submissions()
                if self.prefetch_pager is not None and self.prefetch_pager.has_work():
                    # page hinted blocks up-tier between steps: a bounded
                    # slice when serving (never stalls the batch), full
                    # throttle when idle.  Progress while idle loops again
                    # immediately — an idle engine's job is to page.
                    progress = self._run_prefetch(
                        idle=not self.scheduler.has_work()
                    )
                    if progress and not self.scheduler.has_work():
                        self.loop_account.idle()
                        continue
                if not self.scheduler.has_work():
                    self.loop_account.idle()
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
                    continue
                t_step, emitted_before = self._begin_step()
                decision = self.scheduler.schedule()
                if not (self.unified_batch and self._maybe_run_unified(decision)):
                    self._run_split_step(decision)
                self._end_step(t_step, emitted_before)
            except Exception as exc:  # noqa: BLE001 — scheduler-level bug:
                # keep the thread alive (callers would hang forever), don't
                # hot-spin
                self._phase(None)
                self.loop_account.abandon_step()
                logger.exception("engine step failed")
                if self.flight.enabled:
                    self.flight.record_event(
                        "step_error", error=f"{type(exc).__name__}: {exc}"
                    )
                    self.flight.maybe_dump("step_error")
                time.sleep(0.1)
        # shutdown with a window in flight: retire it so already-computed
        # tokens reach their streams instead of vanishing with the thread
        try:
            self._sync_pipeline()
        except Exception:  # noqa: BLE001
            logger.exception("pipeline drain at shutdown failed")
        self._phase(None)

    def _begin_step(self) -> tuple[float, int]:
        """Open one iteration of the step loop: reset the per-step scratch
        and start the `schedule` phase (its part `admit`: the scheduler's
        decision and the route to a step program)."""
        t_step = self.loop_account.begin_step()
        self._step_prefill_tokens = 0
        self._step_decode_tokens = 0
        self._step_attn_ctx = 0
        self._step_weight_streams = 0.0
        self._step_lane_steps = 0
        self._step_waited_kind = self._step_dispatched_kind = None
        self._step_waited_samples = self._step_dispatched_samples = False
        self._phase("schedule")
        self._part("admit")
        return t_step, self._tokens_emitted

    def _end_step(self, t_step: float, emitted_before: int) -> None:
        """Close the iteration: ONE record of it, handed to all three
        recorders.  Its time is booked to the window the DEVICE was
        executing: the one this iteration waited for (under overlap, the
        previous iteration's), else the one it dispatched; an iteration
        that served no window carried no prompt token."""
        self._phase(None)
        self._iterations += 1
        acct = self.loop_account
        step_duration_s = acct.end_step() - t_step
        if step_duration_s > 1.0:
            # seconds-long iterations are compiles or giant windows;
            # either way the operator wants to know which
            logger.info(
                "slow step %d: %.1fs (prefill_tokens=%d decode_tokens=%d "
                "compiles so far=%d)", self._iterations, step_duration_s,
                self._step_prefill_tokens, self._step_decode_tokens,
                compile_counts()["compiles_total"],
            )
        rec = StepRecord(
            iteration=self._iterations,
            kind=(self._step_waited_kind or self._step_dispatched_kind
                  or KIND_DECODE),
            duration_s=step_duration_s,
            readback_wait_s=acct.step_readback_s,
            num_running=self.scheduler.num_running,
            num_waiting=self.scheduler.num_waiting,
            kv_active_blocks=self.allocator.used_blocks,
            kv_total_blocks=self.allocator.num_blocks,
            prefill_tokens=self._step_prefill_tokens,
            decode_tokens=self._step_decode_tokens,
            decode_lane_steps=self._step_lane_steps,
            attn_ctx_tokens=self._step_attn_ctx,
            weight_streams=self._step_weight_streams,
            emitted_tokens=self._tokens_emitted - emitted_before,
            sample_sort_skipped=not (
                self._step_waited_samples if self._step_waited_kind
                else self._step_dispatched_samples
            ),
            starved_s=acct.step_starved_s,
            starved_slack_s=acct.step_slack_s,
            starved_dispatches=acct.step_starved_dispatches,
            offcpu_s=acct.step_offcpu_s,
            no_work_s=acct.step_no_work_s,
        )
        self.step_telemetry.observe(rec)
        if rec.num_running:
            # a busy step's length, so a window's tail of steps is the
            # difference of two histograms (no per-step series kept)
            get_recorder().observe(
                "engine.step." + rec.kind, step_duration_s, component="engine")
        self.utilization.observe(rec)
        if not self.flight.enabled:
            return
        preempted = self.scheduler.preemptions_total
        if preempted > self._flight_preemptions:
            self.flight.record_event(
                "preemption",
                count=preempted - self._flight_preemptions,
                total=preempted,
            )
            self._flight_preemptions = preempted
        # this step's own utilization (the rolling rates are computed when
        # stats() asks, not per step)
        self.flight.record_step(
            iteration=rec.iteration,
            kind=rec.kind,
            num_running=rec.num_running,
            num_waiting=rec.num_waiting,
            kv_usage=self.allocator.usage,
            prefill_tokens=rec.prefill_tokens,
            decode_tokens=rec.decode_tokens,
            emitted_tokens=rec.emitted_tokens,
            step_duration_s=rec.duration_s,
            readback_wait_s=rec.readback_wait_s,
            starved_s=rec.starved_s,
            offcpu_s=rec.offcpu_s,
            no_work_s=rec.no_work_s,
            mfu=self.utilization.step_mfu(rec),
            goodput_tok_s=(
                rec.emitted_tokens / rec.duration_s
                if rec.duration_s > 0.0 else 0.0
            ),
        )

    def _run_split_step(self, decision) -> None:
        """The split prefill/decode step: one dispatch per prefill window
        plus one batched decode dispatch — the engine's historical path,
        kept whole as the unified step's fallback."""
        for seq in decision.prefills:
            if seq.status == SeqStatus.FINISHED:
                continue  # failed/aborted before this step got to it
            self._maybe_record_queue_span(seq)
            t_prefill = time.time()
            try:
                self._run_prefill(seq)
            except Exception as exc:  # noqa: BLE001 — fail THIS
                # sequence (free blocks, resolve its caller) and
                # keep serving; retrying would hot-spin on
                # deterministic failures and skipping the rest of
                # the batch would leave restore plans unexecuted
                logger.exception("prefill failed for %s", seq.seq_id)
                self._close_prefill_spans(
                    [self._open_prefill_span(seq, t_prefill)], status="error"
                )
                self._fail_sequence(seq, exc)
            else:
                span = self._open_prefill_span(seq, t_prefill)
                if seq.status is SeqStatus.PREFILLING:
                    # intermediate chunk: dispatched, nothing waited for it
                    self._unwaited_prefills.append(span)
                else:
                    self._close_prefill_spans([span])
        decodes = [
            s for s in self.scheduler.running if s.status == SeqStatus.RUNNING
        ]
        if decodes:
            try:
                self._run_decode(decodes)
            except Exception as exc:  # noqa: BLE001
                logger.exception("decode step failed")
                # a poisoned in-flight window must not feed the next
                # dispatch (and _fail_sequence is about to free the
                # failing lanes' blocks)
                self._abandon_pipeline(decodes)
                for seq in decodes:
                    if seq.status == SeqStatus.RUNNING:
                        self._fail_sequence(seq, exc)
        elif self._inflight is not None:
            # nothing decodable this iteration (every lane finished,
            # is prefilling, or was preempted) while a window is
            # still in flight: retire it so its tokens emit and
            # deferred finishes release their lanes/blocks
            self._sync_pipeline()

    # -- ragged unified-batch step ----------------------------------------
    def _unified_skip(self, reason: str, detail: str | None = None) -> None:
        """Record a unified-batch fallback under a short reason slug
        (stats() → dyn_worker_unified_fallbacks_total{reason}) and log it
        once per engine per reason — the per-step route checks run every
        scheduler iteration, so unconditional logging would spam."""
        self._unified_fallbacks[reason] = (
            self._unified_fallbacks.get(reason, 0) + 1
        )
        if self.flight.enabled:
            self.flight.record_event("unified_fallback", reason=reason)
        if reason not in self._unified_fallback_logged:
            self._unified_fallback_logged.add(reason)
            logger.info(
                "unified batch fallback [%s]: %s", reason, detail or
                "window served by the split step"
            )

    def _maybe_run_unified(self, decision) -> bool:
        """Serve this iteration as ONE ragged dispatch mixing prefill
        chunks and decode tokens.  Returns False when the step needs the
        split path (which then runs unchanged): guided lanes, multimodal or
        disagg-prefill sequences, token batches past the largest compile
        bucket, or OOM requiring the preempting synchronous machinery."""
        prefills = list(decision.prefills)
        decodes = [
            s for s in self.scheduler.running
            if s.status == SeqStatus.RUNNING and s not in prefills
        ]
        if not prefills and not decodes:
            return False  # idle / window-retire-only: split loop handles
        for seq in prefills:
            if seq.prefill_only or seq.mm_embeds is not None:
                # disagg extract / multimodal keep their routes
                self._unified_skip("disagg_or_mm")
                return False
            if seq.guided is not None:
                self._unified_skip("guided")
                return False
        for seq in decodes:
            if seq.guided is not None:
                self._unified_skip("guided")
                return False

        spans: list[tuple[Sequence, int, int]] = []
        for seq in prefills:
            n = len(seq.all_token_ids)
            start = max(seq.prefilled_tokens, seq.cached_tokens)
            end = min(seq.chunk_target, n) if (
                self.chunk_tokens is not None and seq.chunk_target
            ) else n
            if end <= start:
                # degenerate window: split path owns it
                self._unified_skip("degenerate_span")
                return False
            spans.append((seq, start, end))
        if not spans:
            # decode-only iterations keep the exact-lane decode program: the
            # unified window's bucketed token axis would pad pure decode
            # upward for nothing.  Unified earns its keep exactly when a
            # prefill span shares the window — the iterations where the
            # split path pays a second dispatch and (under overlap) an
            # admission drain.  Windows from either program chain through
            # the same feedback array, so alternating costs nothing.
            # (Deliberately uncounted: this is the designed route, not a
            # fallback.)
            return False
        # decode lanes and spans both pack DENSELY — the kernel routes per
        # row, not per block — so every token costs exactly one flat slot
        total = len(decodes) + sum(end - start for _, start, end in spans)
        if total > self.buckets[-1]:
            self._unified_skip("bucket_overflow")
            return False
        bucket = self._bucket_len(total)
        unseeded = sum(
            1 for seq, start, _ in spans if start == seq.cached_tokens
        )
        if unseeded > self._unified_seed_slots:
            self._unified_skip("seed_overflow")
            return False

        # per-window overlap gate, same rule as _overlap_ok: top_logprobs
        # lanes ship K-wide rows that belong on the synchronous path
        overlap = self.decode_overlap and not any(
            s.request.sampling.top_logprobs > 0 for s in prefills + decodes
        )
        try:
            return self._run_unified(spans, decodes, bucket, overlap)
        except Exception as exc:  # noqa: BLE001
            logger.exception("unified step failed")
            self._abandon_pipeline(prefills + decodes)
            for seq in prefills + decodes:
                if seq.status in (SeqStatus.PREFILLING, SeqStatus.RUNNING):
                    self._fail_sequence(seq, exc)
            return True  # the step was consumed (by failing its batch)

    def _run_unified(
        self,
        spans: list[tuple[Sequence, int, int]],
        decodes: list[Sequence],
        bucket: int,
        overlap: bool,
    ) -> bool:
        """Build the ragged batch, dispatch once, then either read back
        synchronously or put the window in flight (overlap).  A newly
        admitted sequence needs NO pipeline drain here: its prefill tokens
        come from the host while resident decode lanes keep reading the
        previous window's on-device feedback."""
        lanes = self.config.max_batch_size
        bs = self.config.block_size
        oob = self.config.num_blocks * bs
        vocab = self.config.model.vocab_size
        prev = self._inflight

        # preempted-then-readmitted prefix restores run exactly like
        # _run_prefill's, but a failed restore fails ONLY its sequence (the
        # split path's per-sequence error contract — one bad host-tier read
        # must not take down every request in the window).  The plan goes
        # back first so free_sequence can unregister the garbage landing
        # blocks and release the host pins.
        failed: list[Sequence] = []
        for seq, _, _ in spans:
            restore = self.allocator.take_restore_plan(seq.seq_id)
            if restore:
                try:
                    self._restore_blocks(restore)
                except Exception as exc:  # noqa: BLE001
                    logger.exception("prefix restore failed for %s", seq.seq_id)
                    self.allocator.put_back_restore_plan(seq.seq_id, restore)
                    self._fail_sequence(seq, exc)
                    failed.append(seq)
        if failed:
            spans = [(s, a, b) for s, a, b in spans if s not in failed]
            if not spans:
                return False  # decode-only now: the split path serves it

        # decode slot growth: overlap allocates at the DEVICE context and
        # never preempts (a lagged window may still write into a victim's
        # blocks) — on OOM the pipeline drains and the preempting split
        # path serves this iteration; sync mode drains first and preempts
        # like the plain decode path.
        slots: dict[str, int] = {}
        self._part("slots")
        if overlap:
            for seq in decodes:
                dev_ctx = min(
                    seq.context_len + seq.inflight_tokens, self.max_len
                )
                slot = self.scheduler.try_slots_at(
                    seq, dev_ctx, 1, max_pos=self.max_len - 1
                )
                if slot is None:
                    self._unified_skip("slot_oom")
                    self._sync_pipeline()
                    return False
                slots[seq.seq_id] = slot
        else:
            self._sync_pipeline()
            for seq in list(decodes):
                if seq.status != SeqStatus.RUNNING:
                    continue  # preempted as a victim earlier in this loop
                slot = self.scheduler.ensure_slots(
                    seq, 1, max_pos=self.max_len - 1
                )
                if slot is None:
                    self.scheduler.preempt(seq)
                    continue
                slots[seq.seq_id] = slot
            decodes = [s for s in decodes if s.status == SeqStatus.RUNNING]
            # ensure_slots may have victimized a PREFILLING span owner
            spans = [
                (s, a, b) for s, a, b in spans
                if s.status in (SeqStatus.PREFILLING, SeqStatus.RUNNING)
            ]
            if not decodes and not spans:
                return True  # everything preempted: step consumed

        self._part("build")
        tb = self._tb_for(bucket)
        token_ids = np.zeros((bucket,), np.int32)
        token_pos = np.full((bucket,), -1, np.int32)
        token_slot = np.full((bucket,), oob, np.int32)
        token_lane = np.full((bucket,), lanes, np.int32)
        use_fb = np.zeros((bucket,), bool)
        context_lens = np.zeros((lanes,), np.int32)
        sample_rows = np.zeros((lanes,), np.int32)
        sample_gate = np.zeros((lanes,), np.int32)
        nseed = self._unified_seed_slots
        # the [nseed, vocab] seed rows only exist on windows that actually
        # admit (the rare case); steady-state windows reuse one resident
        # no-op scatter instead of re-uploading ~vocab-sized zeros
        need_seed = any(
            start == seq.cached_tokens for seq, start, _ in spans
        )
        seed_lanes = seed_prompt = seed_gen = None
        if need_seed:
            seed_lanes = np.full((nseed,), lanes, np.int32)
            seed_prompt = np.zeros((nseed, vocab), np.int32)
            seed_gen = np.zeros((nseed, vocab), np.int32)

        emit_seqs: list[Sequence] = []
        cursor = 0
        for seq in decodes:
            self._prep_decode_seq(seq)
            lane = seq.lane
            dev_ctx = min(
                seq.context_len + (seq.inflight_tokens if overlap else 0),
                self.max_len,
            )
            pos = dev_ctx - 1
            token_ids[cursor] = seq.last_token_id
            # the host's last token lags the device while a window holding
            # this lane is in flight: read the feedback array instead
            use_fb[cursor] = overlap and seq.inflight_tokens > 0
            token_pos[cursor] = pos
            token_slot[cursor] = slots[seq.seq_id]
            token_lane[cursor] = lane
            context_lens[lane] = dev_ctx
            sample_rows[lane] = cursor
            sample_gate[lane] = 1
            emit_seqs.append(seq)
            cursor += 1  # packed decode lanes: one flat slot per lane
        si = 0
        for seq, start, end in spans:
            self._maybe_record_queue_span(seq)
            lane = seq.lane
            tokens = seq.all_token_ids
            n = len(tokens)
            span = end - start
            blocks = np.asarray(
                self.allocator.block_ids(seq.seq_id), np.int32
            )
            token_ids[cursor : cursor + span] = tokens[start:end]
            ppos = np.arange(start, end, dtype=np.int32)
            token_pos[cursor : cursor + span] = ppos
            token_slot[cursor : cursor + span] = (
                blocks[ppos // bs] * bs + ppos % bs
            )
            token_lane[cursor : cursor + span] = lane
            context_lens[lane] = end
            sample_rows[lane] = cursor + span - 1
            final = end >= n
            sample_gate[lane] = 1 if final else 0
            if start == seq.cached_tokens:
                # first window of this admission: (re)seed lane sampling
                # state exactly like the split prefill programs do
                seed_lanes[si] = lane
                seed_prompt[si] = self._count_row(seq.request.token_ids)
                seed_gen[si] = self._count_row(seq.output_ids)
                si += 1
                self._seed_lane_key(seq)
                seq.sampling_seeded = True
            if final:
                emit_seqs.append(seq)
            cursor += span

        self._part("tables")
        tables = self._decode_tables(decodes + [s for s, _, _ in spans])
        # per token block, the spans of pages its lanes can see: the ragged
        # kernel loops over exactly those (it resolves physical pages from
        # the block tables itself), so every window of this bucket shares
        # ONE compiled program regardless of batch composition.
        pallas = self.attention_impl.startswith("pallas")
        if pallas:
            from dynamo_tpu.ops.pallas import kv_step_pages, pack_spans

            self._phase("pack")
            self._part("spans")
            # a family that attends its window's own rows itself has only
            # the pages resident BEFORE the window walked
            walk_pos = token_pos
            if self.family.unified_attends_window:
                from dynamo_tpu.ops.pallas.mla_attention import last_resident_pos

                walk_pos = last_resident_pos(token_lane, token_pos, lanes)
            page_meta = pack_spans(
                token_lane, walk_pos, lanes=lanes, tb_tokens=tb,
                block_size=bs, sliding_window=self._sliding_window,
            )
            if self._window_blocks:
                # the window layers walk their own spans: the same lanes,
                # from the page their window starts in
                self._part("window_spans")
                window_meta = pack_spans(
                    token_lane, token_pos, lanes=lanes, tb_tokens=tb,
                    block_size=bs, sliding_window=self._attn_layers[2],
                )
        else:
            # the XLA twin routes per token off token_lane/token_pos and
            # never reads the spans: ship fixed-shape zeros
            flat = np.zeros((bucket,), np.int32)
            page_meta = window_meta = (
                flat, flat, flat, np.zeros((bucket // tb,), np.int32)
            )
        self._phase("upload")
        self._part("sampling")
        sampling_tail = self._device_sampling_tail(emit_seqs, lanes)
        self._part("arrays")
        if overlap and prev is not None:
            feedback_in = prev.feedback
        else:
            if self._fb_zero is None:
                self._fb_zero = jnp.zeros((lanes,), jnp.int32)
            feedback_in = self._fb_zero
        if need_seed:
            seed_args = (
                jnp.asarray(seed_lanes), jnp.asarray(seed_prompt),
                jnp.asarray(seed_gen),
            )
        else:
            if self._seed_none is None:
                self._seed_none = (
                    jnp.full((nseed,), lanes, jnp.int32),  # OOB → drop
                    jnp.zeros((nseed, vocab), jnp.int32),
                    jnp.zeros((nseed, vocab), jnp.int32),
                )
            seed_args = self._seed_none
        page_args = [jnp.asarray(a) for a in page_meta]
        if self._window_blocks:
            page_args = [
                KvPools(a, jnp.asarray(b)) for a, b in zip(page_args, window_meta)
            ]
        args = (
            jnp.asarray(token_ids), feedback_in, jnp.asarray(use_fb),
            tables, jnp.asarray(context_lens), jnp.asarray(token_pos),
            jnp.asarray(token_slot), jnp.asarray(token_lane),
            *page_args,
            jnp.asarray(sample_rows), jnp.asarray(sample_gate),
            *seed_args,
        )
        # a unified window always carries a span (decode-only iterations
        # keep the decode program), so it is a prompt window
        live_pages = int(page_meta[2].sum()) if pallas else 0
        t_prefill = time.time()
        self._phase("dispatch", kind=KIND_PROMPT, tokens=cursor, live_pages=live_pages)
        tokens, lps, tkvs, tkis, self.cache, self._gen_counts, self._prompt_counts = self._jit_unified(
            self.params, self.cache, self._gen_counts, self._prompt_counts,
            *args, *sampling_tail, self.cos, self.sin,
        )
        self._phase("post")
        self._step_dispatched_kind = KIND_PROMPT
        self._after_dispatch(
            [(s, int(context_lens[s.lane])) for s in decodes]
            + [(s, end) for s, _, end in spans]
        )

        # host bookkeeping (device-ordered: any later program — including
        # another engine's extract over published blocks — sees the writes)
        opened = []  # this window's engine.prefill spans: closed by its wait
        ragged_ctx = 0  # positions the ragged kernel attends (window-clipped)
        for seq, start, end in spans:
            seq.prefilled_tokens = end
            self._step_prefill_tokens += end - start
            full, seen = self._attended_ctx(start, end)
            self._step_attn_ctx += full
            ragged_ctx += seen
            all_tokens = seq.all_token_ids
            if end >= len(all_tokens):
                if seq.status == SeqStatus.PREFILLING:
                    seq.status = SeqStatus.RUNNING
                self._publish_stored(seq.seq_id, all_tokens)
            else:
                self._publish_stored(seq.seq_id, all_tokens[:end])
            opened.append(self._open_prefill_span(seq, t_prefill))
        self._step_decode_tokens += len(decodes)
        n_full, n_window, w = self._attn_layers
        full_ctx = sum(self._attended_ctx(a, b)[0] for _, a, b in spans)
        for s in decodes:
            ctx = int(context_lens[s.lane])
            self._step_attn_ctx += ctx
            full_ctx += ctx
            ragged_ctx += ctx if w is None else min(ctx, w)
        self._step_weight_streams += 1
        self._unified_windows += 1
        self._book_prompt_window(
            len(decodes) + sum(end - start for _, start, end in spans), bucket
        )
        if decodes:
            self._decode_steps_total += 1
            self._step_lane_steps += len(decodes)
        if pallas:
            # the kernel's work, from the spans just built: every page of a
            # span is copied once, into a KV step of its span (the span's
            # last step partly empty), and the loop runs no other step
            cost = self.utilization.cost
            work = self._kernel_work
            launched = page_meta[3] > 0     # token blocks with a KV step
            kv_steps = int(page_meta[3].sum())
            work["ragged_live_pages_total"] += live_pages
            work["ragged_page_slots_total"] += kv_steps * kv_step_pages(bs)
            work["ragged_kv_steps_total"] += kv_steps
            work["ragged_token_blocks_total"] += int(launched.sum())
            work["ragged_live_rows_total"] += int(
                (np.repeat(launched, tb) & (token_pos >= 0)).sum()
            )
            # a layer's products over the context IT attends (a window
            # layer's in-window work only) and the pages IT copies
            layers_n = n_full + n_window
            window_pages = int(window_meta[2].sum()) if self._window_blocks else live_pages
            own_flops = own_bytes = 0
            walked_ctx = full_ctx
            if self.family.unified_attends_window:
                # the keys that are rows of this window (a span's triangle;
                # a decode row itself) went decompressed, at that launch's
                # rate and traffic; the rest, resident, through the walk
                own_ctx = len(decodes) + sum(
                    (b - a) * (b - a + 1) // 2 for _, a, b in spans)
                work["mla_window_ctx_total"] += own_ctx
                work["mla_attended_ctx_total"] += full_ctx
                own_flops = own_ctx * cost.window_attn_flops_per_ctx_token
                own_bytes = cursor * cost.window_attn_bytes_per_row
                walked_ctx = full_ctx - own_ctx
            work["ragged_attn_flops_total"] += own_flops + (
                cost.attn_flops(walked_ctx) * n_full
                + cost.attn_flops(ragged_ctx) * n_window
            ) // layers_n
            work["ragged_kv_read_bytes_total"] += own_bytes + (
                (live_pages * n_full + window_pages * n_window)
                * bs * cost.kv_bytes_per_token // layers_n
            )
            if self._window_blocks:
                work["window_pages_visited_total"] += window_pages
                work["window_pages_full_total"] += live_pages
            work["ragged_cross_kv_read_bytes_total"] += (
                live_pages * cost.cross_layers * bs * cost.kv_bytes_per_token // layers_n
            )
        self._book_state_rows(
            len(decodes), sum(end - start for _, start, end in spans), len(spans)
        )

        if not overlap:
            _, opened, samples = self._take_unwaited(KIND_PROMPT, opened)
            self._phase("readback", kind=KIND_PROMPT)
            tokens_h = np.asarray(tokens)
            lps_h = np.asarray(lps)
            want_top = any(
                s.request.sampling.top_logprobs > 0 for s in emit_seqs
            )
            tkv_h = np.asarray(tkvs) if want_top else None
            tki_h = np.asarray(tkis) if want_top else None
            self._phase("post")
            self._note_wait(KIND_PROMPT, opened, samples)
            self._sync_windows += 1
            self._part("tokens")
            for seq in emit_seqs:
                if seq.status != SeqStatus.RUNNING:
                    continue
                lane = seq.lane
                want = seq.request.sampling.top_logprobs > 0
                self._process_token(
                    seq, int(tokens_h[lane]), float(lps_h[lane]),
                    top=(tkv_h[lane], tki_h[lane]) if want else None,
                )
            return True

        # overlap: the window retires one iteration from now, while the
        # NEXT window (possibly carrying a fresh admission) computes
        for arr in (tokens, lps):
            arr.copy_to_host_async()
        for seq in emit_seqs:
            seq.inflight_tokens += 1
        if emit_seqs:
            _, opened, samples = self._take_unwaited(KIND_PROMPT, opened)
            self._inflight = _InflightWindow(
                tokens=tokens, lps=lps, feedback=tokens,
                active=emit_seqs, lane_ids=[s.lane for s in emit_seqs],
                steps=1, kind=KIND_PROMPT, samples=samples, prefills=opened,
            )
        else:
            # a chunk-only window samples nothing worth retiring: nothing
            # goes in flight (KV writes are device-ordered regardless), and
            # the next window's wait covers this one's time on the device
            self._inflight = None
            self._unwaited_prefills += opened
        if prev is not None:
            self._retire_window(prev)
        return True

    def _open_prefill_span(self, seq: Sequence, start_ts: float) -> tuple:
        """One ``engine.prefill`` span per prefill window (chunked prefills
        show every chunk), opened at dispatch with what only that instant
        knows.  It closes when the host has waited for the window
        (``_close_prefill_spans``): dispatch is asynchronous, so closing it
        here would measure the dispatch, not the window."""
        # intermediate chunks leave the sequence PREFILLING; the final
        # window flips it to RUNNING (or FINISHED for prefill_only)
        final = seq.status is not SeqStatus.PREFILLING
        return seq, start_ts, final, {
            "prefilled_tokens": seq.prefilled_tokens,
            "cached_tokens": seq.cached_tokens,
        }

    def _close_prefill_spans(self, spans: list, status: str = "ok") -> None:
        """The host has waited for these prefill windows: record them.  The
        window that produced the first token carries the engine-side TTFT
        (arrival → first sample on the host)."""
        now = time.time()
        for seq, start_ts, final, attrs in spans:
            if seq.trace is None:
                continue
            # a preemption-recompute prefill is not a first-token event:
            # TTFT attaches exactly once per request, on the window that
            # sampled the first token
            if final and status == "ok" and not seq.ttft_recorded:
                seq.ttft_recorded = True
                attrs["ttft_s"] = max(0.0, now - seq.arrival_ts)
            get_recorder().record(
                "engine.prefill", seq.trace, start_ts, now,
                component="engine", status=status, attrs=attrs,
            )

    def _take_unwaited(
        self, own: str, prefills: list | None = None
    ) -> tuple[str, list, bool]:
        """Kind and open prefill spans of the window being dispatched, and
        whether a lane of it samples, together with the prompt work
        dispatched before it that nothing waits for (a chunk-only unified
        window, a split prefill's intermediate chunk).  On the device that
        work runs first, so the wait for THIS window covers its time: the
        window inherits the kind ``prompt``, the spans and a lane that
        samples."""
        pending, self._unwaited_prefills = self._unwaited_prefills, []
        samples, self._packed_samples = self._packed_samples, False
        if pending:
            return KIND_PROMPT, pending + (prefills or []), samples
        return own, prefills or [], samples

    def _note_wait(self, kind: str, prefills: list, samples: bool) -> None:
        """The host has blocked on a window of ``kind``: the heaviest
        window waited for names the iteration (which sorted a vocabulary if
        any window it waited for ``samples``), and the prefill windows the
        wait covered close their spans."""
        if self._step_waited_kind != KIND_PROMPT:
            self._step_waited_kind = kind
        self._step_waited_samples |= samples
        self._close_prefill_spans(prefills)

    def _on_preempt(self, seq: Sequence) -> None:
        """Scheduler preemption hook: close the victim's decode span (the
        wait + recompute after preemption must not be billed as decode
        time) and re-arm the queue span so the re-admission wait records as
        a second engine.queue span starting at the preemption instant.
        ``arrival_ts`` is untouched — TTFT always measures from request
        arrival, even when the first token lands after a preemption."""
        self._record_decode_span(seq, status="preempted")
        if seq.trace is not None:
            seq.queue_span_recorded = False
            seq.queue_start_ts = time.time()

    def _maybe_record_queue_span(self, seq: Sequence) -> None:
        """One engine.queue span per admission: submission (or preemption
        re-queue) → first time the scheduler put the sequence on device.
        Called at prefill scheduling AND at decode start — the latter
        covers remote-prefilled sequences, which the scheduler admits
        straight to RUNNING without a local prefill pass."""
        if seq.trace is None or seq.queue_span_recorded:
            return
        seq.queue_span_recorded = True
        get_recorder().record(
            "engine.queue", seq.trace, seq.queue_start_ts or seq.arrival_ts,
            time.time(), component="engine",
            attrs={"prompt_tokens": seq.prompt_len,
                   "cached_tokens": seq.cached_tokens},
        )

    def _record_decode_span(self, seq: Sequence, status: str = "ok") -> None:
        """Close the sequence's decode span (first decode step → finish)."""
        if seq.trace is None or seq.decode_start_ts == 0.0:
            return
        get_recorder().record(
            "engine.decode", seq.trace, seq.decode_start_ts, time.time(),
            component="engine", status=status,
            attrs={"tokens_out": len(seq.output_ids)},
        )
        seq.decode_start_ts = 0.0

    def _fail_sequence(self, seq: Sequence, exc: BaseException) -> None:
        """Terminate one sequence on an engine-side error: free its
        resources and resolve its caller with the failure."""
        self._record_decode_span(seq, status="error")
        self.scheduler.finish(seq)
        if seq.on_prefill_done:
            seq.on_prefill_done(exc)
        elif seq.emit:
            seq.emit([], FinishReason.ERROR, f"{type(exc).__name__}: {exc}")

    def _drain_submissions(self) -> None:
        while True:
            try:
                op, seq = self._submit_q.get_nowait()
            except thread_queue.Empty:
                return
            if op == "add":
                # read BEFORE add: after add the new sequence itself makes
                # the scheduler busy
                backlog = self.scheduler.has_work()
                self.scheduler.add(seq)
                if (
                    self.prefetch_pager is not None
                    and self.prefix_caching
                    and seq.mm_embeds is None
                    and not seq.remote_prefilled
                    and (
                        backlog
                        or not self.allocator.can_allocate(
                            len(seq.request.token_ids)
                        )
                    )
                ):
                    # queue-hint: while this sequence waits for admission
                    # (budget/lane/blocks), its offloaded prefix pages in
                    # behind the current batch — the page-in that demand
                    # paging would have paid inside allocate_sequence.  An
                    # idle engine with room admits the sequence this same
                    # iteration, so hashing the prompt here (device
                    # thread) would be pure duplicate work — skip it.
                    hashes = compute_block_hashes(
                        seq.request.token_ids, self.config.block_size
                    )
                    if hashes:
                        self.prefetch_pager.submit(hashes, source="queued")
            elif op == "abort":
                if seq.status == SeqStatus.RUNNING:
                    # abort frees the lane's blocks: drain the decode
                    # pipeline first so no lagged in-flight step writes
                    # into storage the allocator is about to reclaim.
                    # (Only RUNNING lanes can be in a window — cancelling
                    # a still-queued request must not stall the pipeline.)
                    self._sync_pipeline()
                if seq.status != SeqStatus.FINISHED:
                    self._record_decode_span(seq, status="cancelled")
                    self.scheduler.abort(seq)
                    seq.status = SeqStatus.FINISHED
                    if seq.emit:
                        seq.emit([], FinishReason.CANCELLED)
            elif op == "warm_verify":
                done = seq  # payload: completion callback (exc | None)
                try:
                    self._warm_verify_step()
                except Exception as exc:  # noqa: BLE001 — surface to the
                    # awaiting warmup() call; a swallowed failure here could
                    # hide a donation-consumed cache behind a "successful"
                    # warmup
                    logger.exception("verify warmup failed")
                    done(exc)
                else:
                    done(None)
            elif op == "clear_kv":
                done = seq  # payload is the completion callback
                # admin flush: retire the in-flight window first so deferred
                # finishes release their blocks before the count is judged
                # (warmup asserts a clean pool right after this resolves)
                self._sync_pipeline()
                cleared = self.allocator.clear_published()
                if self.host_tier is not None:
                    self.host_tier.clear()
                logger.info("cleared %d published kv block hashes", cleared)
                if done is not None:
                    done()
            elif op == "inject":
                # evictions queued by the reservation for THIS inject (or any
                # other asyncio-thread mutator) must offload before the
                # inject overwrites their blocks — the loop-top flush does
                # not cover reservations racing into the same drain pass
                self.allocator.flush_offloads()
                block_ids, blocks, done = seq  # payload tuple
                n = len(block_ids)
                nb = self._table_len(n)  # bucketed, not max-padded
                ids = np.zeros((nb,), np.int32)
                ids[:n] = block_ids
                # pad each leaf to the bucketed id length; leaf geometry
                # comes from the live cache pytree, so asymmetric layouts
                # (DeepSeek MLA latent/rope widths) shape correctly.  Device
                # arrays (same-process transfer) pad on device — no host hop
                def pad(leaf, incoming):
                    if isinstance(incoming, jax.Array):
                        if incoming.devices() <= leaf.devices():
                            out = jnp.zeros(
                                (leaf.shape[0], nb, *leaf.shape[2:]),
                                incoming.dtype,
                            )
                            return out.at[:, :n].set(incoming)
                        # same-process transfer from an engine on a
                        # DIFFERENT device partition (disagg prefill mesh →
                        # decode mesh): this engine owns placement, so hop
                        # through host and let the jit place the result on
                        # OUR devices
                        incoming = jax.device_get(incoming)
                    incoming = np.asarray(incoming)
                    out = np.zeros((leaf.shape[0], nb, *leaf.shape[2:]), incoming.dtype)
                    out[:, :n] = incoming
                    return jnp.asarray(out)

                try:
                    padded = jax.tree.map(pad, _pages(self.cache), blocks)
                    self.cache = self._jit_inject(
                        self.cache, padded, jnp.asarray(ids), jnp.int32(n)
                    )
                except Exception as exc:  # noqa: BLE001 — fail the caller,
                    # don't leave its future hanging
                    logger.exception("kv inject failed")
                    done(exc)
                else:
                    done()

    def _bucket_len(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _book_prompt_window(self, live: int, bucket: int) -> None:
        self._prompt_window_live_tokens += live
        self._prompt_window_bucket_tokens += bucket

    def _table_len(self, nblocks: int) -> int:
        """Smallest block-table compile bucket covering ``nblocks``.
        Batched ops (offload flush, transfer benchmarks) can exceed one
        sequence's table — those bucket to the next power of two."""
        for b in self._table_buckets:
            if b >= nblocks:
                return b
        n = self.max_blocks_per_seq
        while n < nblocks:
            n <<= 1
        return min(n, self.config.num_blocks)

    # -- G2 host offload ---------------------------------------------------
    def _offload_blocks(self, pairs: list[tuple[int, int]]) -> list[int]:
        """Allocator eviction hook: copy the evicted blocks' cache slices to
        the host tier in ONE bucketed gather + device→host transfer (device
        thread, before the new owners write).  Returns hashes that failed to
        offload (host tier full of pins) — those must be announced removed."""
        n = len(pairs)
        nb = self._table_len(n)
        ids = np.zeros((nb,), np.int32)
        for i, (bid, _) in enumerate(pairs):
            ids[i] = bid
        gathered = jax.tree.map(
            np.asarray, self._jit_extract(self.cache, jnp.asarray(ids))
        )
        failed: list[int] = []
        # host-LRU evictions triggered by these puts are judged AFTER the
        # whole batch: a hash evicted mid-batch may be re-inserted by a
        # later put (no event), or end up in no tier (removed event)
        self._host_evictions = []
        try:
            for i, (_, h) in enumerate(pairs):
                content = jax.tree.map(lambda a, i=i: a[:, i], gathered)
                if not self.host_tier.put(h, content):
                    failed.append(h)
            for h in self._host_evictions:
                if (
                    not self.host_tier.has(h)
                    and not self.allocator.is_registered(h)
                    and h not in failed
                ):
                    failed.append(h)
        finally:
            self._host_evictions = None
        return failed

    def _host_evicted(self, seq_hash: int) -> None:
        """Host-tier LRU eviction observer.  During an offload batch the
        verdict is deferred to the end of the batch (a later put may
        re-insert the hash); host puts only happen inside batches, but keep
        a direct-emit fallback for any other path."""
        if self._host_evictions is not None:
            self._host_evictions.append(seq_hash)
            return
        if not self.allocator.is_registered(seq_hash):
            self.allocator.emit_removed([seq_hash])

    # -- predictive prefetch execution (device thread) ---------------------
    def _run_prefetch(self, idle: bool) -> bool:
        """Drain the pager within this iteration's block budget.  Returns
        True when any block actually moved (the idle loop uses it to keep
        paging without sleeping; headroom-deferred work must NOT spin)."""
        pager = self.prefetch_pager
        # effective budget is link-priced: a tier behind ici/dcn gets a
        # smaller per-step allowance (all-local topology = full budget)
        budget = pager.effective_blocks_per_step() * (pager.idle_boost if idle else 1)
        progress = False
        moved = 0
        wall0 = time.time()
        self._phase("prefetch.page")
        while budget > 0:
            job = pager.next_job()
            if job is None:
                break
            touched, leftover = self._execute_prefetch(job.hashes, budget)
            budget -= max(touched, 1)  # an all-resident job still costs a walk
            moved += touched
            progress = progress or touched > 0
            if leftover:
                # HBM headroom exhausted or the block budget cut the chain:
                # retry the rest next round instead of dropping it, and
                # stop this round (further jobs would fare the same).  The
                # original enqueue time rides along so a hint that keeps
                # deferring past its TTL still goes stale.
                pager.requeue(leftover, enqueued=job.enqueued)
                break
        # hot-prefix pinning rides the prefetch loop (never the demand
        # path): promote + pin prefixes that keep paging back in
        pinned = self.host_tier.pin_hot()
        self._phase(None)
        if moved:
            # prefetch work is not tied to any request: spans hang off the
            # engine-lifetime prefetch root trace (one trace id per engine)
            get_recorder().record(
                "engine.prefetch", self._prefetch_trace, wall0, time.time(),
                component="engine",
                attrs={"blocks": moved, "idle": idle, "pinned": pinned},
            )
        return progress or pinned > 0

    def _execute_prefetch(
        self, hashes: list[int], budget: int
    ) -> tuple[int, list[int]]:
        """Page one hinted prefix toward HBM: walk the hash chain, promote
        disk/remote-resident blocks into the host tier (OffloadManager
        onboard path), then pre-restore host-resident blocks into device
        landing blocks drawn from the TRULY-free list under the headroom
        reservation.  Returns (blocks touched, leftover hashes the caller
        must requeue: headroom-deferred plus any chain tail the block
        budget cut off — a long prefix finishes over later iterations
        instead of losing its tail)."""
        pager = self.prefetch_pager
        touched = 0
        restore: list[int] = []
        promote: list[int] = []
        overflow: list[int] = []
        for i, h in enumerate(hashes):
            if len(restore) >= budget:
                overflow = [
                    x for x in hashes[i:]
                    if not self.allocator.is_registered(x)
                ]
                break
            if self.allocator.is_registered(h):
                continue  # already in HBM
            tier = self.host_tier.locate(h)
            if tier is None:
                break  # chain broken: content gone — deeper blocks useless
            if tier > 0:
                promote.append(h)
            restore.append(h)
        if promote:
            moved = self.host_tier.promote_to_host(promote)
            pager.record_onboarded(moved)
            touched += moved
        if not restore:
            return touched, overflow
        plan, deferred = self.allocator.prefetch_reserve(
            restore, self._prefetch_headroom_blocks
        )
        if plan:
            t0 = time.perf_counter()
            try:
                self._restore_blocks(plan)
            except Exception:  # noqa: BLE001 — prefetch is best-effort; a
                # failed speculative restore must not poison serving
                logger.exception("prefetch restore failed")
                self.allocator.abort_prefetch(plan)
                return touched, []
            cost = (time.perf_counter() - t0) / len(plan)
            self.allocator.finish_prefetch(plan)
            for h, _bid in plan:
                pager.record_restored(h, cost)
            touched += len(plan)
        return touched, deferred + overflow

    def _restore_blocks(self, plan: list[tuple[int, int]]) -> None:
        """Scatter pinned host blocks into their device landing blocks (one
        batched inject, id array bucketed)."""
        n = len(plan)
        nb = self._table_len(n)
        ids = np.full((nb,), self.config.num_blocks, np.int32)
        staged = {
            k: np.zeros((v.shape[0], nb, *v.shape[2:]), np.dtype(v.dtype))
            for k, v in _pages(self.cache).items()
        }
        # one batched read per tier (a G4-resident prefix costs one DCN
        # round trip for the whole plan, not one per block)
        contents = self.host_tier.read_pinned_many([h for h, _ in plan])
        for i, (h, bid) in enumerate(plan):
            content = contents.get(h)
            assert content is not None, "pinned host block vanished"
            ids[i] = bid
            for name, arr in content.items():
                staged[name][:, i] = arr
        self.cache = self._jit_inject(
            self.cache, jax.tree.map(jnp.asarray, staged),
            jnp.asarray(ids), jnp.int32(n),
        )
        # content is on device now: the landing blocks become matchable
        self.allocator.register_restored(plan)

    def _sampling_arrays(self, seqs: list[Sequence], lanes: int):
        vocab = self.config.model.vocab_size
        kb = self.config.logit_bias_k
        temp = np.zeros((lanes,), np.float32)
        top_k = np.zeros((lanes,), np.int32)
        top_p = np.ones((lanes,), np.float32)
        greedy = np.ones((lanes,), bool)
        pres = np.zeros((lanes,), np.float32)
        freq = np.zeros((lanes,), np.float32)
        rep = np.ones((lanes,), np.float32)
        # OpenAI logit_bias: fixed-width sparse rows, pad id = vocab (OOB
        # drop in the scatter)
        bias_ids = np.full((lanes, kb), vocab, np.int32)
        bias_vals = np.zeros((lanes, kb), np.float32)
        for i, seq in enumerate(seqs):
            s = seq.request.sampling
            lane = seq.lane if lanes > 1 else i
            temp[lane] = s.temperature if s.temperature is not None else 0.0
            top_k[lane] = s.top_k or 0
            top_p[lane] = s.top_p if s.top_p is not None else 1.0
            greedy[lane] = bool(
                s.use_greedy or s.temperature is None or s.temperature <= 0.0
            )
            pres[lane] = s.presence_penalty or 0.0
            freq[lane] = s.frequency_penalty or 0.0
            rep[lane] = s.repetition_penalty if s.repetition_penalty else 1.0
            if s.logit_bias and kb:
                # drop out-of-vocab ids BEFORE truncating so they cannot
                # displace valid biases from the bucket
                entries = sorted(
                    (
                        (int(t), float(v))
                        for t, v in s.logit_bias.items()
                        if 0 <= int(t) < vocab
                    ),
                    key=lambda e: -abs(e[1]),
                )[:kb]  # over-wide requests keep the strongest biases
                for j, (tok, val) in enumerate(entries):
                    bias_ids[lane, j] = tok
                    bias_vals[lane, j] = val
        # by the program's own rule (``sample_tokens``: ``force_greedy``)
        if not (greedy | (temp <= np.float32(1e-5))).all():
            self._packed_samples = self._step_dispatched_samples = True
        return temp, top_k, top_p, greedy, pres, freq, rep, bias_ids, bias_vals

    def _next_rng(self) -> np.ndarray:
        return self._host_rng.integers(0, 2**32, size=2, dtype=np.uint32)

    def _count_row(self, token_ids: list[int]) -> np.ndarray:
        """Per-vocab token counts [vocab] int32 (penalty bookkeeping)."""
        vocab = self.config.model.vocab_size
        if not token_ids:
            return np.zeros((vocab,), np.int32)
        return np.bincount(
            np.asarray(token_ids, np.int64) % vocab, minlength=vocab
        ).astype(np.int32)

    def _seed_lane_state(self, seq: Sequence) -> None:
        """Initialize a lane's penalty counts + rng key for a sequence that
        skipped local prefill (disagg decode side)."""
        prompt_row = self._count_row(seq.request.token_ids)
        gen_row = self._count_row(seq.output_ids)
        lane = jnp.int32(seq.lane)
        self._prompt_counts = self._jit_set_row(self._prompt_counts, lane, jnp.asarray(prompt_row))
        self._gen_counts = self._jit_set_row(self._gen_counts, lane, jnp.asarray(gen_row))
        self._seed_lane_key(seq)
        seq.sampling_seeded = True

    def _seed_lane_key(self, seq: Sequence) -> np.ndarray:
        """Per-lane PRNG key: derived from the request seed when given
        (reproducible sampling), else from the engine stream."""
        seed = seq.request.sampling.seed
        if seed is not None:
            # same packing as jax.random.PRNGKey(seed): [hi32, lo32]
            s = int(seed) & ((1 << 64) - 1)
            row = np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)
        else:
            row = self._next_rng()
        self._lane_keys[seq.lane if seq.lane >= 0 else 0] = row
        return row

    def _extract_block_range(
        self, blocks: list[int], start_b: int, end_b: int, device: bool
    ):
        """Gather cache leaves for ``blocks[start_b:end_b]`` (device thread).
        The gather table is bucketed like _jit_extract's full-sequence use so
        streamed chunks reuse the same compiled gathers."""
        count = end_b - start_b
        ids = np.zeros((self._table_len(count),), np.int32)
        ids[:count] = blocks[start_b:end_b]
        gathered = self._jit_extract(self.cache, jnp.asarray(ids))
        if device:
            return jax.tree.map(lambda x: x[:, :count], gathered)
        return jax.tree.map(lambda x: np.asarray(x)[:, :count], gathered)

    def _stream_prefill_chunk(self, seq: Sequence, blocks: list[int], end: int) -> None:
        """Streamed disagg transfer: after an intermediate chunk wrote KV up
        to token ``end``, extract the newly COMPLETED blocks (never a
        partially-written one) and hand them to ``seq.on_chunk_done`` while
        later chunks compute.  The watermark only moves forward, so a
        preemption recompute re-runs chunks without re-streaming blocks the
        receiver already injected."""
        done_b = end // self.config.block_size
        if done_b <= seq.streamed_blocks:
            return
        start_b = seq.streamed_blocks
        out = self._extract_block_range(blocks, start_b, done_b, seq.extract_device)
        seq.streamed_blocks = done_b
        try:
            seq.on_chunk_done(start_b, out, done_b - start_b)
        except Exception:  # noqa: BLE001 — a sink bug must not kill the device loop
            logger.exception("on_chunk_done failed for %s", seq.seq_id)

    def _run_prefill(self, seq: Sequence) -> None:
        tokens = seq.all_token_ids
        n = len(tokens)
        restore = self.allocator.take_restore_plan(seq.seq_id)
        if restore:
            try:
                self._restore_blocks(restore)
            except BaseException:
                # the plan must survive a failed restore: a retry (pallas
                # fallback) re-executes it, and _fail_sequence → free_sequence
                # needs it to unregister the garbage landing blocks and
                # release the host pins
                self.allocator.put_back_restore_plan(seq.seq_id, restore)
                raise
        blocks = self.allocator.block_ids(seq.seq_id)
        temp, top_k, top_p, greedy, pres, freq, rep, bias_ids, bias_vals = (
            self._sampling_arrays([seq], 1)
        )
        sampling_tail = (
            jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(greedy), jnp.asarray(pres), jnp.asarray(freq),
            jnp.asarray(rep), jnp.asarray(bias_ids), jnp.asarray(bias_vals),
        )
        key = self._seed_lane_key(seq)
        seq.sampling_seeded = True
        lane = max(seq.lane, 0)  # prefill_only sequences have no decode lane
        # nonzero only on preemption recompute (token_ids include generated)
        gen_row = self._count_row(seq.output_ids)

        # window for this call: everything past the already-written prefix
        # (cached blocks and/or completed chunks) up to the scheduler's
        # budgeted chunk target
        start = max(seq.prefilled_tokens, seq.cached_tokens)
        end = min(seq.chunk_target, n) if (
            self.chunk_tokens is not None and seq.chunk_target
        ) else n
        final = end >= n

        if seq.mm_embeds is not None:
            # multimodal: patch embeddings occupy positions [0, mm_len),
            # text tokens follow; embeddings splice in-jit
            total = seq.context_len
            bucket = self._bucket_len(total)
            tok_arr = np.zeros((bucket,), np.int32)
            text = seq.request.token_ids + seq.output_ids
            tok_arr[seq.mm_len : seq.mm_len + len(text)] = text
            emb_pad = np.zeros((bucket, self.config.model.hidden_size), np.float32)
            emb_pad[: seq.mm_len] = seq.mm_embeds
            block_ids = np.zeros((self.max_blocks_per_seq,), np.int32)
            block_ids[: len(blocks)] = blocks
            self._phase("dispatch", kind=KIND_PROMPT, tokens=total)
            token, lp, tkv, tki, self.cache, self._gen_counts, self._prompt_counts = self._jit_prefill_mm(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                jnp.int32(lane), jnp.asarray(emb_pad), jnp.asarray(tok_arr),
                jnp.int32(seq.mm_len), jnp.asarray(block_ids), jnp.int32(total),
                jnp.asarray(gen_row), jnp.asarray(key), *sampling_tail,
                self._guided_row(seq), self.cos, self.sin,
            )
            seq.prefilled_tokens = total
            self._book_prompt_window(total, bucket)
            self._step_prefill_tokens += total
            self._step_attn_ctx += total * (total + 1) // 2
            self._step_weight_streams += 1
            want_top = seq.request.sampling.top_logprobs > 0
            token_h, lp_h = self._read_prefill_sample(token, lp)
            self._process_token(
                seq, token_h, lp_h, top=(tkv, tki) if want_top else None
            )
            return
        # the continued-prefill jit serves prefix hits AND every chunk (an
        # intermediate first chunk needs its sample gate; start_pos=0 masks
        # the prefix away entirely)
        if self._jit_prefill_prefix is not None and (start > 0 or not final):
            # continued prefill: queries attend to the resident prefix
            # blocks (none when start == 0).  The block table is
            # bucketed like token lengths so the per-layer prefix gather
            # scales with the actual context, not max_blocks_per_seq
            start_blocks = start // self.config.block_size
            tail = tokens[start:end]
            live = t = len(tail)
            padded = np.zeros((self._bucket_len(t),), np.int32)
            padded[:t] = tail
            table_len = self.allocator.blocks_needed(
                self._bucket_len(min(n + 1, self.max_len))
            )
            full_ids = np.zeros((table_len,), np.int32)
            full_ids[: len(blocks)] = blocks
            tail_ids = np.zeros((table_len,), np.int32)
            tail_ids[: len(blocks) - start_blocks] = blocks[start_blocks:]
            prompt_row = self._count_row(seq.request.token_ids)
            self._phase("dispatch", kind=KIND_PROMPT, tokens=t)
            token, lp, tkv, tki, self.cache, self._gen_counts, self._prompt_counts = self._jit_prefill_prefix(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                jnp.int32(lane), jnp.asarray(padded), jnp.asarray(full_ids),
                jnp.asarray(tail_ids), jnp.int32(t), jnp.int32(start),
                jnp.int32(n), jnp.asarray(prompt_row), jnp.asarray(gen_row),
                jnp.int32(1 if final else 0), jnp.asarray(key), *sampling_tail,
                # intermediate chunks discard their sample: no constraint
                self._guided_row(seq) if final else self._guided_true_row,
                self.cos, self.sin,
            )
        else:
            live = end
            padded = np.zeros((self._bucket_len(end),), np.int32)
            padded[:end] = tokens[:end]
            block_ids = np.zeros((self.max_blocks_per_seq,), np.int32)
            block_ids[: len(blocks)] = blocks
            block_ids = jnp.asarray(block_ids)
            if self._window_blocks:
                window_ids = np.zeros((self.max_blocks_per_seq,), np.int32)
                wblocks = self.allocator.window_block_ids(seq.seq_id)
                window_ids[: len(wblocks)] = wblocks
                block_ids = KvPools(block_ids, jnp.asarray(window_ids))
            self._phase("dispatch", kind=KIND_PROMPT, tokens=end)
            token, lp, tkv, tki, self.cache, self._gen_counts, self._prompt_counts = self._jit_prefill(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                jnp.int32(lane), jnp.asarray(padded), block_ids,
                jnp.int32(end), jnp.int32(0), jnp.asarray(gen_row), jnp.asarray(key),
                *sampling_tail, self._guided_row(seq), self.cos, self.sin,
            )
        self._phase("post")
        self._step_dispatched_kind = KIND_PROMPT
        self._after_dispatch([(seq, end)])
        seq.prefilled_tokens = end
        self._book_prompt_window(live, padded.shape[0])
        # utilization accounting: this window computed [start, end) — each
        # position p attends p+1 context positions (causal)
        self._step_prefill_tokens += end - start
        self._step_attn_ctx += (end * (end + 1) - start * (start + 1)) // 2
        self._step_weight_streams += 1
        self._book_state_rows(0, end - start, 1)
        if not final:
            # intermediate chunk: KV written, no token sampled; publish the
            # completed blocks so routers (and future prompts) can hit them
            self._publish_stored(seq.seq_id, tokens[:end])
            if seq.prefill_only and seq.on_chunk_done is not None:
                self._stream_prefill_chunk(seq, blocks, end)
            return
        # the final chunk's sample is the only readback of a split prefill
        # (intermediate chunks stay pipelined: nothing waits for them)
        token_h, lp_h = self._read_prefill_sample(token, lp)
        if seq.status == SeqStatus.PREFILLING:
            seq.status = SeqStatus.RUNNING  # last chunk done → decode
        if seq.prefill_only:
            # disagg prefill worker: hand back first token + the KV blocks.
            # With streaming, earlier chunks already shipped blocks up to the
            # watermark — extract only the tail past it (the final chunk's
            # last block is never complete before now, so the tail is always
            # non-empty and the closing part always carries blocks).
            n_used = self.allocator.blocks_needed(n)
            start_b = min(seq.streamed_blocks, n_used)
            blocks_out = self._extract_block_range(
                blocks, start_b, n_used, seq.extract_device
            )
            want_top = seq.request.sampling.top_logprobs
            top_rows = None
            if want_top > 0:
                tkv_h, tki_h = np.asarray(tkv), np.asarray(tki)
                k = min(want_top, len(tki_h))
                top_rows = [[int(tki_h[i]), float(tkv_h[i])] for i in range(k)]
            result = (token_h, lp_h, top_rows, blocks_out, n_used)
            self.scheduler.finish(seq)
            if seq.on_prefill_done:
                seq.on_prefill_done(result)
            return
        if seq.mm_embeds is None:
            self._publish_stored(seq.seq_id, tokens)
        want_top = seq.request.sampling.top_logprobs > 0
        self._part("tokens")
        self._process_token(
            seq, token_h, lp_h, top=(tkv, tki) if want_top else None
        )
        self._part(None)

    def _read_prefill_sample(self, token, lp) -> tuple[int, float]:
        """Read a split prefill's sampled token back (the `readback` phase:
        the host blocks until the prefill program is done)."""
        _, prefills, samples = self._take_unwaited(KIND_PROMPT)  # earlier chunks
        self._phase("readback", kind=KIND_PROMPT)
        out = int(token), float(lp)
        self._phase("post")
        self._note_wait(KIND_PROMPT, prefills, samples)
        return out

    def _ngram_draft(self, tokens: list[int]) -> list[int]:
        """Prompt-lookup drafting: find the most recent earlier occurrence
        of the sequence's final ``spec_ngram`` tokens and propose the
        continuation that followed it (up to ``spec_tokens``)."""
        g = self.config.spec_ngram
        k = self.config.spec_tokens
        if len(tokens) < g + 1:
            return []
        # bound the host-side scan: matches far behind the tail rarely help,
        # and an O(context) rescan per lane per step would grow with
        # generation length
        tokens = tokens[-4096:]
        arr = np.asarray(tokens, np.int64)
        tail = arr[-g:]
        # windows of width g ending strictly before the final position
        windows = np.lib.stride_tricks.sliding_window_view(arr[:-1], g)
        matches = np.flatnonzero((windows == tail).all(axis=1))
        if len(matches) == 0:
            return []
        j = int(matches[-1])  # most recent prior occurrence
        draft = arr[j + g : j + g + k]
        return draft.tolist()

    def _spec_ok(self, seq: Sequence) -> bool:
        """Greedy verification is exact only for greedy, penalty-free
        sampling (logit_bias is static per-lane and stays exact)."""
        s = seq.request.sampling
        greedy = bool(s.use_greedy or s.temperature is None or s.temperature <= 0.0)
        return (
            greedy
            and not s.presence_penalty
            and not s.frequency_penalty
            and (not s.repetition_penalty or s.repetition_penalty == 1.0)
        )

    def _run_decode(self, seqs: list[Sequence]) -> None:
        if self.spec_enabled:
            # draft first: the w-wide verify program only earns its keep
            # when enough lanes drafted (non-drafting lanes pay w× the
            # logits/sampling cost for one token)
            running = [s for s in seqs if s.status == SeqStatus.RUNNING]
            drafts = {
                seq.seq_id: self._ngram_draft(seq.all_token_ids)
                for seq in running
                if self._spec_ok(seq)
            }
            n_drafting = sum(1 for d in drafts.values() if d)
            if n_drafting and n_drafting >= (
                len(running) * self.config.spec_min_fraction
            ):
                # verify consumes host-side drafts and its acceptance count
                # gates emission per lane — inherently synchronous
                self._sync_pipeline()
                return self._run_verify_decode(seqs, drafts)
        if self._overlap_ok(seqs):
            return self._run_overlap_decode(seqs)
        self._sync_pipeline()
        return self._run_plain_decode(seqs)

    def _overlap_ok(self, seqs: list[Sequence]) -> bool:
        """Overlap serves a window only when no active lane needs per-token
        host state: guided lanes advance a host automaton that must gate the
        NEXT sample (same reason guidance pins decode_steps=1), and
        top_logprobs lanes ship K-wide rows whose readback belongs on the
        synchronous path.  Mixed batches fall back whole — lane masks can't
        split one jitted window."""
        if not self.decode_overlap:
            return False
        for seq in seqs:
            if seq.status != SeqStatus.RUNNING:
                continue
            if seq.guided is not None or seq.request.sampling.top_logprobs > 0:
                return False
        return True

    def _sync_pipeline(self) -> None:
        """Retire the in-flight window (if any): host state catches up with
        the device before anything that needs it — preemption, aborts,
        verify, the synchronous decode path, batch-composition changes."""
        w = self._inflight
        if w is None:
            return
        self._inflight = None
        # back to the caller's phase; outside a step (an abort or clear_kv
        # from _drain_submissions) that is none, and `post` must not stay
        # open over the idle wait that follows
        resume = self._phase_name
        self._retire_window(w)
        self._phase(resume)

    def _abandon_pipeline(self, seqs: list[Sequence]) -> None:
        """Decode-step failure cleanup: drop the in-flight window without
        retiring it (its arrays may be poisoned) and zero the in-flight
        token accounting so a recovered loop rebuilds from host state.
        Deferred finishes attached to the dropped window still release
        their lanes/blocks — leaking them would starve a recovered engine."""
        w = self._inflight
        self._inflight = None
        if w is not None:
            # the dropped window's device program may still be EXECUTING
            # (the failure that got us here can be a later dispatch): wait
            # for it (errors swallowed — completion, not success, is what
            # gates release) so freeing the deferred sequences' blocks
            # cannot race its lagged writes into a new owner's storage
            try:
                jax.block_until_ready(w.tokens)
            except Exception:  # noqa: BLE001 — a failed program still ended
                pass
            self._close_prefill_spans(w.prefills, status="error")
            for seq in w.deferred:
                self.scheduler.finish(seq)
            for seq in w.active:
                seq.inflight_tokens = 0
        for seq in seqs:
            seq.inflight_tokens = 0

    def _retire_window(self, w: _InflightWindow) -> None:
        """Readback + emission for one dispatched window.  Runs AFTER the
        next window was dispatched (steady state), so the device computes
        while the host blocks here — this wait is the `readback` phase of
        the iteration AFTER the one that dispatched
        it, which is why that iteration's time is booked to ``w.kind``."""
        self._phase("readback", kind=w.kind)
        try:
            tokens_host = np.asarray(w.tokens)
            lps_host = np.asarray(w.lps)
            if tokens_host.ndim == 1:
                tokens_host = tokens_host[None, :]
                lps_host = lps_host[None, :]
            self._phase("post")
            self._note_wait(w.kind, w.prefills, w.samples)
            for seq in w.active:
                seq.inflight_tokens = max(0, seq.inflight_tokens - w.steps)
            self._part("tokens")
            for s in range(tokens_host.shape[0]):
                for seq in w.active:
                    if seq.status != SeqStatus.RUNNING:
                        continue  # finished at an earlier step in this window
                    self._process_token(
                        seq, int(tokens_host[s, seq.lane]),
                        float(lps_host[s, seq.lane]),
                    )
        finally:
            # sequences that finished while THIS window was in flight: their
            # lagged garbage steps have now executed (or been masked), so
            # the lane and blocks go back to the pools — even when the
            # readback/emission above raised (this window is no longer
            # reachable from self._inflight, so a skipped release here
            # would leak the lane and blocks forever)
            if w.deferred:
                self._part("release")
                for seq in w.deferred:
                    self.scheduler.finish(seq)

    def _finish_decoded(self, seq: Sequence) -> None:
        """Finish a sequence from the decode path.  While an in-flight
        window still references its lane the release is DEFERRED: freeing
        the blocks now would let the lagged device step garbage-write into
        storage the allocator may hand to (or prefix-match for) someone
        else.  Emission already happened — only lane/block release waits."""
        w = self._inflight
        if w is not None and seq.lane in w.lane_ids:
            seq.status = SeqStatus.FINISHED
            w.deferred.append(seq)
        else:
            self.scheduler.finish(seq)

    def _prep_decode_seq(self, seq: Sequence) -> None:
        """Shared per-sequence bookkeeping at decode dispatch (every decode
        path: overlap, plain, verify): lane sampling state for sequences
        that skipped local prefill, and first-decode span/timestamping."""
        if not seq.sampling_seeded:
            # remotely-prefilled: entered decode without a local prefill
            self._seed_lane_state(seq)
        if seq.decode_start_ts == 0.0:
            # covers remote-prefilled admission (no prefill pass)
            self._maybe_record_queue_span(seq)
            seq.decode_start_ts = time.time()

    def _run_overlap_decode(self, seqs: list[Sequence]) -> None:
        lanes = self.config.max_batch_size
        steps = self.config.decode_steps
        bs = self.config.block_size
        oob = self.config.num_blocks * bs
        prev = self._inflight

        active = [s for s in seqs if s.status == SeqStatus.RUNNING]
        if prev is not None:
            # the feedback array only carries tokens for sequences that were
            # in the previous window: a NEW sequence (fresh prefill, or a
            # lane reused after a deferred release) forces a drain + host
            # rebuild.  A SHRINKING batch keeps the pipeline hot — vacated
            # lanes get context_len 0 below, which masks them to OOB slots
            # on device (the lagged lane cannot write into freed blocks).
            prev_members = set(map(id, prev.active))
            if any(id(s) not in prev_members for s in active):
                # THE admission sync point the unified step removes: a lane
                # the feedback array doesn't cover (fresh prefill, reused
                # lane) forces a drain + host rebuild here
                self._admission_drains += 1
                self._sync_pipeline()
                prev = None
                active = [s for s in active if s.status == SeqStatus.RUNNING]
        if not active:
            self._sync_pipeline()
            return

        # pre-extend every block table to cover the window at the DEVICE
        # context (host context + dispatched-unretired tokens) — the one-step
        # stop-condition lag means these positions may be written before the
        # host learns whether the lane already finished.  No preemption here:
        # a preemption would free blocks a lagged in-flight step still
        # writes; on OOM the pipeline drains and the preempting synchronous
        # path serves this iteration instead.
        slots: dict[str, int] = {}
        self._part("slots")
        for seq in active:
            # clamp at max_len: a lane the host is about to LENGTH-finish can
            # have in-flight windows past the end — those steps are pure
            # garbage (truncated at retire), and unclamped they would index
            # past the block table the max_pos cap stops growing
            dev_ctx = min(seq.context_len + seq.inflight_tokens, self.max_len)
            slot = self.scheduler.try_slots_at(
                seq, dev_ctx, steps, max_pos=self.max_len - 1
            )
            if slot is None:
                self._sync_pipeline()
                return self._run_plain_decode(seqs)
            slots[seq.seq_id] = slot

        self._part("build")
        context_lens = np.zeros((lanes,), np.int32)
        slot_ids = np.full((lanes,), oob, np.int32)
        token_ids = np.zeros((lanes,), np.int32) if prev is None else None
        for seq in active:
            self._prep_decode_seq(seq)
            lane = seq.lane
            context_lens[lane] = min(
                seq.context_len + seq.inflight_tokens, self.max_len
            )
            if steps <= 1:
                slot_ids[lane] = slots[seq.seq_id]
            if token_ids is not None:
                token_ids[lane] = seq.last_token_id
        self._part("tables")
        tables = self._decode_tables(active)
        self._phase("upload")
        self._part("sampling")
        sampling_tail = self._device_sampling_tail(active, lanes)
        self._part("arrays")
        # token feedback: step N+1's input IS step N's on-device output —
        # the host never sees (or waits for) the tokens it dispatches
        tok_in = prev.feedback if prev is not None else jnp.asarray(token_ids)
        lens_dev = jnp.asarray(context_lens)
        if steps <= 1:
            if self._gmodes_unguided is None:
                self._gmodes_unguided = jnp.asarray(
                    np.full((lanes,), -1, np.int32)
                )
            args = (
                tok_in, tables, lens_dev, jnp.asarray(slot_ids),
                *sampling_tail, self._guided_table, self._gmodes_unguided,
            )
            self._phase("dispatch", kind=KIND_DECODE, tokens=len(active))
            tokens, lps, _tkvs, _tkis, self.cache, self._gen_counts = self._jit_decode(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                *args, self.cos, self.sin,
            )
            feedback = tokens
            w_tokens, w_lps = tokens, lps
        else:
            args = (tok_in, tables, lens_dev, *sampling_tail)
            self._phase("dispatch", kind=KIND_DECODE, tokens=len(active) * steps)
            w_tokens, w_lps, _tkvs, _tkis, feedback, self.cache, self._gen_counts = self._jit_decode(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                *args, self.cos, self.sin,
            )
        self._phase("post")
        self._after_dispatch([(s, int(context_lens[s.lane])) for s in active])
        kind, prefills, samples = self._take_unwaited(KIND_DECODE)
        self._step_dispatched_kind = kind
        # start the device→host copies now; by the time this window is
        # retired (one iteration from now) the transfer may already be done
        for arr in (w_tokens, w_lps):
            arr.copy_to_host_async()
        for seq in active:
            seq.inflight_tokens += steps
        self._inflight = _InflightWindow(
            tokens=w_tokens, lps=w_lps, feedback=feedback,
            active=list(active), lane_ids=[s.lane for s in active],
            steps=steps, kind=kind, samples=samples, prefills=prefills,
        )
        self._overlap_windows += 1
        self._count_decode_window(context_lens, len(active), steps)
        if prev is not None:
            self._retire_window(prev)

    def _device_sampling_tail(self, active: list[Sequence], lanes: int) -> tuple:
        """Device copies of (lane_keys, temp, top_k, top_p, greedy, pres,
        freq, rep, bias_ids, bias_vals), reused across windows while the
        host values are unchanged (see ``_tail_cache`` in __init__)."""
        host_tail = (self._lane_keys,) + self._sampling_arrays(active, lanes)
        cached = self._tail_cache
        if cached is not None and all(
            np.array_equal(a, b) for a, b in zip(cached[0], host_tail)
        ):
            return cached[1]
        sampling_tail = tuple(jnp.asarray(x) for x in host_tail)
        self._tail_cache = (
            tuple(np.copy(x) for x in host_tail), sampling_tail
        )
        return sampling_tail

    def _decode_tables(self, active: list[Sequence]):
        """Device block-table array for a decode window.  Host rows are
        persistent and rewritten ONLY for lanes whose (sequence, block list)
        changed since the last window; the device copy is reused while every
        row is clean.  Stale rows for vacated lanes are harmless: inactive
        lanes have context_len 0, so their slots mask to OOB and attention
        reads nothing."""
        dirty = self._bt_dev is None
        for seq in active:
            dirty |= self._table_row(
                self._bt_host, self._bt_lane_key, seq,
                self.allocator.block_ids(seq.seq_id),
            )
        if dirty:
            self._bt_dev = jnp.asarray(self._bt_host)
        if not self._window_blocks:
            return self._bt_dev
        # the window pool's tables beside them: ordinals as in the full
        # pool's, entries behind a lane's window stale (nobody reads them)
        dirty = self._wbt_dev is None
        for seq in active:
            dirty |= self._table_row(
                self._wbt_host, self._wbt_lane_key, seq,
                self.allocator.window_block_ids(seq.seq_id),
            )
        if dirty:
            # a copy: the host rows are rewritten (entries behind a window
            # zeroed) while a step that reads this upload may be in flight,
            # and an upload may alias or still be reading its source
            self._wbt_dev = jnp.asarray(self._wbt_host.copy())
        return KvPools(self._bt_dev, self._wbt_dev)

    @staticmethod
    def _table_row(host, lane_keys, seq: Sequence, blocks: list[int]) -> bool:
        """Write ``seq``'s block list into its lane's host row if it
        changed; whether it did."""
        lane = seq.lane
        key = lane_keys[lane]
        if key is not None and key[0] == seq.seq_id and key[1] == blocks:
            return False
        row = host[lane]
        n = len(blocks)
        row[:n] = blocks
        row[n:] = 0
        lane_keys[lane] = (seq.seq_id, blocks)
        return True

    def _after_dispatch(self, seqs_at: list[tuple[Sequence, int]]) -> None:
        """Bookkeeping a model with a window pool or expert layers needs
        once a step program is on its way: each sequence's window-pool
        blocks behind the window of its NEXT query go back to the pool, and
        the expert layers' counters leave the cache (the step's result, not
        yet donated to the next) for a fresh zero leaf."""
        released = bool(self._window_blocks)
        if released:
            self._part("release")
            for seq, next_pos in seqs_at:
                self.allocator.release_behind_window(seq.seq_id, next_pos)
        # ... every 50 ms, not every step: the leaf goes on counting on the
        # device meanwhile (int32: hours at this model's rates, not 50 ms)
        now = time.monotonic()
        if "moe_stats" in self.cache and now - self._moe_taken_at >= 0.05:
            if not released:
                released = True
                self._part("release")
            self._moe_taken_at = now
            taken = self.cache["moe_stats"]
            taken.copy_to_host_async()
            if self._moe_zero is None:
                self._moe_zero = np.zeros(taken.shape, taken.dtype)
            self.cache = {**self.cache, "moe_stats": jax.device_put(self._moe_zero)}
            # earlier steps' leaves are read once their step has run: never
            # waited for (the host is a step ahead of the device)
            waiting = []
            for leaf in self._moe_pending:
                if not leaf.is_ready():
                    waiting.append(leaf)
                    continue
                for i, n in enumerate(np.asarray(leaf).tolist()):
                    self._moe_totals[i] += n
            self._moe_pending = [*waiting, taken]
        if released:
            self._part(None)

    def _attended_ctx(self, start: int, end: int) -> tuple[int, int]:
        """Context positions the tokens at ``[start, end)`` attend, causally
        (position p sees p + 1: what the cost model has always booked) and
        as the kernel sees them (the sliding window's width once past it)."""
        full = (end * (end + 1) - start * (start + 1)) // 2
        w = self._attn_layers[2]
        if w is None or end <= w:
            return full, full
        a = max(start, w)   # first position whose view the window clips
        return full, (a * (a + 1) - start * (start + 1)) // 2 + (end - a) * w

    def _count_decode_window(self, context_lens: np.ndarray, lanes: int, steps: int) -> None:
        """Book one dispatch of the decode program: the step's token /
        context / weight-stream facts and the decode kernel's work (per
        lane the attended positions and the whole pages walked, after the
        sliding window's clip, all layers)."""
        ctx_sum = int(context_lens.sum())
        self._decode_steps_total += steps
        self._step_lane_steps += lanes * steps
        self._step_decode_tokens += lanes * steps
        self._step_attn_ctx += ctx_sum * steps
        self._step_weight_streams += steps
        self._book_state_rows(lanes * steps, 0, 0)
        if not self.attention_impl.startswith("pallas"):
            return
        bs = self.config.block_size
        n_full, n_window, w = self._attn_layers
        ctx = context_lens.astype(np.int64)
        full_pages = int(((ctx + (bs - 1)) // bs).sum())
        if w is None:
            attended, pages = ctx_sum, full_pages
        else:
            attended = int(np.minimum(ctx, w).sum())
            pages = int(((ctx + (bs - 1)) // bs - np.maximum(ctx - w, 0) // bs).sum())
        cost = self.utilization.cost
        work = self._kernel_work
        layers_n = n_full + n_window
        work["decode_attn_flops_total"] += (
            cost.attn_flops(ctx_sum * steps) * n_full
            + cost.attn_flops(attended * steps) * n_window
        ) // layers_n
        work["decode_kv_read_bytes_total"] += (
            (full_pages * n_full + pages * n_window)
            * steps * bs * cost.kv_bytes_per_token // layers_n
        )
        if self._window_blocks:
            work["window_pages_visited_total"] += pages * steps
            work["window_pages_full_total"] += full_pages * steps
        work["decode_cross_kv_read_bytes_total"] += (
            full_pages * cost.cross_layers * steps * bs * cost.kv_bytes_per_token // layers_n
        )

    def _book_state_rows(self, decode_rows: int, prompt_rows: int, spans: int) -> None:
        """Book a dispatch's rows through the state-space and memory layers
        (nothing for a model without them): decode rows, rows of prompt
        spans, and the spans (each writes its lane's state out once)."""
        cost = self.utilization.cost
        if not cost.ssm_layers:
            return
        work = self._kernel_work
        rows = decode_rows + prompt_rows
        work["ssm_rows_total"] += rows
        work["gmu_rows_total"] += rows
        work["ssm_flops_total"] += rows * cost.ssm_flops_per_row
        work["ssm_state_bytes_total"] += (
            decode_rows * 2 * cost.ssm_state_bytes_per_lane
            + prompt_rows * cost.ssm_prompt_bytes_per_row
            + spans * cost.ssm_state_bytes_per_lane
        )

    @property
    def _phase_name(self) -> str | None:
        return self.loop_account.name

    def _run_plain_decode(self, seqs: list[Sequence]) -> None:
        lanes = self.config.max_batch_size
        steps = self.config.decode_steps
        token_ids = np.zeros((lanes,), np.int32)
        context_lens = np.zeros((lanes,), np.int32)
        oob = self.config.num_blocks * self.config.block_size
        slot_ids = np.full((lanes,), oob, np.int32)

        slots: dict[str, int] = {}
        candidates: list[Sequence] = []
        self._part("slots")
        for seq in list(seqs):
            if seq.status != SeqStatus.RUNNING:
                continue  # preempted as a victim earlier in this loop
            # pre-extend the block table to cover the whole decode window
            # (when steps > 1 the device re-derives per-step slots from the
            # block tables; the returned slot is then only an OOM signal)
            slot = self.scheduler.ensure_slots(seq, steps, max_pos=self.max_len - 1)
            if slot is None:
                # could not allocate even after preemption: preempt self
                self.scheduler.preempt(seq)
                continue
            slots[seq.seq_id] = slot
            candidates.append(seq)
        # build arrays only after all allocations settled: a sequence
        # preempted as a victim must not keep a live lane pointing at freed
        # (possibly re-allocated) blocks
        active = [s for s in candidates if s.status == SeqStatus.RUNNING]
        self._part("build")
        for seq in active:
            self._prep_decode_seq(seq)
            lane = seq.lane
            token_ids[lane] = seq.last_token_id
            context_lens[lane] = seq.context_len
            if steps <= 1:
                slot_ids[lane] = slots[seq.seq_id]
        if not active:
            return
        self._part("tables")
        tables = self._decode_tables(active)

        want_top = any(
            seq.request.sampling.top_logprobs > 0 for seq in active
        )
        self._phase("upload")
        self._part("sampling")
        sampling_tail = self._device_sampling_tail(active, lanes)
        self._part("arrays")
        kind, prefills, samples = self._take_unwaited(KIND_DECODE)
        if steps <= 1:
            gmodes = np.full((lanes,), -1, np.int32)
            for seq in active:
                if seq.guided is not None:
                    gmodes[seq.lane] = seq.guided.mode_id
            args = (
                jnp.asarray(token_ids), tables,
                jnp.asarray(context_lens), jnp.asarray(slot_ids),
                *sampling_tail, self._guided_table, jnp.asarray(gmodes),
            )
            self._phase("dispatch", kind=KIND_DECODE, tokens=len(active))
            tokens, lps, tkvs, tkis, self.cache, self._gen_counts = self._jit_decode(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                *args, self.cos, self.sin,
            )
            self._phase("readback", kind=kind)
            tokens_host = np.asarray(tokens)[None, :]  # [1, lanes]
            lps_host = np.asarray(lps)[None, :]
            tkv_host = np.asarray(tkvs)[None] if want_top else None
            tki_host = np.asarray(tkis)[None] if want_top else None
        else:
            args = (
                jnp.asarray(token_ids), tables,
                jnp.asarray(context_lens), *sampling_tail,
            )
            self._phase("dispatch", kind=KIND_DECODE, tokens=len(active) * steps)
            tokens, lps, tkvs, tkis, _feedback, self.cache, self._gen_counts = self._jit_decode(
                self.params, self.cache, self._gen_counts, self._prompt_counts,
                *args, self.cos, self.sin,
            )
            self._phase("readback", kind=kind)
            tokens_host = np.asarray(tokens)  # [steps, lanes]
            lps_host = np.asarray(lps)
            tkv_host = np.asarray(tkvs) if want_top else None
            tki_host = np.asarray(tkis) if want_top else None
        self._phase("post")
        self._after_dispatch([(s, int(context_lens[s.lane])) for s in active])
        self._step_dispatched_kind = KIND_DECODE
        self._note_wait(kind, prefills, samples)
        self._sync_windows += 1
        self._count_decode_window(context_lens, len(active), int(tokens_host.shape[0]))

        self._part("tokens")
        for s in range(tokens_host.shape[0]):
            for seq in active:
                if seq.status != SeqStatus.RUNNING:
                    continue  # finished at an earlier step in this window
                self._process_token(
                    seq, int(tokens_host[s, seq.lane]),
                    float(lps_host[s, seq.lane]),
                    top=(
                        (tkv_host[s, seq.lane], tki_host[s, seq.lane])
                        if want_top else None
                    ),
                )

    def _warm_verify_step(self) -> None:
        """Compile the verify program: one launch with every lane inactive
        (ctx 0 ⇒ slots OOB ⇒ all cache writes drop, nothing accepted)."""
        lanes = self.config.max_batch_size
        w = self.config.spec_tokens + 1
        oob = self.config.num_blocks * self.config.block_size
        temp, top_k, top_p, greedy, pres, freq, rep, bias_ids, bias_vals = (
            self._sampling_arrays([], lanes)
        )
        _, _, _, _, _, self.cache, self._gen_counts = self._jit_verify(
            self.params, self.cache, self._gen_counts, self._prompt_counts,
            jnp.zeros((lanes, w), jnp.int32),
            jnp.zeros((lanes, self.max_blocks_per_seq), jnp.int32),
            jnp.zeros((lanes,), jnp.int32),
            jnp.full((lanes, w), oob, jnp.int32),
            jnp.zeros((lanes,), bool), jnp.asarray(self._lane_keys),
            jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(greedy), jnp.asarray(pres), jnp.asarray(freq),
            jnp.asarray(rep), jnp.asarray(bias_ids), jnp.asarray(bias_vals),
            self.cos, self.sin,
        )

    def _run_verify_decode(self, seqs: list[Sequence], drafts: dict) -> None:
        """Speculative decode step: draft via prompt lookup, verify the
        whole window in one forward, emit the accepted prefix."""
        lanes = self.config.max_batch_size
        w = self.config.spec_tokens + 1
        bs = self.config.block_size
        oob = self.config.num_blocks * bs

        candidates: list[Sequence] = []
        for seq in list(seqs):
            if seq.status != SeqStatus.RUNNING:
                continue
            # cover the whole window (like decode_steps=w); rejected
            # positions' blocks are simply reused later
            slot = self.scheduler.ensure_slots(seq, w, max_pos=self.max_len - 1)
            if slot is None:
                self.scheduler.preempt(seq)
                continue
            candidates.append(seq)
        active = [s for s in candidates if s.status == SeqStatus.RUNNING]
        if not active:
            return

        token_mat = np.zeros((lanes, w), np.int32)
        slot_mat = np.full((lanes, w), oob, np.int32)
        block_tables = np.zeros((lanes, self.max_blocks_per_seq), np.int32)
        context_lens = np.zeros((lanes,), np.int32)
        spec_ok = np.zeros((lanes,), bool)
        for seq in active:
            self._prep_decode_seq(seq)
            lane = seq.lane
            draft = drafts.get(seq.seq_id) or []
            if draft:
                spec_ok[lane] = True
            row = [seq.last_token_id] + draft
            row = (row + [row[-1]] * w)[:w]  # pad: never accepted unless equal
            token_mat[lane] = row
            blocks = self.allocator.block_ids(seq.seq_id)
            block_tables[lane, : len(blocks)] = blocks
            ctx = seq.context_len
            context_lens[lane] = ctx + w - 1
            for j in range(w):
                pos = min(ctx - 1 + j, self.max_len - 1)
                slot_mat[lane, j] = blocks[pos // bs] * bs + pos % bs

        want_top = any(s.request.sampling.top_logprobs > 0 for s in active)
        self._phase("upload")
        self._part("sampling")
        sampling_tail = self._device_sampling_tail(active, lanes)
        self._part("arrays")
        self._phase("dispatch", kind=KIND_DECODE, tokens=len(active) * w)
        tokens, n_accept, lps, tkvs, tkis, self.cache, self._gen_counts = self._jit_verify(
            self.params, self.cache, self._gen_counts, self._prompt_counts,
            jnp.asarray(token_mat), jnp.asarray(block_tables),
            jnp.asarray(context_lens), jnp.asarray(slot_mat),
            jnp.asarray(spec_ok), *sampling_tail,
            self.cos, self.sin,
        )
        kind, prefills, samples = self._take_unwaited(KIND_DECODE)
        self._phase("readback", kind=kind)
        tokens_h = np.asarray(tokens)
        n_h = np.asarray(n_accept)
        lps_h = np.asarray(lps)
        tkv_h = np.asarray(tkvs) if want_top else None
        tki_h = np.asarray(tkis) if want_top else None
        self._phase("post")
        self._step_dispatched_kind = KIND_DECODE
        self._note_wait(kind, prefills, samples)
        # count attempts only after the jit succeeded (an attention-fallback
        # retry re-enters this method for the same step); attempted = the
        # whole window (pads can accept too), so accepted/drafted <= 1
        self._spec_drafted += int(spec_ok.sum()) * (w - 1)
        # one verify launch streams the weights once and computes w
        # positions per active lane, EACH attending the lane's full context
        self._step_decode_tokens += len(active) * w
        self._step_attn_ctx += int(context_lens.sum()) * w
        self._step_weight_streams += 1
        self._part("tokens")
        for seq in active:
            lane = seq.lane
            n = int(n_h[lane])
            self._spec_accepted += max(0, n - 1)
            for i in range(n):
                if seq.status != SeqStatus.RUNNING:
                    break
                self._process_token(
                    seq, int(tokens_h[lane, i]), float(lps_h[lane, i]),
                    top=(
                        (tkv_h[lane, i], tki_h[lane, i]) if want_top else None
                    ),
                )

    def _process_token(
        self, seq: Sequence, token: int, logprob: float | None = None,
        top=None,
    ) -> None:
        seq.output_ids.append(token)
        self._tokens_emitted += 1
        if seq.guided is not None:
            was_complete = seq.guided.complete
            seq.guided.advance(token)
            if seq.guided.complete and not was_complete:
                # count the completion on the closing-token TRANSITION: a
                # document that closes exactly on the max_tokens-th token
                # (finish=LENGTH below) is still a completed document
                self._guided_completions += 1
        finish = seq.hit_stop(token)
        if finish is None and seq.guided is not None and seq.guided.complete:
            # the document just closed: stop rather than sample trailing
            # whitespace until max_tokens
            finish = FinishReason.STOP
        if finish is None and seq.context_len >= self.max_len:
            finish = FinishReason.LENGTH
        if seq.emit:
            top_rows = None
            want = seq.request.sampling.top_logprobs
            if top is not None and want > 0:
                vals, ids = top
                k = min(want, len(ids))
                top_rows = [[[int(ids[i]), float(vals[i])] for i in range(k)]]
            # the part `emit` of `post`, once a token: two clock reads into
            # its row, taken out of the open part `tokens` (no annotation)
            t0 = time.perf_counter()
            seq.emit(
                [token], finish,
                logprobs=None if logprob is None else [logprob],
                top_logprobs=top_rows,
            )
            row = self._emit_row
            row[0] += time.perf_counter() - t0
            row[1] += 1
        if finish is not None:
            self._record_decode_span(seq)
            self._finish_decoded(seq)
        elif seq.context_len % self.config.block_size == 0 and seq.mm_embeds is None:
            # (multimodal blocks never publish: text-token hashes cannot
            # describe patch-embedding content)
            self._publish_stored(seq.seq_id, seq.tokens)

    def _publish_stored(self, seq_id: str, tokens) -> None:
        """``allocator.publish_stored``, booked as the part `publish` of
        `post` (a block's worth of tokens a lane: measured where it runs)."""
        t0 = time.perf_counter()
        self.allocator.publish_stored(seq_id, tokens)
        row = self._publish_row
        row[0] += time.perf_counter() - t0
        row[1] += 1
