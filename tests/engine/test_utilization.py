"""Utilization accounting (observability/perf.py): hand-computed cost-model
geometry, rolling MFU/MBU/goodput math, and the engine integration — after a
real generate, stats() must carry nonzero utilization and token totals."""

import jax
import pytest

from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.mixtral import MixtralConfig
from dynamo_tpu.observability.perf import (
    ModelCost,
    UtilizationTracker,
    detect_peaks,
    model_cost,
)
from dynamo_tpu.observability.step_metrics import StepRecord

# tiny geometry chosen so every term is hand-checkable
TINY = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    tie_word_embeddings=False,
)

# per layer: q 64*4*16=4096, k/v 2*(64*2*16)=4096, o 4*16*64=4096 → 12288
ATTN_PER_LAYER = 12288
MLP_PER_LAYER = 3 * 64 * 128          # 24576
EMBED = 256 * 64                      # 16384 (embed) + 16384 (head)


def test_cost_model_hand_computed():
    c = model_cost(TINY)
    assert c.param_count == 2 * EMBED + 2 * (ATTN_PER_LAYER + MLP_PER_LAYER)
    # active matmul params: unembed + per-layer weights (embedding lookup
    # is a gather, not a matmul)
    assert c.linear_flops_per_token == 2 * (
        EMBED + 2 * (ATTN_PER_LAYER + MLP_PER_LAYER)
    )
    # QK^T + AV: 4 * layers * heads * head_dim per attended context token
    assert c.attn_flops_per_ctx_token == 4 * 2 * 4 * 16
    # K + V rows: 2 * layers * kv_heads * head_dim * 2 bytes (bf16)
    assert c.kv_bytes_per_token == 2 * 2 * 2 * 16 * 2
    # bf16 weights
    assert c.weight_bytes == c.param_count * 2


def test_cost_model_quantize_and_kv_dtype():
    base = model_cost(TINY)
    int8 = model_cost(TINY, quantize="int8")
    assert int8.weight_bytes == base.param_count * 1
    assert int8.linear_flops_per_token == base.linear_flops_per_token
    fp8_kv = model_cost(TINY, kv_cache_dtype="fp8")
    assert fp8_kv.kv_bytes_per_token == base.kv_bytes_per_token // 2


def test_cost_model_moe_counts_active_flops_total_bytes():
    cfg = MixtralConfig.tiny_moe()   # h=64 L=2 ie=96 E=4 k=2 v=512 tied f32
    c = model_cost(cfg)
    attn = 12288                     # same attention geometry as TINY
    mlp_total = 4 * 3 * 64 * 96 + 64 * 4     # all experts + router
    mlp_active = 2 * 3 * 64 * 96 + 64 * 4    # routed experts + router
    assert c.param_count == 512 * 64 + 2 * (attn + mlp_total)   # tied embed
    # flops use the ROUTED experts; the tied unembedding still projects
    assert c.linear_flops_per_token == 2 * (512 * 64 + 2 * (attn + mlp_active))
    assert c.weight_bytes == c.param_count * 4   # float32 resident weights


def test_cost_model_never_raises_on_exotic_configs():
    class Weird:
        pass

    c = model_cost(Weird())
    assert isinstance(c, ModelCost)
    assert c.param_count > 0


def test_tracker_rates_are_hand_computable():
    cost = ModelCost(
        param_count=100, weight_bytes=200, linear_flops_per_token=10,
        attn_flops_per_ctx_token=2, kv_bytes_per_token=4,
    )
    t = UtilizationTracker(
        cost, peak_flops=1000.0, peak_bytes_per_s=1000.0, window_s=10.0
    )
    # one step at t=100: 5 tokens, 10 ctx tokens, 1 weight stream, 5 emitted
    t.observe(StepRecord(duration_s=1.0, prefill_tokens=3, decode_tokens=2, attn_ctx_tokens=10,
        weight_streams=1, emitted_tokens=5), now=100.0)
    r = t.rates(now=101.0)
    # flops = 5*10 + 10*2 = 70 over 1s of 1000 peak
    assert abs(r["mfu_perc"] - 0.07) < 1e-9
    # bytes = 200 + 5*4 + 10*4 = 260 over 1s of 1000 peak
    assert abs(r["bandwidth_util_perc"] - 0.26) < 1e-9
    assert abs(r["goodput_tokens_per_second"] - 5.0) < 1e-9
    assert abs(r["prefill_tokens_per_second"] - 3.0) < 1e-9
    # totals are cumulative and survive window pruning
    t.observe(StepRecord(duration_s=1.0, prefill_tokens=1), now=200.0)
    assert t.prefill_tokens_total == 4
    assert t.decode_tokens_total == 2
    # the window moved on: only the t=200 sample remains
    r2 = t.rates(now=201.0)
    assert r2["goodput_tokens_per_second"] == 0.0


def test_tracker_idle_gaps_drag_utilization_down():
    cost = ModelCost(100, 200, 10, 2, 4)
    t = UtilizationTracker(cost, peak_flops=1000.0, peak_bytes_per_s=1e12,
                           window_s=100.0)
    t.observe(StepRecord(duration_s=1.0, decode_tokens=10), now=0.0)
    # same work, read after 1s vs after 10s of wall clock
    busy = t.rates(now=1.0)["mfu_perc"]
    idle = t.rates(now=10.0)["mfu_perc"]
    assert idle < busy / 5


def test_detect_peaks_env_override(monkeypatch):
    monkeypatch.setenv("DYN_PEAK_TFLOPS", "123")
    monkeypatch.setenv("DYN_PEAK_GBPS", "456")
    flops, bw = detect_peaks()
    assert flops == 123e12
    assert bw == 456e9


def test_unknown_device_kind_has_no_peak(monkeypatch):
    """One table keyed by device_kind: a kind missing from it raises in the
    device-metric path, and the always-on tracker reports "unknown" (None)
    instead of a utilization against an assumed peak."""
    from dynamo_tpu.observability.perf import DEVICE_PEAKS, device_peaks

    assert device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        device_peaks("TPU v9 imaginary")
    assert "cpu" not in DEVICE_PEAKS
    monkeypatch.delenv("DYN_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("DYN_PEAK_GBPS", raising=False)
    assert detect_peaks() is None  # tests run on the CPU backend
    t = UtilizationTracker(ModelCost(10, 20, 20, 4, 8), window_s=10.0)
    t.observe(StepRecord(duration_s=0.1, decode_tokens=4, attn_ctx_tokens=8,
                   weight_streams=1.0, emitted_tokens=4), now=1.0)
    r = t.rates(now=1.0)
    assert r["mfu_perc"] is None and r["bandwidth_util_perc"] is None
    assert r["goodput_tokens_per_second"] > 0.0


async def test_engine_stats_export_utilization(monkeypatch):
    """End to end on a real tiny engine: a generate must leave nonzero
    token totals, rolling rates, and the wasted-work counters in stats()."""
    from tests.engine.test_jax_engine import collect, make_engine, request

    # the CPU backend has no published peak; name one like an operator would
    monkeypatch.setenv("DYN_PEAK_TFLOPS", "0.5")
    monkeypatch.setenv("DYN_PEAK_GBPS", "50")
    engine = make_engine()
    try:
        tokens, _finish = await collect(engine, request([2, 3, 4, 5], max_tokens=4))
        assert tokens
        stats = engine.stats()
        for key in (
            "mfu_perc", "bandwidth_util_perc", "goodput_tokens_per_second",
            "prefill_tokens_per_second", "prefill_tokens_total",
            "decode_tokens_total", "tokens_emitted_total",
            "preempted_tokens_total", "spec_rejected_tokens_total",
            "wasted_tokens_total",
        ):
            assert key in stats, key
        assert stats["prefill_tokens_total"] >= 4
        assert stats["decode_tokens_total"] >= len(tokens) - 1
        assert stats["tokens_emitted_total"] == len(tokens)
        assert stats["mfu_perc"] > 0.0
        assert stats["bandwidth_util_perc"] > 0.0
        assert stats["goodput_tokens_per_second"] > 0.0
        assert stats["wasted_tokens_total"] == 0
    finally:
        engine.stop()


async def test_preemption_counts_wasted_tokens():
    """KV-pressure preemption must surface in preempted_tokens_total —
    the recompute is real work a client never sees."""
    from tests.engine.test_jax_engine import collect, make_engine, request

    # tiny pool → long generations collide and preempt
    engine = make_engine(num_blocks=8, block_size=4, max_batch_size=4)
    try:
        import asyncio

        results = await asyncio.gather(
            *(collect(engine, request([2, 3, 4, i], max_tokens=24))
              for i in range(2, 6)),
            return_exceptions=True,
        )
        assert any(not isinstance(r, Exception) for r in results)
        stats = engine.stats()
        if stats["num_preemptions_total"]:
            assert stats["preempted_tokens_total"] > 0
            assert stats["wasted_tokens_total"] >= stats["preempted_tokens_total"]
    finally:
        engine.stop()
