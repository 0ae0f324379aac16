"""The engine's always-on accounting: every iteration booked by the kind of
window the device was executing, the host phases, the attention kernels'
work counted where the worklists are built, and every key in ``stats()``
from engine start."""

import time

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.engine.engine import (
    KERNEL_WORK_KEYS,
    STEP_PHASES,
    _InflightWindow,
)
from dynamo_tpu.observability.step_metrics import KIND_DECODE, KIND_PROMPT
from dynamo_tpu.ops.pallas import kv_step_pages, pack_spans

from tests.engine.test_jax_engine import CFG, PARAMS, collect, make_engine, request

STEP_KEYS = (
    "engine_decode_steps_total", "engine_decode_step_time_total_s",
    "engine_prompt_steps_total", "engine_prompt_step_time_total_s",
    "engine_host_time_total_s",
    "decode_lane_steps_total",
)


def idle_engine(**overrides) -> JaxLlmEngine:
    """An engine whose device thread never starts: the test plays the loop."""
    defaults = dict(model=CFG, num_blocks=64, block_size=4, max_batch_size=4,
                    prefill_buckets=(16, 32), max_model_len=128)
    defaults.update(overrides)
    return JaxLlmEngine(EngineConfig(**defaults), params=PARAMS)


class SlowArray:
    """Stands for a device array whose readback blocks ``wait_s``."""

    def __init__(self, wait_s: float, lanes: int = 4):
        self.wait_s, self.lanes = wait_s, lanes

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.wait_s)
        return np.zeros((self.lanes,), np.int32 if dtype is None else dtype)


async def settled_stats(engine) -> dict:
    """stats() once the loop has booked its last iteration (a stream ends at
    the emission, a beat before the iteration that emitted is booked)."""
    import asyncio

    seen = -1
    while True:
        stats = engine.stats()
        if stats["engine_steps_total"] == seen and not engine.scheduler.has_work():
            return stats
        seen = stats["engine_steps_total"]
        await asyncio.sleep(0.1)


def window(kind: str, wait_s: float) -> _InflightWindow:
    return _InflightWindow(tokens=SlowArray(wait_s), lps=SlowArray(0.0), feedback=None,
                           active=[], lane_ids=[], steps=1, kind=kind)


@pytest.mark.parametrize("key", STEP_KEYS + KERNEL_WORK_KEYS)
def test_every_new_counter_is_in_stats_from_engine_start_at_zero(key):
    """A reader that meets a missing key returns None and its metric
    silently vanishes, so the keys exist before the first step."""
    assert idle_engine().stats()[key] == 0


def test_phase_ms_and_spans_are_in_stats_from_engine_start():
    stats = idle_engine().stats()
    assert set(stats["phase_ms"]) == set(STEP_PHASES)
    assert set(stats["spans"]) == {"hist", "series"}
    assert set(stats["spans"]["hist"]) == {"min_s", "ratio", "buckets"}


def test_a_scripted_run_books_each_wait_to_the_window_that_was_on_the_device():
    """prompt P, then decodes D1 D2 under overlap: iteration k dispatches
    window k and waits for window k-1, so the long wait for P lands in the
    iteration that DISPATCHES D1, and is booked as a prompt step."""
    engine = idle_engine()
    script = [
        # (kind dispatched, window waited for, its readback time)
        (KIND_PROMPT, None, 0.0),            # P goes out, nothing to wait for
        (KIND_DECODE, KIND_PROMPT, 0.06),    # D1 goes out, the host waits for P
        (KIND_DECODE, KIND_DECODE, 0.01),    # D2 goes out, waits for D1
        (None, KIND_DECODE, 0.01),           # drain: waits for D2
    ]
    for dispatched, waited, wait_s in script:
        t_step, emitted = engine._begin_step()
        engine._step_dispatched_kind = dispatched
        if waited is not None:
            engine._retire_window(window(waited, wait_s))
        engine._end_step(t_step, emitted)
    stats = engine.stats()
    assert stats["engine_prompt_steps_total"] == 2      # P's dispatch and P's wait
    assert stats["engine_decode_steps_total"] == 2
    assert stats["engine_prompt_step_time_total_s"] >= 0.06
    assert 0.02 <= stats["engine_decode_step_time_total_s"] < 0.06
    phases = stats["phase_ms"]
    assert phases["readback"]["total_ms"] >= 80
    total = stats["engine_step_time_total_s"]
    assert total == pytest.approx(stats["engine_prompt_step_time_total_s"]
                                  + stats["engine_decode_step_time_total_s"])
    # host time is step time less the `readback` phase
    assert stats["engine_host_time_total_s"] == pytest.approx(
        total - phases["readback"]["total_ms"] / 1e3, abs=1e-4)
    assert stats["engine_host_time_total_s"] < 0.02
    # the existing pair stays what it was: every iteration, whatever its kind
    assert stats["engine_steps_total"] == 4
    assert phases["readback"]["n"] == 3 and phases["schedule"]["n"] == 4


def traced_seq(final: bool):
    """The little of a Sequence that a prefill span reads."""
    from types import SimpleNamespace

    from dynamo_tpu.engine.sequence import SeqStatus
    from dynamo_tpu.observability.trace import TraceContext

    return SimpleNamespace(
        trace=TraceContext.new_root(), arrival_ts=time.time(), ttft_recorded=False,
        status=SeqStatus.RUNNING if final else SeqStatus.PREFILLING,
        prefilled_tokens=8, cached_tokens=0)


def test_a_chunk_nothing_waits_for_hands_its_kind_and_its_span_to_the_next_window():
    """A chunk-only unified window (or a split prefill's intermediate chunk)
    goes out and nothing is put in flight for it.  On the device it runs
    before the decode window dispatched next, so the wait for THAT window
    covers the chunk: it is a prompt step, and the chunk's engine.prefill
    span closes there, not at its dispatch.  A lane of the chunk that samples
    (its program sorts the vocabulary) rides the same way."""
    from dynamo_tpu.observability import get_recorder

    engine = idle_engine()
    seq = traced_seq(final=False)
    # iteration 1: D0 is in flight; the chunk goes out, the host waits for D0
    t_step, emitted = engine._begin_step()
    engine._step_dispatched_kind = KIND_PROMPT
    engine._packed_samples = True
    engine._unwaited_prefills.append(engine._open_prefill_span(seq, time.time()))
    engine._retire_window(window(KIND_DECODE, 0.01))
    engine._end_step(t_step, emitted)
    assert engine.stats()["engine_decode_steps_total"] == 1     # D0 was on the device
    assert not get_recorder().spans_for(seq.trace.trace_id)     # the chunk is still out
    # iteration 2: D1 goes out behind the chunk and inherits it; nothing to wait for
    t_step, emitted = engine._begin_step()
    kind, prefills, samples = engine._take_unwaited(KIND_DECODE)
    engine._step_dispatched_kind = kind
    d1 = window(kind, 0.05)
    d1.prefills, d1.samples = prefills, samples
    engine._end_step(t_step, emitted)
    assert kind == KIND_PROMPT and samples and engine._unwaited_prefills == []
    # iteration 3: D2 goes out (a plain decode window again), the host waits for chunk + D1
    t_step, emitted = engine._begin_step()
    assert engine._take_unwaited(KIND_DECODE) == (KIND_DECODE, [], False)
    engine._step_dispatched_kind = KIND_DECODE
    engine._retire_window(d1)
    assert engine._step_waited_samples
    engine._end_step(t_step, emitted)
    stats = engine.stats()
    assert stats["engine_prompt_steps_total"] == 2 and stats["engine_decode_steps_total"] == 1
    assert stats["engine_prompt_step_time_total_s"] >= 0.05
    assert stats["engine_decode_step_time_total_s"] < 0.05
    (span,) = get_recorder().spans_for(seq.trace.trace_id)
    assert span.name == "engine.prefill" and span.duration_s >= 0.05
    assert "ttft_s" not in span.attrs                           # an intermediate chunk


def test_a_final_prefill_windows_span_carries_ttft_when_the_window_retires():
    from dynamo_tpu.observability import get_recorder

    engine = idle_engine()
    seq = traced_seq(final=True)
    w = window(KIND_PROMPT, 0.03)
    w.prefills = [engine._open_prefill_span(seq, time.time())]
    engine._retire_window(w)
    (span,) = get_recorder().spans_for(seq.trace.trace_id)
    assert span.duration_s >= 0.03 and span.attrs["ttft_s"] >= 0.03
    assert seq.ttft_recorded


def test_a_window_retired_outside_a_step_leaves_no_phase_open():
    """An abort or clear_kv reaches _sync_pipeline from _drain_submissions,
    between steps: `post` must close with the retire, or every idle second
    that follows is booked to it (and drawn as one long dyn.post)."""
    engine = idle_engine()
    engine._inflight = window(KIND_DECODE, 0.0)
    engine._sync_pipeline()
    assert engine._phase_name is None and engine._inflight is None
    time.sleep(0.05)
    assert engine.stats()["phase_ms"]["post"]["total_ms"] < 40
    # inside a step it resumes the phase it interrupted
    t_step, emitted = engine._begin_step()
    engine._inflight = window(KIND_DECODE, 0.0)
    engine._sync_pipeline()
    assert engine._phase_name == "schedule"
    engine._end_step(t_step, emitted)


def test_an_iteration_that_waited_for_a_prompt_window_and_a_decode_window_is_a_prompt_step():
    engine = idle_engine()
    t_step, emitted = engine._begin_step()
    engine._retire_window(window(KIND_PROMPT, 0.0))
    engine._retire_window(window(KIND_DECODE, 0.0))
    engine._end_step(t_step, emitted)
    assert engine.stats()["engine_prompt_steps_total"] == 1


@pytest.mark.parametrize("overlap,prompt_steps", [(True, 2), (False, 1)])
async def test_the_classifier_on_a_real_request(overlap, prompt_steps):
    """One request alone: one prompt window, then decode windows.  Under
    overlap the prompt window costs two iterations (its dispatch and, one
    iteration later, the wait for it); synchronous, one."""
    engine = make_engine(decode_overlap=overlap)
    try:
        await collect(engine, request(list(range(3, 9)), max_tokens=6, ignore_eos=True))
        stats = await settled_stats(engine)
        assert stats["engine_prompt_steps_total"] == prompt_steps
        assert stats["engine_decode_steps_total"] >= 4
        assert (stats["engine_prompt_steps_total"] + stats["engine_decode_steps_total"]
                == stats["engine_steps_total"])
        assert (stats["engine_prompt_step_time_total_s"] + stats["engine_decode_step_time_total_s"]
                == pytest.approx(stats["engine_step_time_total_s"]))
        # one lane decoded in every decode-carrying step
        assert stats["decode_lane_steps_total"] == stats["decode_steps_total"] >= 5
    finally:
        engine.stop()


@pytest.mark.parametrize("unified", [True, False])
async def test_a_chunked_prompt_on_a_real_engine_closes_one_prefill_span_a_chunk(unified):
    """20 prompt tokens in chunks of 8: three prefill windows, the first two
    with nothing waiting for them.  Every chunk gets its engine.prefill span,
    each closes at a wait that came AFTER its dispatch (a later chunk never
    closes before an earlier one), only the last carries the TTFT, and
    nothing is left open."""
    from dynamo_tpu.observability import get_recorder
    from dynamo_tpu.observability.trace import TraceContext
    from dynamo_tpu.runtime.engine import Context

    engine = make_engine(prefill_chunk_tokens=8, unified_batch=unified)
    try:
        ctx = Context(request(list(range(3, 23)), max_tokens=4, ignore_eos=True))
        ctx.ctx.trace = TraceContext.new_root()
        stream = await engine.generate(ctx)
        async for _ in stream:
            pass
        stats = await settled_stats(engine)
        spans = [s for s in get_recorder().spans_for(ctx.ctx.trace.trace_id)
                 if s.name == "engine.prefill"]
        assert [s.attrs["prefilled_tokens"] for s in spans] == [8, 16, 20]
        assert ["ttft_s" in s.attrs for s in spans] == [False, False, True]
        ends = [s.end_s for s in spans]
        assert ends == sorted(ends) and all(s.end_s > s.start_s for s in spans)
        assert engine._unwaited_prefills == []
        assert (stats["decode_windows_unified_total"] > 0) == unified
        assert stats["engine_prompt_steps_total"] >= 3
        assert engine._phase_name is None
    finally:
        engine.stop()


# -- the ragged kernel's page spans -----------------------------------------

def hand_counted(spans, tb, bs, window=None, pages=1):
    """(pages copied, KV steps) of a flat batch laid out span after span: per
    token block and lane, the pages from the first one the window still
    shows the block's first token to the one holding its last token, and
    the steps of ``pages`` pages that run of pages takes."""
    flat = [(lane, pos) for lane, start, end in spans for pos in range(start, end)]
    live = steps = 0
    for t in range(0, len(flat), tb):
        by_lane = {}
        for lane, pos in flat[t:t + tb]:
            lo, hi = by_lane.get(lane, (pos, pos))
            by_lane[lane] = (min(lo, pos), max(hi, pos))
        for lo, hi in by_lane.values():
            first = 0 if window is None else max(0, lo - (window - 1)) // bs
            live += hi // bs + 1 - first
            steps += -(-(hi // bs + 1 - first) // pages)
    return live, steps


@pytest.mark.parametrize("pages", [1, 3, None])
@pytest.mark.parametrize("window", [None, 8])
def test_pack_spans_page_iterations_against_a_hand_count(window, pages):
    tb, bs, lanes = 8, 4, 3
    spans = [(0, 0, 13), (1, 20, 21), (2, 5, 27)]   # a prompt, a decode token, a later chunk
    tokens = sum(end - start for _, start, end in spans)
    t_pad = -(-tokens // tb) * tb
    token_lane = np.full((t_pad,), lanes, np.int32)
    token_pos = np.full((t_pad,), -1, np.int32)
    i = 0
    for lane, start, end in spans:
        token_lane[i:i + end - start] = lane
        token_pos[i:i + end - start] = np.arange(start, end)
        i += end - start
    span_lane, _, span_count, kv_steps = pack_spans(
        token_lane, token_pos, lanes=lanes, tb_tokens=tb, block_size=bs,
        sliding_window=window, pages_per_step=pages)
    # the kernel copies a span's pages once each and its loop runs
    # kv_steps[t] steps for block t: each span's pages in steps of `pages`
    # (the kernels' own width where none is given), and nothing for a span
    # that is not there
    per_step = pages or kv_step_pages(bs)
    live, steps = hand_counted(spans, tb, bs, window, per_step)
    assert (int(span_count.sum()), int(kv_steps.sum())) == (live, steps)
    assert kv_steps.tolist() == (
        -(-span_count.reshape(-1, tb) // per_step)).sum(axis=1).tolist()
    assert (span_count[span_lane < 0] == 0).all()
    if window is None:
        # block by block: [13 of lane 0 -> 2 then 4 pages incl. the decode token's 6], ...
        assert live == 2 + (4 + 6 + 2) + 4 + 6 + 7
        if pages == 3:
            assert steps == 1 + (2 + 2 + 1) + 2 + 2 + 3
    else:
        assert live < hand_counted(spans, tb, bs, None)[0]


def test_pack_spans_on_a_2048_token_prompt_equals_the_closed_forms():
    """A 2,048-token prompt alone, pages of 16: a block of TB tokens is
    block i of 2,048 / TB and sees (i + 1) TB / 16 pages, so the kernel
    copies (2,048 / TB) (2,048 / TB + 1) / 2 x TB / 16 pages: 16,512 at 8
    tokens a block (PR 28's kernel), 4,160 at 32, 2,112 at 64; and at 16
    pages a KV step, 64 tokens a block, it runs sum(ceil((i + 1) / 4)) =
    144 steps."""
    pos = np.arange(2048, dtype=np.int32)
    lane = np.zeros(2048, np.int32)

    def packed(tb, pages):
        _, _, count, steps = pack_spans(
            lane, pos, lanes=1, tb_tokens=tb, block_size=16, pages_per_step=pages)
        return int(count.sum()), int(steps.sum())

    assert packed(8, 1) == (16512, 16512)
    assert packed(32, 8) == (4160, 544)
    assert packed(64, 8) == (2112, 272)
    assert packed(64, 16) == (2112, 144)
    assert kv_step_pages(16) == 16 and packed(64, None) == (2112, 144)


def recount(engine, spans, bucket):
    """The kernel-work totals of one unified window, from pack_spans over
    the window's flat batch as the engine lays it out (span after span,
    padded to the bucket) at the token block the engine packs that bucket to."""
    tb, bs = engine._tb_for(bucket), engine.config.block_size
    lanes = engine.config.max_batch_size
    token_lane = np.full((bucket,), lanes, np.int32)
    token_pos = np.full((bucket,), -1, np.int32)
    i = 0
    for lane, start, end in spans:
        token_lane[i:i + end - start] = lane
        token_pos[i:i + end - start] = np.arange(start, end)
        i += end - start
    _, _, count, steps = pack_spans(
        token_lane, token_pos, lanes=lanes, tb_tokens=tb, block_size=bs)
    launched = np.repeat(steps > 0, tb)
    return {
        "ragged_live_pages_total": int(count.sum()),
        "ragged_kv_steps_total": int(steps.sum()),
        "ragged_page_slots_total": int(steps.sum()) * kv_step_pages(bs),
        "ragged_token_blocks_total": int((steps > 0).sum()),
        "ragged_live_rows_total": int((launched & (token_pos >= 0)).sum()),
    }


def test_the_token_block_is_the_head_geometrys_and_a_bucket_is_cut_to_what_it_holds():
    """No table and no knob: the tiny geometry (two query heads a KV head,
    pages of 4) gets the default block, and each bucket its divisor of it."""
    engine = idle_engine(prefill_buckets=(16, 32, 64))
    # 64 tokens keep a product's score rows under 256; the buckets pack to
    # blocks of 16 / 32 / 64
    assert engine.stats()["kernel_config"] == {"tb_tokens": 64}
    assert [engine._tb_for(b) for b in (16, 32, 64)] == [16, 32, 64]
    # the worklist width and its overflow counter went with the static worklists
    assert "unified_ps_overflows_total" not in engine.stats()


async def test_the_engines_ragged_counters_equal_a_recount_from_the_request():
    """Pallas (interpreted) unified engine, one request of 21 prompt tokens:
    the window's kernel work recounted from the request's length."""
    engine = make_engine(attention_impl="pallas_interpret", block_size=8, num_blocks=32,
                         decode_overlap=False)
    try:
        assert engine.unified_batch
        n = 21
        await collect(engine, request(list(range(3, 3 + n)), max_tokens=3, ignore_eos=True))
        stats = await settled_stats(engine)
        bs = engine.config.block_size
        assert stats["decode_windows_unified_total"] == 1
        # the 32-token bucket is ONE token block (two query heads share a KV
        # head: the block could hold 64 tokens): it copies the prompt's 3
        # pages of 8 positions once, in one KV step of 16 page places
        assert (engine._bucket_len(n), engine._tb_for(32)) == (32, 32)
        want = recount(engine, [(0, 0, n)], 32)
        assert want == {
            "ragged_live_pages_total": 3, "ragged_kv_steps_total": 1,
            "ragged_page_slots_total": 16, "ragged_token_blocks_total": 1,
            "ragged_live_rows_total": n,
        }
        assert {k: stats[k] for k in want} == want
        cost = engine.utilization.cost
        assert stats["ragged_attn_flops_total"] == cost.attn_flops(n * (n + 1) // 2)
        assert stats["ragged_kv_read_bytes_total"] == 3 * bs * cost.kv_bytes_per_token
        # two decode windows followed, each over whole pages of the context
        ctxs = [n + 1, n + 2]
        assert stats["decode_attn_flops_total"] == cost.attn_flops(sum(ctxs))
        assert stats["decode_kv_read_bytes_total"] == (
            sum(-(-c // bs) for c in ctxs) * bs * cost.kv_bytes_per_token)
        # attention is split out of the model totals, not added to them
        assert stats["model_flops_total"] == pytest.approx(
            cost.flops(n + 2, n * (n + 1) // 2 + sum(ctxs)))
        assert stats["phase_ms"]["pack"]["n"] == 1
    finally:
        engine.stop()


async def test_a_mixed_windows_kv_steps_and_their_fill():
    """A prompt admitted beside a running decode (one unified window with a
    span and a packed decode lane), at a small token block so that several
    blocks and partly filled steps occur: every kernel-work total equals
    pack_spans' recount of the two windows, and the live-page share reads
    the fill of the KV steps executed."""
    import asyncio

    engine = make_engine(attention_impl="pallas_interpret", block_size=8, num_blocks=64,
                         decode_overlap=False, prefill_buckets=(24, 40))
    try:
        assert (engine._tb_for(24), engine._tb_for(40)) == (8, 8)
        first = asyncio.ensure_future(collect(
            engine, request(list(range(3, 3 + 12)), max_tokens=24, ignore_eos=True)))
        while engine.stats()["decode_steps_total"] < 2:
            await asyncio.sleep(0.01)
        await collect(engine, request(list(range(40, 40 + 19)), max_tokens=2, ignore_eos=True))
        await first
        stats = await settled_stats(engine)
        assert stats["decode_windows_unified_total"] == 2
        # window 1: the 12-token prompt alone.  window 2: the decode lane's
        # token at context c (its pages: ceil(c / 8)) packed in front of
        # the 19-token prompt, so the blocks of 8 hold [decode + 7], [8], [4]
        alone = recount(engine, [(0, 0, 12)], 24)
        mixed = [recount(engine, [(0, c - 1, c), (1, 0, 19)], 24) for c in range(13, 13 + 24)]
        got = {k: stats[k] - alone[k] for k in alone}
        assert got in mixed, (got, mixed)
        # [decode + 7] copies the decode lane's 2-5 pages and the prompt's
        # first, [8] two, [4] three: four KV steps of 16 places each
        assert got["ragged_kv_steps_total"] == 4
        assert got["ragged_token_blocks_total"] == 3 and got["ragged_live_rows_total"] == 20
        live, slots = stats["ragged_live_pages_total"], stats["ragged_page_slots_total"]
        assert slots == 16 * stats["ragged_kv_steps_total"]
        assert 0 < live / slots < 1
        assert stats["ragged_kv_read_bytes_total"] == (
            live * 8 * engine.utilization.cost.kv_bytes_per_token)
    finally:
        engine.stop()


@pytest.mark.parametrize("start,end,window,want", [
    (0, 5, None, 15), (0, 5, 8, 15), (0, 10, 4, 1 + 2 + 3 + 4 * 7),
    (6, 10, 4, 16), (2, 6, 4, 3 + 4 + 4 + 4), (9, 10, 4, 4), (3, 4, 4, 4),
])
def test_attended_context_under_a_sliding_window(start, end, window, want):
    engine = idle_engine()
    engine._sliding_window = window
    assert engine._attended_ctx(start, end) == (sum(range(start + 1, end + 1)), want)
    assert want == sum(min(p + 1, window or p + 1) for p in range(start, end))
