"""DYN_PROFILER_TRACE_DIR wires utils.profiling into the engine serve path:
engine.start() opens a jax profiler trace, engine.stop() writes it — on the
CPU backend here, so the hook is covered without hardware.  The trace holds
the engine's always-on ``dyn.*`` host annotations and its named programs."""

import jax

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig, init_params
from dynamo_tpu.runtime.engine import Context

CFG = LlamaConfig.tiny()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


async def test_profiler_trace_dir_env_captures_serve_window(tmp_path, monkeypatch):
    trace_dir = tmp_path / "xprof"
    monkeypatch.setenv("DYN_PROFILER_TRACE_DIR", str(trace_dir))
    engine = JaxLlmEngine(
        EngineConfig(
            model=CFG, num_blocks=32, block_size=4, max_batch_size=2,
            prefill_buckets=(16,), max_model_len=64,
        ),
        params=PARAMS,
    )
    engine.start()
    try:
        assert engine._profiler_trace_dir == str(trace_dir)
        req = PreprocessedRequest(
            token_ids=[2, 3, 4, 5],
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=4, ignore_eos=True),
            eos_token_ids=[],
        )
        stream = await engine.generate(Context(req.to_wire()))
        async for _ in stream:
            pass
    finally:
        engine.stop()
    # stop() wrote the capture: xprof traces land under plugins/profile/
    written = list(trace_dir.rglob("*"))
    assert any(p.is_file() for p in written), written
    # with no switch set, the trace holds the step loop's host phases
    # ("dyn.<phase>" TraceAnnotations) beside the programs, which are named
    # by kind and bucket, not "step"
    from benchmark import host_spans, trace

    planes = host_spans.load_planes(trace.find_xplane(str(trace_dir)))
    names = {e[0] for lines in planes.values() for events in lines.values() for e in events}
    assert {"dyn.schedule", "dyn.upload", "dyn.dispatch", "dyn.readback", "dyn.post"} <= names
    # ... and the parts of the large ones that run once a step; `emit` and
    # `publish` run once a token and are never an annotation
    assert {"dyn.post.tokens", "dyn.schedule.admit", "dyn.schedule.slots", "dyn.schedule.build",
            "dyn.schedule.tables", "dyn.upload.sampling", "dyn.upload.arrays"} <= names
    assert not {"dyn.post.emit", "dyn.post.publish"} & names
    # the env-started trace runs without the Python tracer
    assert not any(n.startswith("$") for n in names)
    assert any(n.startswith("PjitFunction(dyn_unified_t16") for n in names), sorted(
        n for n in names if "Pjit" in n)
    assert any(n.startswith("PjitFunction(dyn_decode_w1") for n in names)
    assert not any(n.startswith("PjitFunction(step") for n in names)
    assert host_spans.attribute(planes)["annotations"]["dyn.dispatch"] >= 4
    # the env hook is once-per-process; a second engine must not re-arm it
    # against the (already consumed) global trace state
    engine2 = JaxLlmEngine(
        EngineConfig(
            model=CFG, num_blocks=32, block_size=4, max_batch_size=2,
            prefill_buckets=(16,), max_model_len=64,
        ),
        params=PARAMS,
    )
    engine2.start()
    try:
        assert engine2._profiler_trace_dir == str(trace_dir)
    finally:
        engine2.stop()
