"""aot_precompile contract: the concurrently-compiled programs must be the
EXACT programs the serving loop dispatches — an aval mismatch would
silently compile useless twins and the real path would recompile serially,
erasing the cold-start win.  The persistent compilation cache is the
bridge (and the detector: a matched program produces zero new entries)."""

import asyncio
import os

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxLlmEngine
from dynamo_tpu.llm.protocols.common import (
    Annotated,
    LLMEngineOutput,
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime.engine import Context


def _step_entries(cache_dir) -> set:
    # serving programs: prefill/prefix/verify jits are named "step",
    # the fused multi-step decode is named "multi".  One program may own
    # several files (-cache payload + the LRU policy's -atime sentinel):
    # count programs, not files
    return {
        f.removesuffix("-atime").removesuffix("-cache")
        for f in os.listdir(cache_dir)
        if f.startswith(("jit_step-", "jit_multi-"))
    }


def _reset_cache():
    # jax's compilation-cache singleton binds the directory at first use;
    # re-pointing jax_compilation_cache_dir between tests needs a reset
    from jax._src import compilation_cache as cc

    cc.reset_cache()


async def _drive(engine, n_tokens, max_tokens=12, seed=0):
    # distinct seeds per call: a shared prefix would prefix-hit and
    # dispatch a continued-prefill variant the AOT cold-start set
    # intentionally does not cover (those compile lazily as traffic warms)
    req = PreprocessedRequest(
        token_ids=[
            int(x)
            for x in np.random.default_rng(seed).integers(10, 250, n_tokens)
        ],
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        eos_token_ids=[],
    )
    req.sampling.use_greedy = True
    stream = await engine.generate(Context(req.to_wire()))
    count = 0
    async for item in stream:
        ann = Annotated.from_wire(item, LLMEngineOutput.from_wire)
        if ann.data and ann.data.token_ids:
            count += len(ann.data.token_ids)
    return count


@pytest.mark.slow
def test_aot_precompile_matches_serving_programs(tmp_path):
    import jax

    cache_dir = tmp_path / "jcache"
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _reset_cache()
    try:
        engine = JaxLlmEngine(
            EngineConfig(
                model=LlamaConfig.tiny(), num_blocks=128, block_size=4,
                max_batch_size=4, prefill_buckets=(16,), max_model_len=96,
                prefill_chunk_tokens=16, decode_steps=2,
                top_logprobs_k=0, logit_bias_k=4,
            )
        )
        n = engine.aot_precompile([40, 12], parallel=4)
        assert n >= 3  # chunked-prefix variants + short prefill + decode
        before = _step_entries(cache_dir)
        assert len(before) == n

        async def main():
            engine.start()
            try:
                # long prompt → chunked prefix windows; short → whole
                # prefill; both → the fused decode program
                assert await _drive(engine, 40, seed=0) == 12
                assert await _drive(engine, 12, seed=1) == 12
            finally:
                engine.stop()

        asyncio.run(main())
        after = _step_entries(cache_dir)
        assert after == before, (
            f"serving dispatched {len(after - before)} program(s) the AOT "
            f"pass missed: aval drift between aot_precompile and the "
            f"_run_prefill/_run_decode call sites"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
        _reset_cache()


@pytest.mark.slow
def test_warmup_uses_aot_when_cache_configured(tmp_path):
    """With a compilation cache configured, warmup AOT-compiles its planned
    programs in parallel and the warmup drives are pure cache hits."""
    import jax

    cache_dir = tmp_path / "jcache"
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _reset_cache()
    try:
        engine = JaxLlmEngine(
            EngineConfig(
                model=LlamaConfig.tiny(), num_blocks=128, block_size=4,
                max_batch_size=4, prefill_buckets=(16,), max_model_len=96,
                prefill_chunk_tokens=16, decode_steps=2,
                top_logprobs_k=0, logit_bias_k=4,
            )
        )

        async def main():
            engine.start()
            try:
                await engine.warmup()
                after_warmup = _step_entries(cache_dir)
                assert len(after_warmup) >= 3
                assert await _drive(engine, 12, seed=7) == 12
                assert _step_entries(cache_dir) == after_warmup
            finally:
                engine.stop()

        asyncio.run(main())
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
        _reset_cache()


def test_aot_precompile_lowers_on_the_calling_thread():
    """Lowering is sequential, in a fixed order, on the caller's thread;
    only compiles fan out.  A Pallas kernel's bytecode carries the source
    lines of whichever kernel first traced the jitted jnp helpers they
    share, so the order of first traces decides the cache keys: racing
    threads made them random (seen on the chip as a warm restart compiling
    the five programs its traffic needed)."""
    import threading

    engine = JaxLlmEngine(
        EngineConfig(
            model=LlamaConfig.tiny(), num_blocks=32, block_size=4,
            max_batch_size=2, prefill_buckets=(16,), max_model_len=32,
        )
    )
    where = {"lower": set(), "order": [], "compile": 0}

    class Program:
        def compile(self):
            where["compile"] += 1
            return self

        def memory_analysis(self):
            return None

    class Jit:
        def lower(self, *avals):
            where["lower"].add(threading.current_thread())
            where["order"].append(avals[0])
            return Program()

    engine._aot_jobs = lambda lens: {("fake", i): (Jit(), (i,)) for i in range(6)}
    assert engine.aot_precompile([8], parallel=4) == 6
    assert where["lower"] == {threading.current_thread()}
    assert where["order"] == list(range(6))
    assert where["compile"] == 6


def _big_cache_engine():
    """A tiny model under a cache that dwarfs its activations (8.4 MB of K
    and V against well under 1 MB of temporaries): a program that copies
    the cache cannot hide among them."""
    return JaxLlmEngine(
        EngineConfig(
            model=LlamaConfig.tiny(), num_blocks=4096, block_size=4,
            max_batch_size=2, prefill_buckets=(16,), max_model_len=32,
            top_logprobs_k=0,
        )
    )


def test_step_programs_hold_no_copy_of_the_cache():
    """The cache rides the layer loop as a carry and is written in place:
    what a decode or unified program allocates beside its arguments is
    activations.  (With the cache as per-layer scan inputs and stacked
    outputs each program held at least one whole cache there.)"""
    import jax

    engine = _big_cache_engine()
    assert engine.stats()["program_temp_bytes_max"] == 0  # nothing compiled yet
    assert engine.aot_precompile([12], parallel=2) >= 3
    cache_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(engine.cache))
    temps = engine.program_temp_bytes
    assert ("decode",) in temps and ("unified", 16) in temps, temps
    for name, temp in temps.items():
        assert temp < cache_bytes // 2, (name, temp, cache_bytes)
    stats = engine.stats()
    assert stats["program_temp_bytes_max"] == max(temps.values())
    assert stats["program_temp_bytes"]["decode"] == temps[("decode",)]
    assert stats["program_temp_bytes"]["unified_16"] == temps[("unified", 16)]


@pytest.mark.parametrize("program", [("decode",), ("unified", 16), ("prefill", 16)])
def test_donated_cache_buffer_is_the_returned_one(program):
    """One step with the donated cache: K and V come back in the buffers
    they went in with."""
    import jax
    import jax.numpy as jnp

    engine = _big_cache_engine()
    jit_fn, avals = engine._aot_jobs([12])[program]
    args = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), avals)
    cache = jax.tree.map(lambda a: jnp.ones(a.shape, a.dtype), avals[1])
    try:
        before = {name: leaf.unsafe_buffer_pointer() for name, leaf in cache.items()}
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"this backend exposes no buffer pointer: {e}")
    outs = jit_fn(args[0], cache, *args[2:])
    (new,) = [out for out in outs if isinstance(out, dict)]
    assert all(leaf.is_deleted() for leaf in cache.values())  # it WAS donated
    assert {
        name: leaf.unsafe_buffer_pointer() for name, leaf in new.items()
    } == before


def _resolve_in_child(env_value):
    """Resolve the cache dir in a FRESH interpreter (JAX reads
    JAX_COMPILATION_CACHE_DIR once, at import) with every
    jax_compilation_cache_dir update recorded."""
    import json
    import subprocess
    import sys

    code = (
        "import json, jax\n"
        "updates = []\n"
        "real = jax.config.update\n"
        "def spy(name, value):\n"
        "    updates.append(name)\n"
        "    return real(name, value)\n"
        "jax.config.update = spy\n"
        "from dynamo_tpu.utils.compile_cache import ensure_compile_cache\n"
        "path = ensure_compile_cache()\n"
        "print(json.dumps({'path': path, 'updates': updates,\n"
        "                  'configured': jax.config.jax_compilation_cache_dir}))\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=root, check=True,
        capture_output=True, text=True, timeout=120,
    )
    return json.loads(out.stdout.strip().splitlines()[-1]), root


def test_compile_cache_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache is exactly there and the
    program never calls jax.config.update("jax_compilation_cache_dir")."""
    where = str(tmp_path / "from-outside")
    got, _ = _resolve_in_child(where)
    assert got["path"] == where and got["configured"] == where
    assert "jax_compilation_cache_dir" not in got["updates"]


def test_compile_cache_defaults_to_the_checkout():
    """Unset: <checkout>/.jax_cache — a fixed path (never under $HOME, a
    temporary, or anything pid- or time-derived)."""
    got, root = _resolve_in_child(None)
    assert got["path"] == os.path.join(root, ".jax_cache")
    assert got["configured"] == got["path"]
    assert got["updates"].count("jax_compilation_cache_dir") == 1


@pytest.mark.slow
def test_second_engine_init_compiles_nothing_fresh(tmp_path, monkeypatch):
    """Restart survival: a SECOND engine init + warmup against a warm
    cache directory performs zero fresh compilations — every serving
    program is a persistent-cache hit."""
    import jax

    cache_dir = tmp_path / "jcache"
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _reset_cache()

    def cold_start():
        engine = JaxLlmEngine(
            EngineConfig(
                model=LlamaConfig.tiny(), num_blocks=128, block_size=4,
                max_batch_size=4, prefill_buckets=(16,), max_model_len=96,
                prefill_chunk_tokens=16, decode_steps=2,
                top_logprobs_k=0, logit_bias_k=4,
            )
        )

        async def main():
            engine.start()
            try:
                await engine.warmup()
                assert await _drive(engine, 12, seed=3) == 12
            finally:
                engine.stop()

        asyncio.run(main())
        return {
            f.removesuffix("-atime").removesuffix("-cache")
            for f in os.listdir(cache_dir)
        }

    try:
        # the engine ctor leaves an already-configured dir alone
        first = cold_start()
        assert jax.config.jax_compilation_cache_dir == str(cache_dir)
        assert _step_entries(cache_dir)
        # "restart": fresh process state as far as the persistent cache is
        # concerned (the in-memory jit caches cannot be dropped per-test,
        # so run the restart with a fresh engine + reset cache singleton)
        _reset_cache()
        second = cold_start()
        assert second == first, (
            f"second init compiled {len(second - first)} fresh program(s); "
            "the persistent compile cache did not survive the restart"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
        _reset_cache()
