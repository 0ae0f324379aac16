"""Profiling hooks.

The reference relies on external genai-perf plus ``tracing`` spans
(SURVEY.md §5); on TPU the interesting plane is the device: this wraps
``jax.profiler`` so any engine process can expose traces.

- ``start_server(port)``: serve the profiler so TensorBoard/xprof can attach.
- env ``DYN_PROFILER_PORT``: auto-start the profiler server in serving paths.
- env ``DYN_PROFILER_TRACE_DIR``: capture a device trace of the whole engine
  serve window (``maybe_start_trace_from_env`` at engine start,
  ``maybe_stop_trace`` at engine stop) — open the result in TensorBoard /
  xprof, where the engine's ``dyn.<phase>`` and ``dyn.<phase>.<part>``
  annotations (always emitted) sit beside the device planes.  The Python
  tracer is off in this trace.
"""

from __future__ import annotations


from dynamo_tpu.utils.logging import get_logger
from dynamo_tpu.utils import knobs

logger = get_logger("utils.profiling")

_server_started = False
_trace_dir: str | None = None


def start_server(port: int = 9012) -> None:
    global _server_started
    if _server_started:
        return
    import jax

    jax.profiler.start_server(port)
    _server_started = True
    logger.info("jax profiler server on port %d", port)


def maybe_start_from_env() -> None:
    port = knobs.get("DYN_PROFILER_PORT")
    if port:
        start_server(port)


def maybe_start_trace_from_env() -> str | None:
    """Start a long-running device trace into ``DYN_PROFILER_TRACE_DIR``
    (once per process; the engine serve path calls this at start).  Returns
    the directory when THIS call started the trace, else None — the caller
    that got the directory owns the matching ``maybe_stop_trace``."""
    global _trace_dir
    log_dir = knobs.get("DYN_PROFILER_TRACE_DIR")
    if not log_dir or _trace_dir is not None:
        return None
    import jax

    # no Python tracer: it records every call of the host code (740 k events
    # and 30 MB for a four-token request), slows the loop it is to show by
    # an order of magnitude and is not what this trace is for
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(log_dir, profiler_options=options)
    except Exception as exc:  # noqa: BLE001 — profiling must never stop serving
        logger.warning("profiler trace start failed: %r", exc)
        return None
    _trace_dir = log_dir
    logger.info("profiler trace capturing to %s", log_dir)
    return log_dir


def maybe_stop_trace() -> None:
    """Stop the env-started trace (no-op when none is active)."""
    global _trace_dir
    if _trace_dir is None:
        return
    import jax

    try:
        jax.profiler.stop_trace()
        logger.info("profiler trace written to %s", _trace_dir)
    except Exception as exc:  # noqa: BLE001
        logger.warning("profiler trace stop failed: %r", exc)
    finally:
        _trace_dir = None
