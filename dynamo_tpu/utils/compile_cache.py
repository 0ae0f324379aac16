"""The persistent XLA compile cache: one resolver, one pair of counters.

Placement is decided OUTSIDE the program.  When ``JAX_COMPILATION_CACHE_DIR``
is set (or an embedding program configured ``jax_compilation_cache_dir``
itself) JAX already points there and nothing here touches the setting.
Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path,
because the path is part of what makes a later start find the entries of an
earlier one.
"""

from __future__ import annotations

import threading
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_lock = threading.Lock()
_counts = {"requests": 0, "hits": 0}
_listening = False


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        with _lock:
            _counts["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _counts["hits"] += 1


def ensure_compile_cache() -> str:
    """Resolve the cache directory (see module docstring) and start counting
    compile requests against it.  Every program is persisted, however quick
    its compile: a restart must find ALL of them, and the count of fresh
    compiles after a warm start is then exactly zero."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # A Pallas kernel travels inside its program as serialized bytecode WITH
    # its MLIR locations, which by default hold the Python call stack of
    # whoever traced the kernel first — so the AOT twin of a program never
    # matched the one the device thread dispatched, and a restart missed
    # whenever the tracing order differed (seen on the chip: every jit_step
    # compiled twice, 7 fresh compiles after a warm restart).  One frame —
    # the op's own line — keeps cache keys independent of the call path.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path


def compile_counts() -> dict:
    """Programs this process asked the compiler for since
    ``ensure_compile_cache``: how many the persistent cache answered and
    how many were compiled fresh."""
    with _lock:
        requests, hits = _counts["requests"], _counts["hits"]
    return {
        "compile_requests_total": requests,
        "compile_cache_hits_total": hits,
        "compiles_total": requests - hits,
    }
