"""Native (C++) component loader.

Builds csrc/ sources on demand with g++ into ``csrc/build/`` and binds them
via ctypes (this image has no pybind11; the C ABI keeps the boundary thin).
``DYN_DISABLE_NATIVE=1`` forces the pure-Python twins.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path

from dynamo_tpu.utils.logging import get_logger
from dynamo_tpu.utils import knobs

logger = get_logger("native")

CSRC = Path(__file__).parent.parent.parent / "csrc"
BUILD = CSRC / "build"

_libs: dict[str, ctypes.CDLL | None] = {}
_status: dict[str, str] = {}


def load_native(name: str) -> ctypes.CDLL | None:
    """Compile (cached) + load ``csrc/<name>.cpp`` as lib<name>.so."""
    if knobs.get("DYN_DISABLE_NATIVE"):
        _status[name] = "disabled (DYN_DISABLE_NATIVE)"
        return None
    if name in _libs:
        return _libs[name]
    source = CSRC / f"{name}.cpp"
    lib_path = BUILD / f"lib{name}.so"
    try:
        built = False
        if not lib_path.exists() or source.stat().st_mtime > lib_path.stat().st_mtime:
            BUILD.mkdir(parents=True, exist_ok=True)
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                 str(source), "-o", str(lib_path)],
                check=True, capture_output=True, text=True,
            )
            logger.info("built native %s", lib_path.name)
            built = True
        _libs[name] = ctypes.CDLL(str(lib_path))
        _status[name] = "built+loaded" if built else "loaded"
    except (subprocess.CalledProcessError, OSError) as exc:
        detail = getattr(exc, "stderr", "") or repr(exc)
        logger.warning("native %s unavailable (%s); using Python fallback", name, detail)
        _libs[name] = None
        _status[name] = f"python fallback: {detail.strip()[:200]}"
    return _libs[name]


def native_status() -> dict[str, str]:
    """Build/load every ``csrc/*.cpp`` and say what happened to each:
    "built+loaded", "loaded", or why the Python twin serves instead (a
    missing compiler shows up here, not only in a warning)."""
    for source in sorted(CSRC.glob("*.cpp")):
        load_native(source.stem)
    return dict(_status)
