"""A configuration's own code, found by the two names its file gives.

``reference`` is a module under ``<bench_dir>/reference/`` (the plain
reference ``reference/check.py`` holds the served tokens against) and
``shapes`` one under ``<bench_dir>/`` (parameters, bytes and operations from
the configuration's keys).  Both are loaded by file, so a later PR, or a
test's temporary directory, brings them as new files; the interfaces are in
``benchmark/README.md``.  A configuration that names neither is an error:
nothing falls back to a default.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path

BARE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
KEYS = {"reference": "reference", "shapes": "."}   # key -> directory under bench_dir


def path_of(config: dict, key: str, bench_dir: Path, config_name: str) -> Path:
    name = config.get(key)
    if not isinstance(name, str) or not BARE.match(name):
        raise SystemExit(f"configuration {config_name!r} names no {key} module: its file must "
                         f"give {key!r}, the bare name of a module under {bench_dir / KEYS[key]}")
    path = bench_dir / KEYS[key] / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"configuration {config_name!r}: no {key} module {path}")
    return path


def load(path: Path):
    """The module in ``path``, under a name of its own (two benchmark
    directories may each hold a ``shapes.py``)."""
    path = Path(path).resolve()
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
