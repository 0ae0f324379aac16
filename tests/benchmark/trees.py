"""The two trees every tree-wide test runs on.

``real`` is the repository.  ``next`` is a temporary copy of ``BENCHMARK.json``
and ``benchmark/`` to which the files under ``next_tree/`` have been ADDED and
the entries of ``next_tree/BENCHMARK.append.json`` APPENDED: what the next PR
of the queue brings (a configuration with a cache of its own kind, its cell,
two mixes, four per-layer metrics; a configuration that generates by passes
over a block, its cell, its mix, three per-layer metrics), made the only way
a later PR may make it.
A tree-wide test that fails on ``next`` would refuse that PR.

The lists of cases are worked out while the tests are collected, from the
files alone; the copy is made once a session (``conftest.py``)."""

from __future__ import annotations

import copy
import functools
import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ADDED = Path(__file__).resolve().parent / "next_tree"
TREES = ("real", "next")


@functools.cache
def bench_of(tree: str) -> dict:
    """The tree's BENCHMARK.json (treat as read-only)."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    if tree == "real":
        return real
    more = json.loads((ADDED / "BENCHMARK.append.json").read_text())
    bench = copy.deepcopy(real)
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += more[key]
    for metric in bench["end_to_end"]:
        metric.get("workloads", []).extend(more["end_to_end_workloads"].get(metric["name"], []))
    return bench


def config_of(entry: dict) -> dict:
    """A configuration's file, from wherever it lies now."""
    path = ROOT / entry["file"]
    return json.loads((path if path.exists() else ADDED / entry["file"]).read_text())


def mixes_of(tree: str) -> list[Path]:
    """The mix files of the tree, wherever they lie now."""
    found = sorted((ROOT / "benchmark" / "traffic").glob("*.json"))
    if tree == "next":
        found += sorted((ADDED / "benchmark" / "traffic").glob("*.json"))
    return found


def added_files() -> list[Path]:
    """The files the next PR adds, relative to the root of a tree."""
    return sorted(p.relative_to(ADDED) for p in ADDED.rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts and p.name != "BENCHMARK.append.json")


def each(select, ident=lambda item: item["name"]):
    """One case a tree and an item of ``select(the tree's BENCHMARK.json)``."""
    return [pytest.param(tree, item, id=f"{tree}-{ident(item)}")
            for tree in TREES for item in select(bench_of(tree))]


def grown_only(old, new, where="BENCHMARK.json") -> None:
    """``new`` is ``old`` with lists that grew at their ends, nothing else."""
    if isinstance(old, dict):
        assert isinstance(new, dict) and list(old) == list(new), where
        for key in old:
            grown_only(old[key], new[key], f"{where}.{key}")
    elif isinstance(old, list):
        assert isinstance(new, list) and len(new) >= len(old), where
        for k, item in enumerate(old):
            grown_only(item, new[k], f"{where}[{k}]")
    else:
        assert old == new and type(old) is type(new), where


def make_next(root: Path) -> Path:
    """Writes the ``next`` tree under ``root`` and holds it to the rule of a
    PR that only adds: no file of the real tree differs in the copy but
    BENCHMARK.json, and in it only lists grew."""
    skip = shutil.ignore_patterns("__pycache__", "*.pyc")
    shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=skip)
    for rel in added_files():
        assert not (ROOT / rel).exists(), f"{rel} is already in the tree: a later PR edits no file"
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(ADDED / rel, root / rel)
    (root / "BENCHMARK.json").write_text(json.dumps(bench_of("next"), indent=1) + "\n")
    for src in (ROOT / "benchmark").rglob("*"):
        if src.is_file() and "__pycache__" not in src.parts:
            assert (root / src.relative_to(ROOT)).read_bytes() == src.read_bytes(), src
    grown_only(bench_of("real"), json.loads((root / "BENCHMARK.json").read_text()))
    return root
