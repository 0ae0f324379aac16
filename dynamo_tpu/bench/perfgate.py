"""Perf regression gate: a dynlint-style ratchet over the committed perf
artifacts.

The repo commits a pile of benchmark artifacts (PROFILE_DECODE.json,
DISAGG_BENCH.json, SCENARIO_SOAK.json, PREFETCH_BENCH.json,
MIGRATION_BENCH.json) but, before this gate, nothing
diffed them across PRs — a perf regression was silent while a lint finding
failed tier-1.  This module is the missing ratchet, modeled exactly on
``scripts/dynlint.py`` + ``ANALYSIS_BASELINE.json``:

- a canonical metric-extraction schema (:data:`METRICS`) names the headline
  number(s) in each artifact, its direction, and its tolerance band;
- ``PERF_BASELINE.json`` commits the accepted values;
- a NEW regression (metric degraded beyond its band vs baseline) FAILS;
- a STALE baseline entry (metric no longer extractable / no longer in the
  schema) FAILS — the baseline must be regenerated, never hand-edited;
- an artifact whose provenance header names a different schema generation
  is refused (its metrics are excluded from both checks) instead of being
  diffed as garbage;
- ``scripts/perfgate.py --write-baseline`` re-records legitimately — and
  refuses to run over a dirty artifact set.

Wired into tier-1 via ``tests/bench/test_perf_gate.py``.  Pure stdlib on
purpose: the gate must run without JAX.
"""

from __future__ import annotations

import json
import os
import subprocess
from dataclasses import dataclass
from pathlib import Path

# Bumped when the meaning of extracted metrics changes incompatibly —
# artifacts stamped with a DIFFERENT generation are refused, not diffed.
PERFGATE_SCHEMA_VERSION = 1

BASELINE_NAME = "PERF_BASELINE.json"

ARTIFACTS = (
    "PROFILE_DECODE.json",
    "DISAGG_BENCH.json",
    "SCENARIO_SOAK.json",
    "PREFETCH_BENCH.json",
    "MIGRATION_BENCH.json",
)


@dataclass(frozen=True)
class MetricSpec:
    """One ratcheted metric: where it lives, which way is better, and how
    much drift the band forgives.

    ``path`` is a dot path into the artifact JSON.  A ``max:`` prefix folds
    a list: ``max:rows[].tflops`` is the max of ``row["tflops"]`` over
    ``rows``.  Booleans extract as 0/1 so "must stay true" is just a
    higher-direction metric with a zero band.
    """

    name: str           # stable metric id (baseline key)
    artifact: str       # which committed file it comes from
    path: str           # extraction path (see above)
    direction: str      # "higher" | "lower" — which way is BETTER
    rel_tol: float      # relative drift forgiven before a regression fires
    abs_slack: float = 0.0  # additive slack (for near-zero baselines)
    doc: str = ""


# Counts and booleans that repeat exactly on a CPU, and nothing timed: the
# artifacts are CPU and mocker runs, so a time, a rate or a speed-up in them
# is no speed of the system (PERF_LEDGER.jsonl is the speed ratchet).
METRICS: tuple[MetricSpec, ...] = (
    # -- decode-loop A/B (scripts/profile_decode.py) -------------------------
    MetricSpec(
        "profile_decode.unified_admission_drains", "PROFILE_DECODE.json",
        "mixed.admission_drains_unified", "lower", 0.0,
        doc="admission-forced pipeline drains under unified batch (stay 0)"),
    # -- disagg streamed KV transfer (scripts/disagg_bench.py) ---------------
    MetricSpec(
        "disagg_bench.preferred_is_near", "DISAGG_BENCH.json",
        "fleet.preferred_is_near", "higher", 0.0,
        doc="topology-aware disagg router prefers the near decode worker"),
    # -- scenario soak (scripts/scenario_soak.py) ----------------------------
    MetricSpec(
        "scenario_soak.passed", "SCENARIO_SOAK.json",
        "passed", "higher", 0.0,
        doc="the committed default soak passed every phase assertion"),
    MetricSpec(
        "scenario_soak.worst_burn_rate", "SCENARIO_SOAK.json",
        "slo.worst_burn_rate", "lower", 0.0, abs_slack=0.5,
        doc="worst SLO burn rate observed across the soak"),
    # -- predictive prefetch (scripts/prefetch_bench.py) ---------------------
    MetricSpec(
        "prefetch_bench.prefetch_hits", "PREFETCH_BENCH.json",
        "prefetch.prefetch_hits_total", "higher", 0.10,
        doc="prefetched blocks consumed before eviction"),
    # -- live migration (scripts/migration_bench.py) -------------------------
    MetricSpec(
        "migration_bench.requests_failed", "MIGRATION_BENCH.json",
        "requests.failed", "lower", 0.0,
        doc="failed requests across the migration soak (stay 0)"),
    MetricSpec(
        "migration_bench.byte_identical", "MIGRATION_BENCH.json",
        "byte_identical", "higher", 0.0,
        doc="migrated outputs byte-identical to unmigrated replays"),
    MetricSpec(
        "migration_bench.committed", "MIGRATION_BENCH.json",
        "migrations.committed", "higher", 0.25,
        doc="migrations committed across the soak phases"),
    MetricSpec(
        "migration_bench.defrag_var_drop_ratio", "MIGRATION_BENCH.json",
        "kv_occupancy_variance.kv_occ_var_drop_ratio", "higher", 0.30,
        doc="KV occupancy variance removed by planner defrag"),
)


@dataclass(frozen=True)
class Finding:
    """One gate failure, named like a dynlint finding."""

    kind: str    # "regression" | "stale" | "unbaselined" | "missing-artifact"
                 # | "unreadable-artifact" | "incompatible-artifact"
    metric: str  # metric id, or artifact name for artifact-level findings
    detail: str

    def __str__(self) -> str:
        return f"[{self.kind}] {self.metric}: {self.detail}"


# -- provenance --------------------------------------------------------------


def provenance_stamp() -> dict:
    """The shared provenance header artifact writers embed (under the
    ``provenance`` key) so the gate can refuse to diff incompatible
    artifact generations.  Host class comes from the knob override, else
    the JAX default backend; git describe is passed via env by CI."""
    from dynamo_tpu.utils import knobs

    host_class = knobs.get(knobs.K_PERFGATE_HOST_CLASS)
    if not host_class:
        try:
            import jax

            host_class = jax.default_backend()
        except Exception:  # noqa: BLE001 — the stamp must work without JAX
            host_class = "unknown"
    return {
        "schema_version": PERFGATE_SCHEMA_VERSION,
        "git_describe": knobs.get(knobs.K_PERFGATE_GIT_DESCRIBE) or "",
        "host_class": host_class,
    }


def provenance_finding(artifact: str, data: dict) -> Finding | None:
    """A finding iff the artifact carries a provenance header from a
    DIFFERENT schema generation.  Artifacts without a header predate the
    provenance stamp and are accepted as the current generation."""
    prov = data.get("provenance")
    if not isinstance(prov, dict):
        return None
    version = prov.get("schema_version")
    if version != PERFGATE_SCHEMA_VERSION:
        return Finding(
            "incompatible-artifact", artifact,
            f"provenance schema_version={version!r} but this gate speaks "
            f"{PERFGATE_SCHEMA_VERSION}; regenerate the artifact",
        )
    return None


# -- extraction --------------------------------------------------------------


def _extract_path(data, path: str):
    """Value at a dot path; ``max:`` folds a ``seg[]`` list segment."""
    fold = None
    if path.startswith("max:"):
        fold, path = max, path[4:]
    node = data
    for seg in path.split("."):
        if seg.endswith("[]"):
            if isinstance(node, dict):
                node = node.get(seg[:-2])
            if not isinstance(node, list):
                return None
            continue
        if isinstance(node, list):
            node = [item.get(seg) for item in node
                    if isinstance(item, dict) and item.get(seg) is not None]
        elif isinstance(node, dict):
            node = node.get(seg)
        else:
            return None
        if node is None:
            return None
    if isinstance(node, list):
        if fold is None or not node:
            return None
        return fold(node)
    if fold is not None:
        return None
    return node


def _as_number(value) -> float | None:
    if isinstance(value, bool):
        return 1.0 if value else 0.0
    if isinstance(value, (int, float)):
        return float(value)
    return None


def extract_metrics(root: str | os.PathLike) -> tuple[dict, list[Finding]]:
    """(metric id → value) over every readable, compatible artifact under
    ``root``, plus artifact-level findings (missing / unreadable /
    incompatible).  Metrics of refused artifacts are absent from the value
    map AND recorded in the second element of the return so callers can
    exclude them from stale checks."""
    root = Path(root)
    values: dict[str, float] = {}
    findings: list[Finding] = []
    refused: set[str] = set()
    loaded: dict[str, dict] = {}
    for artifact in ARTIFACTS:
        path = root / artifact
        if not path.exists():
            findings.append(Finding(
                "missing-artifact", artifact, f"{path} does not exist"))
            refused.add(artifact)
            continue
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError) as exc:
            findings.append(Finding(
                "unreadable-artifact", artifact, f"{path}: {exc}"))
            refused.add(artifact)
            continue
        bad = provenance_finding(artifact, data)
        if bad is not None:
            findings.append(bad)
            refused.add(artifact)
            continue
        loaded[artifact] = data
    for spec in METRICS:
        if spec.artifact in refused:
            continue
        value = _as_number(_extract_path(loaded[spec.artifact], spec.path))
        if value is not None:
            values[spec.name] = value
    return values, findings


def refused_artifacts(findings: list[Finding]) -> set[str]:
    return {
        f.metric for f in findings
        if f.kind in ("missing-artifact", "unreadable-artifact",
                      "incompatible-artifact")
    }


# -- baseline ----------------------------------------------------------------


def baseline_path(root: str | os.PathLike) -> Path:
    from dynamo_tpu.utils import knobs

    explicit = knobs.get(knobs.K_PERFGATE_BASELINE)
    if explicit:
        return Path(explicit)
    return Path(root) / BASELINE_NAME


def load_baseline(path: str | os.PathLike) -> dict:
    data = json.loads(Path(path).read_text())
    if not isinstance(data.get("metrics"), dict):
        raise ValueError(f"{path}: no 'metrics' map (not a perf baseline?)")
    return data


def write_baseline(root: str | os.PathLike,
                   path: str | os.PathLike | None = None,
                   note: str | None = None) -> Path:
    """Re-record the baseline from the current artifact pile.  Refuses when
    any artifact is missing/unreadable/incompatible — a baseline must only
    ever be written over a clean, current pile."""
    values, findings = extract_metrics(root)
    if findings:
        raise ValueError(
            "refusing to write a baseline over a broken artifact pile:\n"
            + "\n".join(str(f) for f in findings)
        )
    out = Path(path) if path is not None else baseline_path(root)
    payload = {
        "version": 1,
        "schema_version": PERFGATE_SCHEMA_VERSION,
        "note": note or (
            "Perf-gate ratchet over the committed benchmark artifacts. "
            "Regenerate with scripts/perfgate.py --write-baseline after a "
            "LEGITIMATE perf change (see docs/autopilot.md) — never "
            "hand-edit."
        ),
        "metrics": {name: values[name] for name in sorted(values)},
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    return out


def dirty_artifacts(root: str | os.PathLike) -> list[str]:
    """Artifact files with uncommitted modifications per git — the
    --write-baseline refusal: a baseline recorded over a dirty pile would
    launder unreviewed numbers into the ratchet."""
    try:
        proc = subprocess.run(
            ["git", "status", "--porcelain", "--", *ARTIFACTS, BASELINE_NAME],
            cwd=str(root), capture_output=True, text=True, timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired):
        return []
    if proc.returncode != 0:
        return []  # not a git checkout: nothing to refuse on
    dirty = []
    for line in proc.stdout.splitlines():
        name = line[3:].strip()
        if name and name != BASELINE_NAME:
            dirty.append(name)
    return sorted(set(dirty))


# -- the gate ----------------------------------------------------------------


def _band_ok(spec: MetricSpec, value: float, base: float) -> bool:
    if spec.direction == "higher":
        floor = base * (1.0 - spec.rel_tol) - spec.abs_slack
        return value >= floor
    ceiling = base * (1.0 + spec.rel_tol) + spec.abs_slack
    return value <= ceiling


def check(root: str | os.PathLike,
          baseline: dict | None = None) -> list[Finding]:
    """All gate findings for the artifact pile under ``root`` (repo root in
    tier-1).  Empty list = gate passes."""
    root = Path(root)
    if baseline is None:
        baseline = load_baseline(baseline_path(root))
    values, findings = extract_metrics(root)
    refused = refused_artifacts(findings)
    specs = {spec.name: spec for spec in METRICS}
    base_metrics = baseline.get("metrics", {})
    for name, base in sorted(base_metrics.items()):
        spec = specs.get(name)
        if spec is None:
            findings.append(Finding(
                "stale", name,
                "baseline entry is not in the metric schema anymore; "
                "regenerate with scripts/perfgate.py --write-baseline"))
            continue
        if spec.artifact in refused:
            continue  # already failed artifact-level; don't double-report
        value = values.get(name)
        if value is None:
            findings.append(Finding(
                "stale", name,
                f"baseline entry no longer extractable from {spec.artifact} "
                f"(path {spec.path!r}); regenerate the baseline"))
            continue
        base_num = _as_number(base)
        if base_num is None:
            findings.append(Finding(
                "stale", name, f"baseline value {base!r} is not numeric"))
            continue
        if not _band_ok(spec, value, base_num):
            findings.append(Finding(
                "regression", name,
                f"{spec.artifact}:{spec.path} = {value:g}, baseline "
                f"{base_num:g}, direction={spec.direction} "
                f"rel_tol={spec.rel_tol:g} abs_slack={spec.abs_slack:g} "
                f"({spec.doc})"))
    for name in sorted(values):
        if name not in base_metrics and specs[name].artifact not in refused:
            findings.append(Finding(
                "unbaselined", name,
                "metric extracted but absent from the baseline; record it "
                "with scripts/perfgate.py --write-baseline"))
    return findings
