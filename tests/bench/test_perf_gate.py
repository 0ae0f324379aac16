"""The perf regression gate under tier-1 (dynamo_tpu/bench/perfgate.py):
the committed artifact pile must pass against PERF_BASELINE.json, a
degraded metric must fail with a NAMED finding, a stale baseline entry
must fail, and --write-baseline must refuse a dirty artifact set — the
dynlint ratchet model, applied to performance."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO_ROOT / "scripts"))

from perfgate import main as perfgate_main  # noqa: E402

from dynamo_tpu.bench import perfgate  # noqa: E402


def _copy_pile(dst: Path) -> None:
    for name in perfgate.ARTIFACTS + (perfgate.BASELINE_NAME,):
        shutil.copy(REPO_ROOT / name, dst / name)


def _edit_json(path: Path, mutate) -> None:
    data = json.loads(path.read_text())
    mutate(data)
    path.write_text(json.dumps(data, indent=2) + "\n")


# -- the tier-1 gate itself ---------------------------------------------------
def test_committed_pile_passes_the_gate():
    """THE gate: the repo's committed artifacts vs the committed baseline.
    A failure here means a PR moved a count or a boolean the artifacts
    record (fix it) or legitimately changed one (rerun scripts/perfgate.py
    --write-baseline and commit the new baseline with the artifacts)."""
    findings = perfgate.check(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_every_schema_metric_is_extractable_and_baselined():
    values, findings = perfgate.extract_metrics(REPO_ROOT)
    assert findings == []
    assert set(values) == {spec.name for spec in perfgate.METRICS}
    baseline = perfgate.load_baseline(perfgate.baseline_path(REPO_ROOT))
    assert set(baseline["metrics"]) == set(values)


# -- regression detection -----------------------------------------------------
def _scale_prefetch_hits(factor: float):
    """prefetch_bench.prefetch_hits: higher is better, 10% band."""
    def mutate(d):
        d["prefetch"]["prefetch_hits_total"] *= factor
    return mutate


def test_degraded_metric_fails_with_named_finding(tmp_path):
    _copy_pile(tmp_path)
    _edit_json(tmp_path / "PREFETCH_BENCH.json", _scale_prefetch_hits(0.5))
    findings = perfgate.check(tmp_path)
    assert len(findings) == 1
    f = findings[0]
    assert f.kind == "regression"
    assert f.metric == "prefetch_bench.prefetch_hits"
    assert "PREFETCH_BENCH.json" in f.detail
    assert "baseline" in f.detail


def test_improvement_and_in_band_drift_pass(tmp_path):
    _copy_pile(tmp_path)

    _edit_json(tmp_path / "PREFETCH_BENCH.json", _scale_prefetch_hits(1.5))  # improvement
    _edit_json(  # within migration_bench.committed's 25% band
        tmp_path / "MIGRATION_BENCH.json",
        lambda d: d["migrations"].update(committed=d["migrations"]["committed"] * 0.95),
    )
    assert perfgate.check(tmp_path) == []


def test_lower_direction_metric_regresses_upward(tmp_path):
    _copy_pile(tmp_path)
    # worst_burn_rate is a lower-is-better metric with abs_slack=0.5
    _edit_json(
        tmp_path / "SCENARIO_SOAK.json",
        lambda d: d["slo"].update(worst_burn_rate=99.0),
    )
    findings = perfgate.check(tmp_path)
    assert [f.metric for f in findings] == ["scenario_soak.worst_burn_rate"]
    assert findings[0].kind == "regression"


# -- stale / unbaselined ------------------------------------------------------
def test_stale_baseline_entry_fails(tmp_path):
    _copy_pile(tmp_path)
    _edit_json(
        tmp_path / perfgate.BASELINE_NAME,
        lambda d: d["metrics"].update({"ghost.metric_gone": 1.0}),
    )
    findings = perfgate.check(tmp_path)
    assert [(f.kind, f.metric) for f in findings] == [("stale", "ghost.metric_gone")]


def test_no_longer_extractable_entry_is_stale(tmp_path):
    _copy_pile(tmp_path)
    _edit_json(
        tmp_path / "PREFETCH_BENCH.json",
        lambda d: d["prefetch"].pop("prefetch_hits_total"),
    )
    findings = perfgate.check(tmp_path)
    assert [(f.kind, f.metric) for f in findings] == [
        ("stale", "prefetch_bench.prefetch_hits")
    ]


def test_unbaselined_metric_fails(tmp_path):
    _copy_pile(tmp_path)
    _edit_json(
        tmp_path / perfgate.BASELINE_NAME,
        lambda d: d["metrics"].pop("prefetch_bench.prefetch_hits"),
    )
    findings = perfgate.check(tmp_path)
    assert [(f.kind, f.metric) for f in findings] == [
        ("unbaselined", "prefetch_bench.prefetch_hits")
    ]


# -- provenance refusal -------------------------------------------------------
def test_incompatible_provenance_is_refused_not_diffed(tmp_path):
    _copy_pile(tmp_path)
    _edit_json(
        tmp_path / "SCENARIO_SOAK.json",
        lambda d: d.update(provenance={"schema_version": 999}),
    )
    findings = perfgate.check(tmp_path)
    # exactly one artifact-level refusal — the refused artifact's metrics
    # must NOT cascade into stale/regression noise
    assert [(f.kind, f.metric) for f in findings] == [
        ("incompatible-artifact", "SCENARIO_SOAK.json")
    ]


def test_missing_artifact_is_a_finding(tmp_path):
    _copy_pile(tmp_path)
    (tmp_path / "PREFETCH_BENCH.json").unlink()
    kinds = {(f.kind, f.metric) for f in perfgate.check(tmp_path)}
    assert ("missing-artifact", "PREFETCH_BENCH.json") in kinds


def test_provenance_stamp_matches_gate_generation():
    stamp = perfgate.provenance_stamp()
    assert stamp["schema_version"] == perfgate.PERFGATE_SCHEMA_VERSION
    assert perfgate.provenance_finding("X.json", {"provenance": stamp}) is None
    assert perfgate.provenance_finding("X.json", {}) is None  # pre-provenance ok


# -- CLI + dirty-pile refusal -------------------------------------------------
def _git(cwd: Path, *args: str) -> None:
    subprocess.run(
        ["git", "-c", "user.name=t", "-c", "user.email=t@t", *args],
        cwd=str(cwd), check=True, capture_output=True,
    )


@pytest.fixture
def committed_pile(tmp_path):
    _git(tmp_path, "init", "-q")
    _copy_pile(tmp_path)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "pile")
    return tmp_path


def test_write_baseline_refuses_dirty_pile(committed_pile, capsys):
    _edit_json(
        committed_pile / "PREFETCH_BENCH.json",
        lambda d: d["prefetch"].update(prefetch_hits_total=4242.0),
    )
    assert perfgate.dirty_artifacts(committed_pile) == ["PREFETCH_BENCH.json"]
    rc = perfgate_main(["--root", str(committed_pile), "--write-baseline"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "refusing --write-baseline" in out
    assert "PREFETCH_BENCH.json" in out


def test_write_baseline_over_clean_pile_then_gate_passes(committed_pile, capsys):
    _edit_json(
        committed_pile / "PREFETCH_BENCH.json",
        lambda d: d["prefetch"].update(prefetch_hits_total=4242.0),
    )
    _git(committed_pile, "add", "-A")
    _git(committed_pile, "commit", "-q", "-m", "legit perf change")
    assert perfgate_main(["--root", str(committed_pile), "--write-baseline"]) == 0
    baseline = perfgate.load_baseline(committed_pile / perfgate.BASELINE_NAME)
    assert baseline["metrics"]["prefetch_bench.prefetch_hits"] == 4242.0
    assert perfgate_main(["--root", str(committed_pile)]) == 0


def test_cli_exit_code_and_findings_output(tmp_path, capsys):
    _copy_pile(tmp_path)
    _edit_json(tmp_path / "PREFETCH_BENCH.json", _scale_prefetch_hits(0.5))
    rc = perfgate_main(["--root", str(tmp_path)])
    assert rc == 1
    assert "[regression] prefetch_bench.prefetch_hits" in capsys.readouterr().out
