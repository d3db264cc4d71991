"""Perf-trajectory sentinel (tools/perfwatch.py, docs/OBSERVABILITY.md
"Fleet performance"): every checked-in round artifact must ingest into a
schema-valid PERF_TRAJECTORY.json, the docs/PERF.md trend table must stay
fresh, and the --check budget gate must fail a doctored regression while
passing the honest line it was doctored from."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from tools import perfwatch  # noqa: E402

# BENCH_r01/r02 were deleted (their logged tails named a device transport
# that no longer exists; records are not edited): the on-chip wrappers start
# at r03.
ARTIFACTS = (
    [f"BENCH_r{i:02d}.json" for i in range(3, 11)]
    + [f"BENCH_soak_r{i:02d}.json" for i in range(1, 5)]
    + [f"MULTICHIP_r{i:02d}.json" for i in range(1, 7)]
)


def _line(**overrides):
    base = {
        "bench_schema_version": 2, "metric": "output_tok_s",
        "value": 100.0, "unit": "tok/s", "p50_ttft_s": 0.5,
        "kv_hit_rate": 0.7, "effective_tokens_per_target_step": 1.0,
        "errors_total": 0, "backend": "cpu",
    }
    base.update(overrides)
    return base


# ------------------------------------------------------------- ingestion
def test_all_checked_in_artifacts_discovered():
    found = {os.path.basename(p)
             for p in perfwatch.discover_artifacts(REPO)}
    assert set(ARTIFACTS) <= found


@pytest.mark.parametrize("name", ARTIFACTS)
def test_every_artifact_ingests_schema_valid(name):
    entries = perfwatch.load_artifact(os.path.join(REPO, name))
    assert entries, f"{name} produced no trajectory entries"
    doc = {"schema": perfwatch.SCHEMA, "entries": entries}
    assert perfwatch.validate_trajectory(doc) == []
    for e in entries:
        assert e["source"] == name


def test_trajectory_covers_all_families_and_known_values():
    doc = perfwatch.build_trajectory(REPO)
    assert perfwatch.validate_trajectory(doc) == []
    entries = doc["entries"]
    assert {e["family"] for e in entries} == {"bench", "soak", "multichip"}
    assert len({e["source"] for e in entries}) >= len(ARTIFACTS)
    by = {(e["source"], e["variant"]): e for e in entries}
    # Round-era spot checks: the wrapper shape, the disagg sibling line,
    # the r10 mode grid, a soak class, and the multichip curve.
    assert by[("BENCH_r03.json", "stack")]["metrics"]["output_tok_s"] \
        == pytest.approx(1239.99)
    assert ("BENCH_r06.json", "disagg") in by
    assert by[("BENCH_r10.json", "tree:acceptance_limited")]["metrics"][
        "effective_tokens_per_target_step"] == pytest.approx(1.494)
    assert by[("BENCH_soak_r04.json", "totals")]["metrics"][
        "status_5xx"] == 0
    assert by[("MULTICHIP_r06.json", "8chip")]["metrics"][
        "output_tok_s"] == pytest.approx(32.59)
    # MULTICHIP r01-r05 are metric-less smoke runs: ingested as passing
    # smoke entries, never dropped.
    assert by[("MULTICHIP_r01.json", "smoke")]["metrics"][
        "errors_total"] == 0


def test_unrecognized_and_unreadable_artifacts_degrade(tmp_path):
    weird = tmp_path / "BENCH_r99.json"
    weird.write_text('{"surprising": true}')
    entries = perfwatch.load_artifact(str(weird))
    assert entries[0]["variant"] == "smoke"
    broken = tmp_path / "BENCH_r98.json"
    broken.write_text("{not json")
    entries = perfwatch.load_artifact(str(broken))
    assert entries[0]["variant"] == "unreadable"
    assert entries[0]["metrics"]["errors_total"] == 1


# ------------------------------------------------------------ schema gate
def test_schema_gate_rejects_drift():
    assert perfwatch.validate_trajectory([]) != []
    assert perfwatch.validate_trajectory({"schema": "bogus",
                                          "entries": []}) != []
    bad_family = {"schema": perfwatch.SCHEMA, "entries": [
        {"source": "x", "family": "vibes", "variant": "v", "backend": "",
         "metrics": {}}]}
    assert any("family" in p
               for p in perfwatch.validate_trajectory(bad_family))
    bad_metric = {"schema": perfwatch.SCHEMA, "entries": [
        {"source": "x", "family": "bench", "variant": "v", "backend": "",
         "metrics": {"output_tok_s": "fast"}}]}
    assert any("not a number" in p
               for p in perfwatch.validate_trajectory(bad_metric))
    unknown_key = {"schema": perfwatch.SCHEMA, "entries": [
        {"source": "x", "family": "bench", "variant": "v", "backend": "",
         "metrics": {"vibes_per_s": 1.0}}]}
    assert any("unknown key" in p
               for p in perfwatch.validate_trajectory(unknown_key))


# ------------------------------------------------------------ budget math
def _doc_with(*lines):
    doc = {"schema": perfwatch.SCHEMA, "entries": []}
    for ln in lines:
        perfwatch.ingest_line(doc, ln)
    return doc


def test_check_passes_honest_line_against_itself():
    doc = _doc_with(_line())
    assert perfwatch.check_line(doc, _line()) == []


def test_check_fails_each_budget_independently():
    doc = _doc_with(_line())
    assert any("tok/s" in p for p in
               perfwatch.check_line(doc, _line(value=50.0)))
    assert any("p50 TTFT" in p for p in
               perfwatch.check_line(doc, _line(p50_ttft_s=2.0)))
    assert any("kv_hit_rate" in p for p in
               perfwatch.check_line(doc, _line(kv_hit_rate=0.2)))
    assert any("target-step" in p for p in
               perfwatch.check_line(
                   doc, _line(effective_tokens_per_target_step=0.4)))
    assert any("zero-5xx" in p for p in
               perfwatch.check_line(doc, _line(errors_total=2)))


def test_check_within_tolerance_passes():
    doc = _doc_with(_line())
    # 25% down on tok/s sits inside the 30% default budget.
    assert perfwatch.check_line(doc, _line(value=75.0)) == []
    # Tighter tolerance turns the same delta into a regression.
    assert perfwatch.check_line(doc, _line(value=75.0),
                                tolerance=0.1) != []


def test_check_no_comparable_baseline_passes_with_warning():
    doc = _doc_with(_line(backend="cpu"))
    assert perfwatch.check_line(doc, _line(backend="tpu-v99")) == []
    # ...but the zero-5xx bar holds even with no baseline.
    assert perfwatch.check_line(
        doc, _line(backend="tpu-v99", errors_total=1)) != []


def test_check_ignores_soak_and_multichip_baselines():
    doc = {"schema": perfwatch.SCHEMA, "entries": [
        perfwatch._entry("s.json", "soak", "interactive", "cpu",
                         {"output_tok_s": 10_000.0}),
        perfwatch._entry("m.json", "multichip", "8chip", "cpu",
                         {"output_tok_s": 10_000.0}),
    ]}
    # Only bench-family entries are comparable; these must not set budgets.
    assert perfwatch.check_line(doc, _line(value=5.0)) == []


# --------------------------------------------------- CLI + regression exit
def _run(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perfwatch.py"),
         *args],
        capture_output=True, text=True, cwd=cwd,
    )


def test_cli_regression_exit_code(tmp_path):
    traj = tmp_path / "T.json"
    honest = tmp_path / "honest.json"
    honest.write_text(json.dumps(_line()))
    doctored = tmp_path / "doctored.json"
    doctored.write_text(json.dumps(_line(value=50.0)))

    r = _run(["--ingest-line", str(honest), "--trajectory", str(traj),
              "--source", "smoke"])
    assert r.returncode == 0, r.stderr
    assert perfwatch.validate_trajectory(
        json.loads(traj.read_text())) == []

    r = _run(["--check", str(honest), "--trajectory", str(traj)])
    assert r.returncode == 0, r.stderr
    r = _run(["--check", str(doctored), "--trajectory", str(traj)])
    assert r.returncode == 1
    assert "REGRESSION" in r.stderr


def test_cli_check_rejects_invalid_trajectory(tmp_path):
    traj = tmp_path / "T.json"
    traj.write_text(json.dumps({"schema": "bogus", "entries": []}))
    line = tmp_path / "l.json"
    line.write_text(json.dumps(_line()))
    r = _run(["--check", str(line), "--trajectory", str(traj)])
    assert r.returncode == 2


# ------------------------------------------------------------ docs freshness
def test_checked_in_trajectory_and_docs_are_fresh():
    """The committed PERF_TRAJECTORY.json and docs/PERF.md trend table must
    match a rebuild from the committed artifacts (the CI --check-docs
    gate, same contract as the gen_docs metrics tables)."""
    r = _run(["--check-docs"])
    assert r.returncode == 0, r.stderr


def test_check_docs_detects_staleness(tmp_path):
    import shutil

    scratch = tmp_path / "repo"
    scratch.mkdir()
    for name in ("BENCH_r03.json", "PERF_TRAJECTORY.json"):
        shutil.copy(os.path.join(REPO, name), scratch / name)
    (scratch / "docs").mkdir()
    shutil.copy(os.path.join(REPO, "docs", "PERF.md"),
                scratch / "docs" / "PERF.md")
    # Fewer artifacts than the committed trajectory ingested -> stale.
    r = _run(["--project-root", str(scratch), "--check-docs"])
    assert r.returncode == 1
    assert "out of date" in r.stderr
