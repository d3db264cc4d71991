"""The command itself, rehearsed end to end on the CPU at the tiny preset
(``--rehearse``): engine and router children, set-up probes and preload,
an open-loop window, the last line."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import CONTRACT_KEYS, REPO, copy_benchmark, run_cell  # noqa: E402

CELL = "mistral-7b-d16.agent-prefix"


@pytest.fixture(scope="module")
def rehearsal():
    code, line, err = run_cell(REPO, CELL, "--rehearse")
    assert code == 0 and line is not None, err
    return line


@pytest.fixture(scope="module")
def manifest():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_last_line_has_the_contracts_keys(rehearsal):
    assert CONTRACT_KEYS <= set(rehearsal)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        rehearsal["device"])


def test_metrics_are_the_cells_end_to_end_metrics(rehearsal, manifest):
    want = {m["name"]: m["unit"] for m in manifest["end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]}
    got = {k: v["unit"] for k, v in rehearsal["metrics"].items()}
    assert got == want
    assert all(isinstance(v["value"], float) and v["value"] > 0
               for v in rehearsal["metrics"].values())


def test_a_rehearsal_never_counts(rehearsal):
    assert rehearsal["correct"] is False
    assert rehearsal["device"]["platform"] == "cpu"


def test_every_request_was_answered_and_counted(rehearsal):
    assert rehearsal["attempted"] == 15 and rehearsal["failed"] == 0
    # On the CPU the only fault is that the kernels run interpreted: usage,
    # finish reasons, token counters, probes and compile count all held.
    assert rehearsal["faults"] == ["Pallas kernels run interpreted"]


def test_no_backlog_is_left(rehearsal):
    assert rehearsal["waiting_end"] <= rehearsal["waiting_mid"] + 1


def test_without_a_chip_it_fails_and_prints_no_result():
    code, line, err = run_cell(REPO, CELL, timeout=120)
    assert code != 0 and line is None
    assert "no CPU fallback" in err


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    root = copy_benchmark(str(tmp_path), with_program=False)
    code, line, _ = run_cell(root, CELL, "--rehearse", timeout=120)
    assert code != 0 and line is None
