"""A configuration, a traffic mix, a cell and a per-layer metric are each
added by NEW files and ONE new entry: done here in a temporary copy, whose
new cell (a closed loop, traced) is then rehearsed."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import CONTRACT_KEYS, copy_benchmark, run_cell  # noqa: E402

NEW_CELL = "extra-config.extra-closed"


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("extended")))
    chip = os.path.join(root, "benchmarks", "chip")
    before = {
        os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(chip) for f in fs}
    # a configuration: its directory
    shutil.copytree(os.path.join(chip, "configs", "qwen2.5-3b"),
                    os.path.join(chip, "configs", "extra-config"))
    # a traffic mix: one data file
    mix = json.load(open(os.path.join(chip, "traffic",
                                      "chat-saturated.json")))
    mix.update(name="extra-closed", users=2, rounds_max=30)
    json.dump(mix, open(os.path.join(chip, "traffic", "extra-closed.json"),
                        "w"))
    # a per-layer metric: its file, and a reader module of its own
    json.dump({"name": "answered_count", "reader": "answered",
               "args": {"scale": 1.0}, "what": "requests answered"},
              open(os.path.join(chip, "metrics", "answered_count.json"), "w"))
    with open(os.path.join(chip, "readers", "answered.py"), "w") as f:
        f.write("def read(ctx, scale):\n"
                "    return scale * sum(1 for r in ctx['results'] if r.ok)\n")
    # and one entry each
    path = os.path.join(root, "BENCHMARK.json")
    doc = json.load(open(path))
    doc["configs"].append({
        **doc["configs"][0], "name": "extra-config",
        "file": "benchmarks/chip/configs/extra-config/config.json"})
    doc["workloads"].append({
        "name": NEW_CELL, "config": "extra-config",
        "traffic": "extra-closed", "chips": 1, "why": "added by a test"})
    doc["per_layer"].append({
        "name": "answered_count", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "client/bench",
        "moves": "req_p50_ms", "workloads": [NEW_CELL]})
    json.dump(doc, open(path, "w"))
    code, line, err = run_cell(root, NEW_CELL, "--rehearse", trace=1)
    assert code == 0 and line is not None, err
    untouched = all(os.path.getmtime(p) == t for p, t in before.items())
    return root, doc, line, untouched


def test_nothing_that_was_there_was_edited(extended):
    assert extended[3]


def test_the_extended_manifest_is_valid(extended):
    root, doc, _, _ = extended
    sys.path.insert(0, root)
    from benchmarks.chip.lib.manifest import validate

    assert validate(doc, root) == []


def test_the_new_cell_runs_and_reports_the_new_metric(extended):
    _, doc, line, _ = extended
    assert CONTRACT_KEYS <= set(line) and line["failed"] == 0
    assert line["metrics"]["answered_count"] == {
        "value": float(line["attempted"]), "unit": "count"}
    assert line["attempted"] >= 2


def test_a_traced_run_reports_per_layer_metrics_only(extended):
    _, doc, line, _ = extended
    per_layer = {m["name"] for m in doc["per_layer"]
                 if "workloads" not in m or NEW_CELL in m["workloads"]}
    end_to_end = {m["name"] for m in doc["end_to_end"]}
    assert set(line["metrics"]) <= per_layer
    assert not set(line["metrics"]) & end_to_end
    assert {"queue_wait_mean_ms", "tok_per_decode_dispatch",
            "prefix_hit_pct", "gen_late_p99_ms"} <= set(line["metrics"])
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
