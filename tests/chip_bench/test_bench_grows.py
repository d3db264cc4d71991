"""What ``BENCHMARK.json`` must keep as later PRs append to it: the lists that
PR 30 left are still there in their order and place, PR 24's ten metrics are
the block they were (all that
``test_bench_spans.py::test_extended_manifest_is_valid_and_only_grew``
asserts except that the block is LAST, which no appending PR can keep: see
``conftest.py``), and every metric that lists its cells lists cells that
exist and has a reader."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from test_bench_spans import (COUNTER_METRICS, NEW_METRICS,  # noqa: E402
                              SCOPE_METRICS, SPAN_METRICS)

# The entries of each list as PR 30 left them, in order.
ACCEPTED = {
    "configs": ["qwen2.5-3b", "mistral-7b-d16"],
    "workloads": ["qwen2.5-3b.chat-steady", "mistral-7b-d16.agent-prefix",
                  "qwen2.5-3b.chat-saturated"],
    "end_to_end": ["req_p50_ms", "tpot_p50_ms", "setup_s"],
}
# Where PR 24's block of ten starts in ``per_layer`` (PR 30's list had 30).
PR24_AT = 20


def test_the_accepted_lists_are_still_there_in_their_order():
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert validate(doc, REPO) == []
    for key, was in ACCEPTED.items():
        assert [e["name"] for e in doc[key]][:len(was)] == was


def test_pr24_block_is_where_it_was_and_lists_every_cell():
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    names = [m["name"] for m in doc["per_layer"]]
    assert names[PR24_AT:PR24_AT + len(NEW_METRICS)] == list(NEW_METRICS)
    cells = [w["name"] for w in doc["workloads"]]
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in SPAN_METRICS + SCOPE_METRICS:
        assert by_name[name]["workloads"] == cells
    for name in COUNTER_METRICS:
        assert "workloads" not in by_name[name]
    assert {by_name[n]["source"] for n in SPAN_METRICS} == {"program_span"}
    # What follows the block was appended by a later PR, which lists the
    # cells where its readers find something to read.
    for metric in doc["per_layer"][PR24_AT + len(NEW_METRICS):]:
        assert metric.get("workloads"), metric["name"]


def test_every_metric_lists_cells_that_exist_and_has_a_reader():
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cells = [w["name"] for w in doc["workloads"]]
    manifest = Manifest(REPO)
    for metric in doc["per_layer"]:
        listed = metric.get("workloads", cells)
        assert listed and set(listed) <= set(cells), metric["name"]
        # in the cells' own order, so that a list only ever grows at its end
        assert listed == [c for c in cells if c in listed], metric["name"]
        fn, args = manifest.reader(metric["name"])
        assert callable(fn) and isinstance(args, dict)
