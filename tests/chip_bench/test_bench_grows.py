"""What ``BENCHMARK.json`` must keep as later PRs append to it: the lists that
PR 30 left are still there in their order and place, PR 24's ten metrics are
the block they were, every metric that lists its cells lists cells that
exist and has a reader, and the manifest only GREW, at the ends of its
lists, from the one recorded copy (``data/manifest.recorded.json``: PR 51
left it, after giving the dense arithmetic's three metrics their lists of
cells; ``bench_helpers.grown_from`` is the comparison, shown here to fail
on each way a manifest can change otherwise)."""

import copy
import hashlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from test_bench_spans import (COUNTER_METRICS, NEW_METRICS,  # noqa: E402
                              PR24_AT, SCOPE_METRICS, SPAN_METRICS)

# The entries of each list as PR 30 left them, in order.
ACCEPTED = {
    "configs": ["qwen2.5-3b", "mistral-7b-d16"],
    "workloads": ["qwen2.5-3b.chat-steady", "mistral-7b-d16.agent-prefix",
                  "qwen2.5-3b.chat-saturated"],
    "end_to_end": ["req_p50_ms", "tpot_p50_ms", "setup_s"],
}
# PR 51: the cells whose ``config.json`` ``lib/shapes.py`` describes, and
# those in which every layer calls the paged decode kernel once a step.
DENSE_CELLS = ACCEPTED["workloads"]
KERNEL_A_LAYER_CELLS = DENSE_CELLS + [
    "kanana-2-30b-a3b-d8.chat-saturated",
    "xing4.0-29b-a4b-d7.chat-saturated",
    "trinity-mini-d8.longdoc-saturated"]
LISTED_BY_PR51 = {"decode_step_ms": KERNEL_A_LAYER_CELLS,
                  "decode_roofline_pct": DENSE_CELLS,
                  "prefill_mfu_pct": DENSE_CELLS}
# ``json.dumps`` of PR 51's parent's manifest (766f6ef), hashed.
PARENT_SHA256 = ("4b5920fa51054da5dd1e9ff00e4f1fc090b01d421091770f7f91379816"
                 "e09785")


def test_the_accepted_lists_are_still_there_in_their_order():
    doc = live()
    assert validate(doc, REPO) == []
    for key, was in ACCEPTED.items():
        assert [e["name"] for e in doc[key]][:len(was)] == was


def test_pr24_block_is_where_it_was_and_lists_every_cell():
    doc = live()
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
    doc = live()
    cells = [w["name"] for w in doc["workloads"]]
    manifest = Manifest(REPO)
    for metric in doc["per_layer"]:
        listed = metric.get("workloads", cells)
        assert listed and set(listed) <= set(cells), metric["name"]
        # in the cells' own order, so that a list only ever grows at its end
        assert listed == [c for c in cells if c in listed], metric["name"]
        fn, args = manifest.reader(metric["name"])
        assert callable(fn) and isinstance(args, dict)


# ------------------------------------------- PR 51: the three have their lists
@pytest.mark.parametrize("at,name", enumerate(LISTED_BY_PR51, start=14))
def test_the_dense_arithmetics_three_list_their_cells_letter_for_letter(
        at, name):
    """Where they stood (indices 14-16), with what else they said: exactly
    these cells in the record; a later PR may have named more behind."""
    assert recorded()["per_layer"][at]["workloads"] == LISTED_BY_PR51[name]
    entry = live()["per_layer"][at]
    assert entry["name"] == name
    assert entry["workloads"][:len(LISTED_BY_PR51[name])] == \
        LISTED_BY_PR51[name]
    assert list(entry) == ["name", "unit", "better", "source", "layer",
                           "moves", "workloads"]


def test_the_share_by_an_operations_name_lists_no_cells_and_says_why():
    for doc in (recorded(), live()):
        assert doc["per_layer"][17] == {
            "name": "attn_share_pct", "unit": "%", "better": "lower",
            "source": "device_trace",
            "layer": "model and attention kernels", "moves": "tpot_p50_ms"}
    spec = json.load(open(os.path.join(
        REPO, "benchmarks", "chip", "metrics", "attn_share_pct.json")))
    for words in ("Lists no cells", "no model's arithmetic",
                  "cannot pass 100", "reads 0"):
        assert words in spec["what"]


def test_the_record_is_its_parent_but_for_the_three_lists():
    """Every other entry as the parent had it, in its place: the record
    with the three lists taken out again is the parent's manifest."""
    was = recorded()
    for metric in was["per_layer"]:
        if metric["name"] in LISTED_BY_PR51:
            del metric["workloads"]
    assert hashlib.sha256(json.dumps(was).encode()).hexdigest() == \
        PARENT_SHA256


# --------------------------------- the manifest only grew, and what that is
def test_the_manifest_only_grew_from_the_record():
    assert grown_from(live(), recorded()) == []


def _append_a_cell(doc):
    doc["configs"].append({**doc["configs"][0], "name": "tenth"})
    doc["workloads"].append({**doc["workloads"][0], "name": "tenth.mix",
                             "config": "tenth"})
    for metric in doc["per_layer"]:
        if metric["name"] in ("fetch_lag_ms", "decode_step_ms"):
            metric["workloads"].append("tenth.mix")
    doc["per_layer"].append({**doc["per_layer"][-1], "name": "tenth_pct",
                             "workloads": ["tenth.mix"]})


def test_appending_a_cell_its_name_and_a_metric_is_growing():
    doc = recorded()
    _append_a_cell(doc)
    assert grown_from(doc, recorded()) == []


def _delist(doc):
    doc["per_layer"][14]["workloads"].remove(
        "trinity-mini-d8.longdoc-saturated")


def _list_the_list_less(doc):
    doc["per_layer"][17]["workloads"] = list(DENSE_CELLS)


NOT_GROWING = {
    "a_cell_delisted": _delist,
    "a_cell_named_in_front": lambda d: d["per_layer"][15][
        "workloads"].insert(0, "trinity-mini-d8.longdoc-saturated"),
    "a_list_given_to_a_list_less_entry": _list_the_list_less,
    "a_list_taken_from_an_entry": lambda d: d["per_layer"][15].pop(
        "workloads"),
    "an_entry_in_front_of_the_last": lambda d: d["per_layer"].insert(
        -1, {**d["per_layer"][0], "name": "squeezed_in"}),
    "an_entry_gone": lambda d: d["per_layer"].pop(),
    "an_arrow_turned": lambda d: d["per_layer"][15].update(
        moves="req_p50_ms"),
    "a_bound_changed": lambda d: d["end_to_end"][0].update(bound=0.05),
    "an_end_to_end_metric_more": lambda d: d["end_to_end"].append(
        {**d["end_to_end"][0], "name": "another_ms"}),
    "a_cells_why_reworded": lambda d: d["workloads"][3].update(why="x"),
    "a_configuration_in_front": lambda d: d["configs"].insert(
        0, {**d["configs"][0], "name": "first"}),
    "a_longer_run": lambda d: d.update(run_seconds=40),
    "a_key_more": lambda d: d.update(notes=[]),
}


@pytest.mark.parametrize("case", sorted(NOT_GROWING))
def test_what_is_not_growing_is_named(case):
    doc = copy.deepcopy(recorded())
    NOT_GROWING[case](doc)
    assert grown_from(doc, recorded()), case
