"""BENCHMARK.json against the parts of the builder's contract a file can
show, and the validator against manifests that break them."""

import copy
import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402


@pytest.fixture(scope="module")
def doc():
    return Manifest(REPO).doc


def test_committed_manifest_has_no_faults(doc):
    assert validate(doc, REPO) == []


def test_top_level_keys_are_exactly_the_contracts(doc):
    assert sorted(doc) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert len(json.dumps(doc)) < 64 * 1024


def _break(doc, how):
    bad = copy.deepcopy(doc)
    how(bad)
    return validate(bad, REPO)


def _four_chip_majority(d):
    for w in d["workloads"]:
        w["chips"] = 4
    d["workloads"].append({**d["workloads"][0], "name": "x.y",
                           "traffic": "chat-saturated"})


BREAKS = {
    "name_with_space": lambda d: d["per_layer"][0].update(name="a b"),
    "unit_too_long": lambda d: d["per_layer"][0].update(
        unit="tokens_per_second_x"),
    "unit_with_space": lambda d: d["end_to_end"][0].update(unit="m s"),
    "better_sideways": lambda d: d["per_layer"][0].update(better="same"),
    "source_unknown": lambda d: d["per_layer"][0].update(source="guess"),
    "e2e_from_program": lambda d: d["end_to_end"][0].update(
        source="program_counter"),
    "bound_over_limit": lambda d: d["end_to_end"][0].update(bound=0.2),
    "no_setup_s": lambda d: d["end_to_end"].pop(),
    "moves_unknown": lambda d: d["per_layer"][0].update(moves="nothing"),
    "moves_not_reported_everywhere": lambda d: (
        d["end_to_end"][0].update(workloads=[]),),
    "config_without_cell": lambda d: d["configs"].append(
        {**d["configs"][0], "name": "orphan", "file": "benchmarks/chip/x"}),
    "cell_of_unknown_config": lambda d: d["workloads"][0].update(
        config="nope"),
    "cell_without_traffic_file": lambda d: d["workloads"][0].update(
        traffic="nope"),
    "three_chips": lambda d: d["workloads"][0].update(chips=3),
    "four_chip_cells_over_a_quarter": _four_chip_majority,
    "duplicate_metric": lambda d: d["per_layer"].append(d["per_layer"][0]),
    "run_seconds_too_long": lambda d: d.update(run_seconds=52),
    "file_outside_paths": lambda d: d["configs"][0].update(
        file="production_stack_tpu/x.json"),
    "metric_without_reader_file": lambda d: d["per_layer"][0].update(
        name="no_such_metric"),
}


@pytest.mark.parametrize("case", sorted(BREAKS))
def test_validator_refuses(doc, case):
    assert _break(doc, BREAKS[case]), case


def test_every_metric_file_names_an_importable_reader(doc):
    manifest = Manifest(REPO)
    for metric in doc["end_to_end"] + doc["per_layer"]:
        read, args = manifest.reader(metric["name"])
        assert callable(read) and isinstance(args, dict)


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        Manifest(REPO).peaks("TPU v9 imaginary")
    assert Manifest(REPO).peaks("TPU v5 lite")["hbm_gbps"] == 819.0
