"""The reduction from a capture to busy time, per-operation time and named
idle gaps: on hand-made events, and on a small capture recorded on a TPU
v5e (``data/decode_window.xplane.pb``, see ``data/README.txt``)."""

import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chip.lib import roofline, xplane  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_busy_time_is_the_union_not_the_sum():
    events = [("a", 0.0, 1.0), ("b", 0.5, 1.5), ("c", 3.0, 4.0),
              ("inner", 3.2, 3.4)]
    assert xplane.union_seconds(events) == pytest.approx(2.5)
    assert xplane.union_seconds([]) == 0.0


def test_a_parents_time_is_less_its_childrens():
    events = [("while", 0.0, 10.0), ("fusion", 1.0, 4.0),
              ("fusion", 5.0, 7.0), ("copy", 5.5, 6.0), ("solo", 11.0, 12.0)]
    got = xplane.self_times(events)
    assert got == pytest.approx(
        {"while": 5.0, "fusion": 4.5, "copy": 0.5, "solo": 1.0})
    assert sum(got.values()) == pytest.approx(xplane.union_seconds(events))


@pytest.mark.parametrize("name,want", [
    ("jit__decode_impl(123456)", "jit__decode_impl"),
    ("jit__prefill_impl", "jit__prefill_impl")])
def test_program_names_lose_their_run_ids(name, want):
    assert xplane.program_of(name) == want


def test_a_gap_is_named_by_the_host_event_that_covers_most_of_it():
    planes = {"/host:CPU": {"main": [("schedule", 0.0, 0.4),
                                     ("sample", 0.4, 2.0)]},
              "/device:TPU:0": {}}
    assert xplane.host_activity(planes, 0.3, 1.0) == "sample"
    assert xplane.host_activity(planes, 5.0, 6.0) == ""


# ---------------------------------------------------- the recorded capture
@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "decode_window.xplane.pb")
    want = json.load(open(os.path.join(DATA, "decode_window.expected.json")))
    return xplane.reduce(path), want


def test_recorded_capture_busy_and_idle(recorded):
    got, want = recorded
    assert got["devices"] == want["devices"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0 < got["busy_s"] < got["window_s"]


def test_recorded_capture_per_operation_sums(recorded):
    got, want = recorded
    # Device operations overlap a little on the chip (not only nest), so
    # the self times fall short of the union, never exceed it.
    assert 0.9 * got["busy_s"] <= sum(got["ops"].values()) <= got["busy_s"]
    for name, seconds in want["top_ops"]:
        assert got["ops"][name] == pytest.approx(seconds, rel=1e-6)
    assert [n for n, _ in got["breakdown"]["device_ops"]] == [
        n for n, _ in want["top_ops"]]
    assert got["programs"].keys() >= set(want["programs"])
    assert len(got["breakdown"]["device_ops"]) <= 10
    assert len(got["breakdown"]["idle_gaps"]) <= 10


def test_recorded_capture_gaps_are_named_and_add_up(recorded):
    got, want = recorded
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert all(">" in name or name == "shorter_gaps" for name in gaps)
    assert want["longest_gap"] in gaps
    programs_s = sum(got["programs"].values())
    assert sum(gaps.values()) <= got["window_s"] - programs_s + 1e-6


def test_recorded_capture_yields_the_kernel_layer_numbers(recorded):
    _, want = recorded
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "chip", "configs", want["config"],
        "config.json")))
    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}

    class R:    # an answered request, as the client records it
        ok = True

        class request:
            prompt_tokens, output_tokens = want["prompt_tokens"], 128

    out = roofline.reduce(
        {"dirs": [DATA], "seconds": want["window_s"],
         "counters": want["counters"]}, cfg, peak, [R()], want["counters"])
    assert out["idle_share"] == pytest.approx(
        1 - want["busy_s"] / want["window_s"], rel=1e-6)
    assert 0 < out["decode_roofline"] <= 1.0
    assert 0 < out["attn_share"] < 1.0
    assert out["decode_step_s"] == pytest.approx(want["decode_step_s"],
                                                 rel=1e-3)
