"""The per-layer metrics that read what a dispatch says at issue (PR 36):
five from counters the engine loop keeps beside
``pstpu:prefill_dispatches_total`` and at apply, one from the capture
(``readers/prefill_tokens.py``: the device time of the prefill runs over
the tokens their ``pstpu.issue`` spans carry). The readers on hand-built
contexts and synthetic captures, nothing on the capture recorded before
the spans carried tokens, the manifest's six new entries, and a traced
rehearsal that reports the counter-based five."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import (REPO, grown_from, live, recorded,  # noqa: E402
                           run_cell)

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import spans, xplane  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import prefill_tokens  # noqa: E402

RECORDED = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
COUNTER_METRICS = ("prefill_fill_pct", "prefill_tok_per_dispatch",
                   "admit_left_waiting_mean", "serving_compile_s",
                   "decode_empty_step_pct")
SPAN_METRIC = "prefill_dev_us_per_token"
ISSUE_METRICS = COUNTER_METRICS[:3] + (SPAN_METRIC,) + COUNTER_METRICS[3:]
# Where the six start in ``per_layer`` (PR 35's list had 40).
PR36_AT = 40


def read(name, ctx):
    fn, args = Manifest(REPO).reader(name)
    return fn(ctx, **args)


# ------------------------------------------------- the counters, by hand
WINDOW_COUNTERS = {
    "pstpu:prefill_dispatches_total": 50.0,
    "pstpu:prefill_tokens_issued_total": 60000.0,
    "pstpu:prefill_tokens_padded_total": 102400.0,      # 50 x [8, 256]
    "pstpu:prefill_rows_issued_total": 380.0,
    "pstpu:prefill_left_waiting_total": 125.0,
    "pstpu:prefill_stop_rows_total": 45.0,
    "pstpu:serving_compile_seconds_total": 0.0,
    "pstpu:serving_compiles_total": 0.0,
    "pstpu:decode_steps_total": 4000.0,
    "pstpu:decode_steps_empty_total": 90.0,
}


@pytest.mark.parametrize("name,want", [
    ("prefill_fill_pct", 100.0 * 60000 / 102400),
    ("prefill_tok_per_dispatch", 1200.0),
    ("admit_left_waiting_mean", 2.5),
    ("serving_compile_s", 0.0),          # a warm engine: 0, not nothing
    ("decode_empty_step_pct", 2.25),
])
def test_counter_metric_from_a_windows_deltas(name, want):
    ctx = {"counters": dict(WINDOW_COUNTERS), "span_s": 50.0}
    assert read(name, ctx) == pytest.approx(want)


def test_a_stall_shows_as_its_seconds():
    ctx = {"counters": {**WINDOW_COUNTERS,
                        "pstpu:serving_compile_seconds_total": 15.25}}
    assert read("serving_compile_s", ctx) == pytest.approx(15.25)


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_counter_metric_is_nothing_where_the_program_lacks_the_series(name):
    """The parent commit exports none of the new series: the reader gives
    nothing and does not raise, and the line leaves the metric out."""
    parent = {"pstpu:prefill_dispatches_total": 50.0,
              "pstpu:decode_steps_total": 4000.0}
    assert read(name, {"counters": parent, "span_s": 50.0}) is None
    assert read(name, {"counters": {}, "span_s": 50.0}) is None


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_a_window_with_no_dispatch_divides_by_nothing(name):
    quiet = dict.fromkeys(WINDOW_COUNTERS, 0.0)
    got = read(name, {"counters": quiet, "span_s": 50.0})
    assert got is None or got == 0.0     # the delta reads 0, a ratio nothing


# ------------------------------------------- spans <-> prefill runs, by hand
def _issue(step, start, tokens, rows=8, t=256, kind="prefill", **attrs):
    span = dict(name="pstpu.issue", start=start, end=start + 0.002,
                step=step, kind=kind, rows=rows, k=t, **attrs)
    if tokens is not None:
        span.update(tokens=tokens, prog_rows=rows if rows == 1 else 8,
                    prog_t=t, left=3, stop="rows")
    return span


def _events(issues, runs, decode_runs=()):
    return {"spans": sorted(issues, key=lambda s: s["start"]),
            "programs": {"jit__prefill_impl": list(runs),
                         "jit__decode_impl": list(decode_runs)}}


def test_each_dispatch_takes_the_first_run_after_its_issue():
    """Three prefills, each queued behind a decode train; the middle one
    ends no prompt (its fetch touches no device, ``lib/spans.py:pair``
    leaves it out): all three pair, in order."""
    issues = [_issue(10, 0.000, 1500), _issue(12, 0.900, 2048),
              _issue(14, 1.800, 900, rows=1, t=1024),
              _issue(11, 0.100, None, kind="decode"),
              _issue(13, 1.000, None, kind="decode")]
    runs = [(0.300, 0.360), (1.200, 1.270), (2.100, 2.140)]
    got = prefill_tokens.reduce(_events(issues, runs))
    assert (got["issues"], got["paired"], got["runs"], got["cut"]) == \
        (3, 3, 3, 0)
    assert got["tokens"] == 1500 + 2048 + 900
    assert got["device_s"] == pytest.approx(0.060 + 0.070 + 0.040)
    assert got["by_program"] == {
        (8, 256): [2, pytest.approx(0.130), 3548],
        (1, 1024): [1, pytest.approx(0.040), 900]}
    pairs = prefill_tokens.pair(
        prefill_tokens.prefill_issues(issues), runs)
    assert [(int(s["step"]), r) for s, r in pairs] == [
        (10, runs[0]), (12, runs[1]), (14, runs[2])]


def test_a_dispatch_cut_by_the_captures_edge_is_left_out_on_both_sides():
    """The capture opens inside a prefill run whose issue span predates it
    (a run with no span) and closes after an issue whose run is not in it
    (a span with no run): neither the orphan run's seconds nor the orphan
    span's tokens enter the ratio."""
    issues = [_issue(21, 0.500, 1000), _issue(23, 1.400, 1200),
              _issue(99, 3.950, 7777)]
    runs = [(0.010, 0.080), (0.800, 0.850), (1.700, 1.760)]
    got = prefill_tokens.reduce(_events(issues, runs))
    assert (got["issues"], got["paired"], got["runs"], got["cut"]) == \
        (3, 2, 3, 1)
    assert got["tokens"] == 1000 + 1200                  # not the 7777
    assert got["device_s"] == pytest.approx(0.050 + 0.060)   # not the 0.070
    assert prefill_tokens.notes(got)[0] == (
        "prefill_tokens: paired 2 of 3 prefill dispatches (1 cut by the "
        "capture's end), 3 runs in the capture")


def test_more_spans_without_a_run_than_the_pipeline_holds_read_as_nothing():
    """The engine loop keeps two dispatches in flight: the capture's end
    cuts no more than that. Ten issue spans over five runs is a capture
    that lost runs, and a ratio over it would rest on wrong pairs."""
    issues = [_issue(2 * i, 0.4 * i, 800) for i in range(10)]
    runs = [(0.4 * i + 0.1, 0.4 * i + 0.15) for i in range(5)]
    got = prefill_tokens.reduce(_events(issues, runs))
    assert (got["issues"], got["paired"], got["cut"]) == (10, 5, 2)
    assert "tokens" not in got
    assert prefill_tokens.notes(got) == [
        "prefill_tokens: paired 5 of 10 prefill dispatches (2 cut by the "
        "capture's end), 5 runs in the capture, under 90% of the rest: no "
        "metric"]
    ctx = {"_prefill_tokens": got}
    assert read(SPAN_METRIC, ctx) is None


def test_a_run_may_start_a_clock_tolerance_before_its_issue_began():
    issues = [_issue(3, 0.1000, 640)]
    got = prefill_tokens.reduce(_events(issues, [(0.0995, 0.130)]))
    assert got["paired"] == 1
    assert prefill_tokens.reduce(
        _events(issues, [(0.0980, 0.130)]))["paired"] == 0


def test_spans_without_tokens_or_no_prefill_runs_read_as_nothing():
    """A program that predates the attributes, and a CPU capture (no
    device plane, so no programs)."""
    old = [_issue(1, 0.0, None), _issue(2, 0.5, None)]
    assert prefill_tokens.reduce(_events(old, [(0.1, 0.2), (0.6, 0.7)])) \
        is None
    assert prefill_tokens.reduce(_events([_issue(1, 0.0, 100)], [])) is None
    assert prefill_tokens.reduce({"spans": [], "programs": {}}) is None
    assert prefill_tokens.notes(None) == []


def test_the_metric_through_its_reader_and_its_notes(monkeypatch, tmp_path):
    """``read`` over a run's context: microseconds a token of the paired
    dispatches, the reduction made once, the notes in the result line."""
    issues = [_issue(10 + 2 * i, 0.4 * i, 1000 + 100 * i) for i in range(5)]
    issues.append(_issue(30, 2.1, 512, rows=1, t=512))
    runs = [(0.4 * i + 0.2, 0.4 * i + 0.25) for i in range(5)]
    runs.append((2.3, 2.32))
    calls = []
    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(spans, "read_events", lambda path: calls.append(
        path) or _events(issues, runs))
    ctx = {"trace_info": {"dirs": [str(tmp_path)]}, "trace": {"notes": []},
           "counters": WINDOW_COUNTERS}
    tokens = sum(1000 + 100 * i for i in range(5)) + 512
    want = 1e6 * (5 * 0.05 + 0.02) / tokens
    assert read(SPAN_METRIC, ctx) == pytest.approx(want)
    assert read(SPAN_METRIC, ctx) == pytest.approx(want)
    assert len(calls) == 1
    assert ctx["trace"]["notes"] == [
        "prefill_tokens: paired 6 of 6 prefill dispatches (0 cut by the "
        "capture's end), 6 runs in the capture",
        "device seconds a prefill dispatch by program: "
        "[1,512] x1 mean 0.0200 s 512 tokens, "
        "[8,256] x5 mean 0.0500 s 6000 tokens",
        "admission over the window: 50 prefill dispatches of 380 rows; "
        "passes stopped by rows 45, seqs 0, tokens 0, window 0, slots 0, "
        "blocks 0"]
    # A program without the counters (the parent) adds no such line.
    assert prefill_tokens.admission_notes(
        {"pstpu:prefill_dispatches_total": 50.0}) == []


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path):
    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(spans, "read_events", broken)
    ctx = {"trace_info": {"dirs": [str(tmp_path)]}, "trace": {"notes": []}}
    assert read(SPAN_METRIC, ctx) is None
    assert ctx["trace"]["notes"] == [
        "prefill_tokens: capture not read (ValueError: truncated)"]


def test_nothing_without_a_capture():
    for ctx in ({"trace_info": {}}, {"trace_info": {"dirs": []}},
                {"trace_info": {"dirs": ["/no/such/dir"]}}, {}):
        assert read(SPAN_METRIC, ctx) is None


def test_nothing_on_the_capture_recorded_before_spans_carried_tokens():
    """``data/loop_spans`` (PR 24, a TPU v5e): prefill issue spans and
    ``jit__prefill_impl`` runs, no ``tokens``. The parent's captures read
    the same way."""
    events = spans.read_events(xplane.find(RECORDED))
    prefills = [s for s in events["spans"] if s["name"] == "pstpu.issue"
                and s.get("kind") == "prefill"]
    assert prefills and events["programs"].get("jit__prefill_impl")
    assert not [s for s in prefills if "tokens" in s]
    ctx = {"trace_info": {"dirs": [RECORDED]}, "trace": {"notes": []}}
    assert read(SPAN_METRIC, ctx) is None
    assert ctx["trace"]["notes"] == []


# ------------------------------------------------------------ the manifest
def test_the_six_are_where_the_record_has_them_and_list_every_cell():
    """PR 36 held its six to be the LAST of ``per_layer``, which no
    appending PR can keep: they are the block at index 40 of the recorded
    manifest, naming every cell it has, and the live manifest has only
    grown from that record (``test_bench_hc.py`` holds the block on the
    live one)."""
    doc, was = live(), recorded()
    assert validate(doc, REPO) == []
    assert grown_from(doc, was) == []
    cells = [w["name"] for w in was["workloads"]]
    block = was["per_layer"][PR36_AT:PR36_AT + len(ISSUE_METRICS)]
    assert [m["name"] for m in block] == list(ISSUE_METRICS)
    manifest = Manifest(REPO)
    for metric in block:
        assert metric["workloads"] == cells
        assert metric["moves"] == ("tpot_p50_ms" if metric["name"]
                                   == "decode_empty_step_pct"
                                   else "req_p50_ms")
        assert metric["source"] == ("program_span" if metric["name"]
                                    == SPAN_METRIC else "program_counter")
        fn, args = manifest.reader(metric["name"])
        assert callable(fn) and isinstance(args, dict)
    layers = {m["name"]: m["layer"] for m in block}
    assert layers[SPAN_METRIC] == layers["serving_compile_s"] == "runner"
    assert {layers[n] for n in ISSUE_METRICS
            if layers[n] != "runner"} == {"scheduler"}


# ----------------------------------------------------- a traced rehearsal
@pytest.fixture(scope="module")
def traced_rehearsal():
    code, line, err = run_cell(REPO, "qwen2.5-3b.chat-saturated",
                               "--rehearse", trace=1, seconds=6)
    assert code == 0 and line is not None, err
    return line


def test_traced_rehearsal_reports_the_issue_metrics(traced_rehearsal):
    metrics = traced_rehearsal["metrics"]
    for name in COUNTER_METRICS:
        assert name in metrics, name
    assert 0 < metrics["prefill_fill_pct"]["value"] <= 100
    assert metrics["prefill_tok_per_dispatch"]["value"] > 0
    assert metrics["admit_left_waiting_mean"]["value"] >= 0
    assert 0 <= metrics["decode_empty_step_pct"]["value"] <= 100
    # Every shape was warmed in set-up: nothing compiles in the window.
    assert metrics["serving_compile_s"]["value"] == 0
    # No device plane on the CPU: the capture's reader finds no prefill
    # run to pair and its metric is left out, as on the parent.
    assert SPAN_METRIC not in metrics


def test_traced_rehearsal_line_has_no_metric_of_an_untraced_run(
        traced_rehearsal):
    assert not {"req_p50_ms", "tpot_p50_ms"} & set(
        traced_rehearsal["metrics"])
    assert traced_rehearsal["correct"] is False      # a CPU rehearsal
