"""The per-layer metrics that read the program's own instrumentation (PR
24): counters, loop spans paired with the device's programs, and the
``jax.named_scope`` of each device operation. Each new reader on a
hand-built context; the pairing and the scope shares on a small capture
recorded on a TPU v5e (``data/loop_spans/``, see its ``README.txt``); the extended ``BENCHMARK.json``; and a
traced rehearsal that reports the counter-based metrics."""

import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(__file__))

from bench_helpers import grown_from, live, run_cell  # noqa: E402
from bench_helpers import recorded as recorded_manifest  # noqa: E402

from benchmarks.chip.lib import spans, xplane  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
COUNTER_METRICS = ("decode_rows_per_step", "decode_wasted_step_pct",
                   "sched_ms_per_dispatch", "host_ms_per_dispatch",
                   "http_surface_ms_mean")
SPAN_METRICS = ("prefill_device_wait_ms", "fetch_lag_ms")
SCOPE_METRICS = ("sample_share_pct", "kv_write_share_pct",
                 "unscoped_share_pct")
NEW_METRICS = COUNTER_METRICS + SPAN_METRICS + SCOPE_METRICS
# Where the ten start in ``per_layer`` (PR 23's list had 20).
PR24_AT = 20


def read(name, ctx):
    fn, args = Manifest(REPO).reader(name)
    return fn(ctx, **args)


# ------------------------------------------------- readers, by hand
WINDOW_COUNTERS = {
    "pstpu:decode_steps_total": 400.0,
    "pstpu:decode_row_steps_total": 1000.0,
    "pstpu:decode_row_steps_wasted_total": 80.0,
    "pstpu:decode_dispatches_total": 30.0,
    "pstpu:prefill_dispatches_total": 20.0,
    "pstpu:loop_schedule_seconds_total": 0.010,
    "pstpu:loop_issue_seconds_total": 0.100,
    "pstpu:loop_fetch_wait_seconds_total": 48.0,
    "pstpu:loop_apply_seconds_total": 0.030,
    "pstpu:loop_idle_seconds_total": 2.0,
    "pstpu:loop_other_seconds_total": 0.010,
    "pstpu:http_ingress_seconds_sum": 0.040,
    "pstpu:http_ingress_seconds_count": 20.0,
    "pstpu:first_chunk_emit_seconds_sum": 0.010,
    "pstpu:first_chunk_emit_seconds_count": 20.0,
    "vllm:generation_tokens_total": 940.0,
    "vllm:time_to_first_token_seconds_count": 20.0,
}


@pytest.mark.parametrize("name,want", [
    ("decode_rows_per_step", 2.5),
    ("decode_wasted_step_pct", 8.0),
    ("sched_ms_per_dispatch", 0.2),
    ("host_ms_per_dispatch", 3.0),       # (10 + 100 + 30 + 10) ms / 50
    ("http_surface_ms_mean", 2.5),       # 2.0 ms + 0.5 ms
])
def test_counter_metric_from_a_windows_deltas(name, want):
    ctx = {"counters": dict(WINDOW_COUNTERS), "span_s": 50.0}
    assert read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_counter_metric_is_nothing_where_the_program_lacks_the_series(name):
    """The parent commit exports none of the new series: the reader gives
    nothing and does not raise, and the line leaves the metric out."""
    parent = {k: v for k, v in WINDOW_COUNTERS.items()
              if k.startswith("vllm:") or "dispatches" in k}
    assert read(name, {"counters": parent, "span_s": 50.0}) is None
    assert read(name, {"counters": {}, "span_s": 50.0}) is None


def test_a_window_with_no_dispatch_divides_by_nothing():
    quiet = dict(WINDOW_COUNTERS)
    quiet.update({"pstpu:decode_dispatches_total": 0.0,
                  "pstpu:prefill_dispatches_total": 0.0,
                  "pstpu:decode_steps_total": 0.0,
                  "pstpu:decode_row_steps_total": 0.0,
                  "pstpu:http_ingress_seconds_count": 0.0})
    for name in COUNTER_METRICS:
        assert read(name, {"counters": quiet, "span_s": 50.0}) is None


@pytest.mark.parametrize("name", SPAN_METRICS + SCOPE_METRICS)
def test_trace_metric_is_nothing_without_a_capture(name):
    for ctx in ({"trace_info": {}}, {"trace_info": {"dirs": []}},
                {"trace_info": {"dirs": ["/no/such/dir"]}}):
        assert read(name, ctx) is None


def test_span_and_scope_readers_read_the_runs_one_reduction():
    ctx = {"_pstpu_spans": {
        "spans": {"prefill_device_wait_s": 0.210, "fetch_lag_s": 0.0012},
        "scopes": {"busy_s": 4.0, "seconds": {
            "ffn": 2.0, "logits": 0.3, "sample": 0.1, "kv_write": 0.2,
            "unscoped": 0.8}}}}
    assert read("prefill_device_wait_ms", ctx) == pytest.approx(210.0)
    assert read("fetch_lag_ms", ctx) == pytest.approx(1.2)
    assert read("sample_share_pct", ctx) == pytest.approx(10.0)
    assert read("kv_write_share_pct", ctx) == pytest.approx(5.0)
    assert read("unscoped_share_pct", ctx) == pytest.approx(20.0)
    # Under 90% paired the span reduction holds no means: nothing read.
    ctx["_pstpu_spans"]["spans"] = {"completed": 10, "matched": 8,
                                    "matched_share": 0.8}
    assert read("fetch_lag_ms", ctx) is None


# --------------------------------------------- spans <-> programs, by hand
def _span(name, start, end, **attrs):
    return dict(attrs, name=name, start=start, end=end)


def _dispatch(step, kind, issue, fetch, sync=1):
    """The four spans of one dispatch: the executor-side parts sit inside
    the loop-side ones."""
    (i0, i1), (f0, f1) = issue, fetch
    return [
        _span("pstpu.issue", i0, i1, step=step, kind=kind, rows=2, k=8),
        _span("pstpu.issue.enqueue", i0 + 1e-4, i1 - 1e-4, step=step),
        _span("pstpu.fetch", f0, f1, step=step, kind=kind, sync=sync),
        _span("pstpu.fetch.sync", f0 + 1e-4, f1 - 2e-4, step=step),
    ]


def test_a_prefill_issued_behind_a_train_waits_for_the_device():
    """Decode train 7 runs 0.000-0.300 on the device; prefill 8 is issued
    at 0.010 and starts when the train ends."""
    found = (_dispatch(7, "decode", (-0.002, 0.000), (0.001, 0.302))
             + _dispatch(8, "prefill", (0.010, 0.012), (0.303, 0.3515)))
    events = {"spans": found, "programs": {
        "jit__decode_impl": [(0.0002, 0.3000)],
        "jit__prefill_impl": [(0.3001, 0.3500)]}}
    got = spans.reduce_spans(events)
    assert (got["completed"], got["matched"]) == (2, 2)
    assert got["prefill_device_wait_s"] == pytest.approx(0.3001 - 0.012)
    assert got["fetch_lag_s"] == pytest.approx((0.002 + 0.0015) / 2)
    assert got["executor_hop_s"] == pytest.approx(2e-4)
    assert got["device_sync_s"] == pytest.approx(
        (0.002 + 0.0015) / 2 - 2e-4)


def test_chained_trains_pair_in_order_and_a_program_is_taken_once():
    """Two decode trains in flight: 5 is issued while 4 runs, 6 while 5
    runs. A train that began before its issue did is never its program."""
    found = (_dispatch(4, "decode", (0.000, 0.002), (0.104, 0.2015))
             + _dispatch(5, "decode", (0.100, 0.102), (0.2016, 0.3015))
             + _dispatch(6, "decode", (0.2017, 0.2030), (0.3016, 0.4015)))
    runs = [(0.003, 0.200), (0.2001, 0.300), (0.3001, 0.400)]
    paired = spans.pair(spans.dispatches(found), {"jit__decode_impl": runs})
    assert [(d["step"], run) for d, run in paired["pairs"]] == [
        (4, runs[0]), (5, runs[1]), (6, runs[2])]


def test_a_capture_cut_mid_dispatch_leaves_it_incomplete_not_mispaired():
    """The capture starts inside train 3 (its issue span is not in it) and
    ends inside train 5 (its fetch span is not in it): only train 4 is a
    completed dispatch, and it takes its own program, not a neighbour's."""
    found = (_dispatch(3, "decode", (0.0, 0.0), (0.001, 0.101))[2:]
             + _dispatch(4, "decode", (0.050, 0.052), (0.1012, 0.2012))
             + _dispatch(5, "decode", (0.1015, 0.1030), (0.0, 0.0))[:2])
    runs = [(0.0005, 0.100), (0.1001, 0.200), (0.2001, 0.290)]
    got = spans.reduce_spans({"spans": found,
                              "programs": {"jit__decode_impl": runs}})
    assert (got["completed"], got["matched"]) == (1, 1)
    assert got["fetch_lag_s"] == pytest.approx(0.2012 - 0.200)
    assert got["prefill_device_wait_s"] is None


def test_a_fetch_that_touches_no_device_is_not_paired():
    """A prefill chunk no row of which ended its prompt: its fetch returns
    at once, long before its program ends (``sync`` 0)."""
    found = _dispatch(9, "prefill", (0.000, 0.002), (0.0021, 0.0022), sync=0)
    got = spans.reduce_spans({"spans": found, "programs": {
        "jit__prefill_impl": [(0.003, 0.060)]}})
    assert got is None


def test_below_ninety_percent_paired_there_is_no_mean():
    found = []
    for step in range(10):
        t = step * 0.1
        found += _dispatch(step, "decode", (t, t + 0.002),
                           (t + 0.003, t + 0.0995))
    runs = [(s * 0.1 + 0.0025, s * 0.1 + 0.098) for s in range(8)]
    got = spans.reduce_spans({"spans": found,
                              "programs": {"jit__decode_impl": runs}})
    assert (got["completed"], got["matched"]) == (10, 8)
    assert "fetch_lag_s" not in got
    got = spans.reduce_spans({"spans": found, "programs": {
        "jit__decode_impl": runs + [(0.8025, 0.898)]}})
    assert got["matched_share"] == 0.9 and got["fetch_lag_s"] > 0


def test_no_spans_or_no_programs_reads_as_nothing():
    assert spans.reduce_spans({"spans": [], "programs": {}}) is None
    assert spans.reduce_spans({"spans": [], "programs": {
        "jit__decode_impl": [(0.0, 0.1)]}}) is None
    assert spans.reduce_scopes({"ops": []}, {}) is None


@pytest.mark.parametrize("tf_op,want", [
    ("jit(_decode_impl)/while/body/while/body/closed_call/ffn/dot_general:",
     "ffn"),
    ("jit(_decode_impl)/while/body/jit(sample_tokens)/sample/argmax:",
     "sample"),
    ("jit(_prefill_impl)/kv_write/scatter:", "kv_write"),
    ("jit(_decode_impl)/while/body/while/body/attn_core/attn_proj/mul:",
     "attn_proj"),                      # the innermost wins
    ("jit(_decode_impl)/while/body/dynamic_update_slice:", None),
    ("kv_k:", None), ("", None), (None, None)])
def test_scope_of_an_operations_path(tf_op, want):
    assert spans.scope_of(tf_op) == want


def test_every_instant_goes_to_one_operation():
    """A parent keeps what its children leave; two operations that overlap
    without nesting share nothing twice; the times sum to the union."""
    ops = [("while", 0.0, 10.0), ("mm", 1.0, 4.0), ("pick", 5.0, 7.0),
           ("async-copy", 6.0, 12.0), ("next", 11.0, 13.0)]
    got = spans.exclusive_seconds(ops)
    # 0-1 while, 1-4 mm, 4-5 while, 5-6 pick, 6-11 async-copy, 11-13 next
    assert got == pytest.approx({"while": 2.0, "mm": 3.0, "pick": 1.0,
                                 "async-copy": 5.0, "next": 2.0})
    assert sum(got.values()) == pytest.approx(13.0)
    assert spans.exclusive_seconds([]) == {}


def test_scope_seconds_over_busy_time():
    ops = [("while", 0.0, 10.0), ("mm", 1.0, 4.0), ("pick", 5.0, 7.0),
           ("%copy.3 = bf16[8,2]{1,0} copy(...)", 11.0, 12.0)]
    got = spans.reduce_scopes({"ops": ops}, {
        "mm": "jit(f)/while/body/ffn/dot_general:",
        "pick": "jit(f)/while/body/sample/argmax:",
        "%copy.3 = bf16[8,2]{1,0} copy(...)": "pool:"})
    assert got["busy_s"] == pytest.approx(11.0)
    assert got["seconds"] == pytest.approx(
        {"ffn": 3.0, "sample": 2.0, "unscoped": 6.0})
    assert got["scoped_ops"] == 2
    assert got["top_unscoped"] == [["while", pytest.approx(5.0)],
                                   ["copy.3 bf16[8,2]", pytest.approx(1.0)]]


# ---------------------------------------------------- the recorded capture
@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "loop_spans.xplane.pb")
    want = json.load(open(os.path.join(DATA, "expected.json")))
    return spans.read_events(path), spans.op_scopes(path), want


def test_recorded_capture_holds_spans_with_their_attributes(recorded):
    events, _, want = recorded
    names = {s["name"] for s in events["spans"]}
    assert {"pstpu.issue", "pstpu.issue.enqueue", "pstpu.fetch",
            "pstpu.fetch.sync", "pstpu.apply", "pstpu.schedule"} <= names
    found = spans.dispatches(events["spans"])
    assert [(d["step"], d.get("kind")) for d in found] == [
        tuple(x) for x in want["dispatches"]]
    assert {p: len(r) for p, r in events["programs"].items()} == \
        want["programs"]


def test_recorded_capture_pairs_each_completed_dispatch(recorded):
    events, _, want = recorded
    got = spans.reduce_spans(events)
    assert (got["completed"], got["matched"]) == (
        want["completed"], want["matched"])
    # The slice cuts a dispatch at each end: cut ones are not completed.
    assert want["completed"] < len(want["dispatches"])
    for key in ("prefill_device_wait_s", "fetch_lag_s", "device_sync_s",
                "executor_hop_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-6), key
    assert got["device_sync_s"] + got["executor_hop_s"] == pytest.approx(
        got["fetch_lag_s"], rel=1e-6)


def test_recorded_capture_scope_shares(recorded):
    events, scopes, want = recorded
    got = spans.reduce_scopes(events, scopes)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert got["scoped_ops"] == want["scoped_ops"] > 0
    assert set(got["seconds"]) >= {"ffn", "attn_proj", "attn_core",
                                   "logits", "kv_write", "unscoped"}
    for scope, seconds in want["scope_seconds"].items():
        assert got["seconds"][scope] == pytest.approx(seconds, rel=1e-6)
    # Every instant is given to one operation: the scopes' seconds sum to
    # busy time, which is the union of the operations' intervals.
    assert sum(got["seconds"].values()) == pytest.approx(got["busy_s"])
    assert got["busy_s"] == pytest.approx(
        xplane.union_seconds(events["ops"]), rel=1e-9)
    assert min(got["seconds"].values()) >= 0


def test_recorded_capture_through_the_readers(recorded):
    _, _, want = recorded
    ctx = {"trace_info": {"dirs": [DATA]}, "trace": {"notes": []},
           "counters": {}, "span_s": 1.0}
    for name, value in want["metrics"].items():
        assert read(name, ctx) == pytest.approx(value, rel=1e-6), name
    assert any(n.startswith("spans: paired") for n in ctx["trace"]["notes"])


# ------------------------------------------------------------ the manifest
def test_extended_manifest_is_valid_and_only_grew():
    """PR 24's ten where the recorded manifest has them (it held them to
    be the LAST of ``per_layer``, which no appending PR can keep), and the
    live manifest grown from that record at the ends of its lists only."""
    doc, was = live(), recorded_manifest()
    assert validate(doc, REPO) == []
    assert grown_from(doc, was) == []
    names = [m["name"] for m in was["per_layer"]]
    assert names[PR24_AT:PR24_AT + len(NEW_METRICS)] == list(NEW_METRICS)
    cells = [w["name"] for w in was["workloads"]]
    by_name = {m["name"]: m for m in was["per_layer"]}
    for name in SPAN_METRICS + SCOPE_METRICS:
        assert by_name[name]["workloads"] == cells
    for name in COUNTER_METRICS:
        assert "workloads" not in by_name[name]
    assert {by_name[n]["source"] for n in SPAN_METRICS} == {"program_span"}
    manifest = Manifest(REPO)
    for name in NEW_METRICS:
        fn, args = manifest.reader(name)
        assert callable(fn) and isinstance(args, dict)


# ----------------------------------------------------- a traced rehearsal
@pytest.fixture(scope="module")
def traced_rehearsal():
    code, line, err = run_cell(REPO, "qwen2.5-3b.chat-steady", "--rehearse",
                               trace=1, seconds=6)
    assert code == 0 and line is not None, err
    return line


def test_traced_rehearsal_reports_the_counter_metrics(traced_rehearsal):
    metrics = traced_rehearsal["metrics"]
    for name in COUNTER_METRICS:
        assert metrics[name]["value"] >= 0, name
    assert metrics["decode_rows_per_step"]["value"] >= 1.0
    assert 0 <= metrics["decode_wasted_step_pct"]["value"] < 100
    assert metrics["host_ms_per_dispatch"]["value"] > \
        metrics["sched_ms_per_dispatch"]["value"] > 0
    # No device plane on the CPU: the span and scope readers find nothing
    # to read and their metrics are left out, as on the parent.
    assert not set(SPAN_METRICS + SCOPE_METRICS) & set(metrics)


def test_traced_rehearsal_keeps_the_counters_identities(traced_rehearsal):
    notes = traced_rehearsal["trace_notes"]
    loop = [n for n in notes if n.startswith("loop: phases sum to")]
    assert loop, notes
    share = float(loop[0].split("(")[1].split("%")[0])
    # The scrapes that bound the window are some tens of milliseconds off
    # its ends; over a 6 s rehearsal that is a percent or two.
    assert 97.0 < share < 103.0
    decode = [n for n in notes if n.startswith("decode: ")][0]
    _, kept, decoded = [float(x.split()[0]) for x in
                        decode[len("decode: "):].split(", ")]
    assert kept == decoded > 0
