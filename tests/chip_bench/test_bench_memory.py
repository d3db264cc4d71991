"""The three per-layer metrics that read what the engine's memory ledger
puts on a capture's executor-side spans (PR 49: ``hbm``, ``hbm_peak``,
``hbm_limit`` on ``pstpu.issue.enqueue`` and ``pstpu.fetch.sync``,
``hbm_explained`` on the first): one reader, ``readers/hbm_spans.py``, on
synthetic captures built the way ``test_bench_issue.py`` builds its own;
nothing without the attributes (the parent's program, the capture recorded
before them) or without a capture; the manifest's three entries pinned to
the INDICES they have and to no end of a list, each naming the nine cells,
and nothing that was in ``BENCHMARK.json`` changed (held against the ONE
recorded manifest, ``data/manifest.recorded.json``, PR 51)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import spans, xplane  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import hbm_spans  # noqa: E402

RECORDED = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
HBM_METRICS = (("hbm_high_water_gb", "GB", "lower", "high_water_gb"),
               ("hbm_headroom_pct", "%", "higher", "headroom_pct"),
               ("hbm_unexplained_gb", "GB", "lower", "unexplained_gb"))
# Where this PR's entries stand (and will, whatever is appended after).
HBM_AT = 66
NINE_CELLS = [
    "qwen2.5-3b.chat-steady", "mistral-7b-d16.agent-prefix",
    "qwen2.5-3b.chat-saturated", "olmo-hybrid-7b-d16.chat-saturated",
    "kanana-2-30b-a3b-d8.chat-saturated",
    "xing4.0-29b-a4b-d7.chat-saturated",
    "granite-4.0-h-micro.chat-saturated", "lfm2-8b-a1b-d16.chat-saturated",
    "trinity-mini-d8.longdoc-saturated"]
GB = 10 ** 9
LIMIT = 16_900_000_000


def read(name, ctx):
    fn, args = Manifest(REPO).reader(name)
    return fn(ctx, **args)


# ----------------------------------------------------- a capture, by hand
def _enqueue(step, start, hbm, peak, explained, limit=LIMIT):
    return dict(name="pstpu.issue.enqueue", start=start, end=start + 0.002,
                step=step, hbm=hbm, hbm_peak=peak, hbm_limit=limit,
                hbm_explained=explained)


def _sync(step, start, hbm, peak, limit=LIMIT):
    return dict(name="pstpu.fetch.sync", start=start, end=start + 0.3,
                step=step, hbm=hbm, hbm_peak=peak, hbm_limit=limit)


def _capture():
    """A decode train and a prefill behind it, three cycles: residents of
    9.7 GB, a decode program holding 2.6 GB and a prefill program 1.9 GB.
    The peak was 14.4 GB before the capture and rises to 14.9 inside it;
    the fullest enqueue (step 14) has 0.3 GB nothing accounts for."""
    return [
        dict(name="pstpu.issue", start=0.0, end=0.004, step=10,
             kind="decode", rows=17, k=32),
        _enqueue(10, 0.001, 12_300_000_000, 14_400_000_000,
                 12_300_000_000),
        _enqueue(11, 0.010, 14_200_000_000, 14_400_000_000,
                 14_200_000_000),
        _sync(10, 0.020, 11_600_000_000, 14_400_000_000),
        _enqueue(12, 0.400, 14_250_000_000, 14_400_000_000,
                 14_200_000_000),
        _sync(11, 0.410, 12_300_000_000, 14_400_000_000),
        _enqueue(13, 0.800, 12_300_000_000, 14_400_000_000,
                 12_300_000_000),
        _sync(12, 0.810, 9_700_000_000, 14_400_000_000),
        _enqueue(14, 1.200, 14_500_000_000, 14_900_000_000,
                 14_200_000_000),
        _sync(13, 1.210, 12_300_000_000, 14_900_000_000),
        dict(name="pstpu.apply", start=1.6, end=1.601, step=13,
             kind="decode"),
    ]


def test_the_three_values_of_a_capture():
    got = hbm_spans.reduce(_capture())
    assert got["spans"] == 9
    assert got["high_water_gb"] == pytest.approx(14.9)
    assert got["headroom_pct"] == pytest.approx(100 * 2.0 / 16.9)
    assert got["unexplained_gb"] == pytest.approx(0.3)
    assert (got["at_step"], got["in_use_gb"]) == (14, pytest.approx(14.5))


def test_the_unexplained_is_signed():
    """A family the ledger measured high explains more than is in use."""
    found = [_enqueue(3, 0.0, 12 * GB, 13 * GB, 12 * GB + 250_000_000)]
    assert hbm_spans.reduce(found)["unexplained_gb"] == pytest.approx(-0.25)


def test_the_peak_may_stand_on_a_sync_span_alone():
    """An allocator that takes temporaries at launch shows its peak after
    the sync; a capture that holds no enqueue reads no ``unexplained``."""
    got = hbm_spans.reduce([_sync(5, 0.0, 10 * GB, 15 * GB),
                            _sync(6, 0.5, 10 * GB, 15 * GB + 5)])
    assert got["high_water_gb"] == pytest.approx(15.000000005)
    assert got["headroom_pct"] == pytest.approx(
        100 * (LIMIT - 15 * GB - 5) / LIMIT)
    assert got["unexplained_gb"] is None


def test_attributes_read_from_a_capture_are_strings_or_numbers():
    """``lib/spans.py`` hands event stats over as the profiler gives them."""
    found = [{k: str(v) if k.startswith("hbm") else v
              for k, v in span.items()} for span in _capture()]
    assert hbm_spans.reduce(found) == hbm_spans.reduce(_capture())


def test_no_limit_no_headroom():
    got = hbm_spans.reduce([_sync(1, 0.0, GB, 2 * GB, limit=0)])
    assert got["high_water_gb"] == 2.0 and got["headroom_pct"] is None


@pytest.mark.parametrize("name,unit,better,field", HBM_METRICS)
def test_each_metric_through_its_reader_once_a_run(monkeypatch, tmp_path,
                                                   name, unit, better,
                                                   field):
    calls = []
    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(spans, "read_events", lambda path: calls.append(
        path) or {"spans": _capture(), "programs": {}})
    ctx = {"trace_info": {"dirs": [str(tmp_path)]}, "trace": {"notes": []}}
    want = {"high_water_gb": 14.9, "headroom_pct": 100 * 2.0 / 16.9,
            "unexplained_gb": 0.3}
    assert read(name, ctx) == pytest.approx(want[field])
    for other, _, _, other_field in HBM_METRICS:
        assert read(other, ctx) == pytest.approx(want[other_field])
    assert len(calls) == 1                  # one read of the capture a run
    assert ctx["trace"]["notes"] == [
        "hbm_spans: 9 spans say the allocator's reading; most in use "
        "14.500 GB at step 14"]
    spec = json.load(open(os.path.join(
        REPO, "benchmarks", "chip", "metrics", f"{name}.json")))
    assert spec["reader"] == "hbm_spans" and spec["args"] == {"field": field}
    assert set(spec) == {"name", "reader", "args", "what"}


# -------------------------------------------------------- nothing to read
@pytest.mark.parametrize("name", [m[0] for m in HBM_METRICS])
def test_nothing_without_a_capture(name):
    for ctx in ({}, {"trace_info": {}}, {"trace_info": {"dirs": []}},
                {"trace_info": None, "trace": None}):
        assert read(name, ctx) is None


@pytest.mark.parametrize("name", [m[0] for m in HBM_METRICS])
def test_nothing_where_no_span_says_the_peak(monkeypatch, tmp_path, name):
    """The parent's program, and a CPU rehearsal: the spans are there and
    carry no ``hbm_peak``."""
    plain = [{k: v for k, v in s.items() if not k.startswith("hbm")}
             for s in _capture()]
    assert hbm_spans.reduce(plain) is None
    assert hbm_spans.reduce([]) is None
    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(spans, "read_events",
                        lambda path: {"spans": plain, "programs": {}})
    ctx = {"trace_info": {"dirs": [str(tmp_path)]}, "trace": {"notes": []}}
    assert read(name, ctx) is None
    assert ctx["trace"]["notes"] == []


@pytest.mark.parametrize("name", [m[0] for m in HBM_METRICS])
def test_nothing_on_the_capture_recorded_before_the_attributes(name):
    """``data/loop_spans`` (PR 24, a TPU v5e): enqueue and sync spans,
    none with the allocator's reading."""
    events = spans.read_events(xplane.find(RECORDED))
    assert {"pstpu.issue.enqueue", "pstpu.fetch.sync"} <= {
        s["name"] for s in events["spans"]}
    assert hbm_spans.reduce(events["spans"]) is None
    ctx = {"trace_info": {"dirs": [RECORDED]}, "trace": {"notes": []}}
    assert read(name, ctx) is None


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path):
    def broken(path):
        raise ValueError("truncated file")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(spans, "read_events", broken)
    ctx = {"trace_info": {"dirs": [str(tmp_path)]}, "trace": {"notes": []}}
    assert read("hbm_high_water_gb", ctx) is None
    assert ctx["trace"]["notes"] == [
        "hbm_spans: capture not read (ValueError: truncated file)"]


# ----------------------------------------------------------- the manifest
@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.mark.parametrize("i,entry", list(enumerate(HBM_METRICS)))
def test_the_three_entries_by_index(doc, i, entry):
    name, unit, better, _ = entry
    assert doc["per_layer"][HBM_AT + i] == {
        "name": name, "unit": unit, "better": better,
        "source": "program_counter", "layer": "device",
        "moves": "tpot_p50_ms", "workloads": NINE_CELLS}


def test_the_manifest_is_valid_and_the_nine_cells_report_the_three(doc):
    assert validate(doc, REPO) == []
    manifest = Manifest(REPO)
    for cell in NINE_CELLS:
        reported = [m["name"] for m in manifest.metrics_of(cell, "per_layer")]
        assert reported.count("hbm_peak_gb") == 1       # stays as it is
        for name, *_ in HBM_METRICS:
            assert reported.count(name) == 1
    # ``hbm_peak_gb`` is as PR 48 left it: index 19, no list, its reader.
    assert doc["per_layer"][19] == {
        "name": "hbm_peak_gb", "unit": "GB", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "tpot_p50_ms"}
    assert manifest.reader("hbm_peak_gb")[0].__module__.endswith(
        "hbm_in_use")


def _cells_and_what_listed_them():
    """(cell, the metrics that listed it when its PR ended, whether every
    layer of its model calls the paged decode kernel once a step) of the
    four accepted tests that hold that set, from their own constants."""
    import test_bench_afmoe as afmoe
    import test_bench_lfm as lfm
    import test_bench_ssm as ssm

    return [
        (ssm.HC_CELL, set(ssm.EVERY_CELL + ssm.HC_SHARED + ssm.HC_METRICS)
         | {"out_tok_s"}, True),
        (ssm.CELL, set(ssm.EVERY_CELL + ssm.SSM_METRICS) | {"out_tok_s"},
         False),
        (lfm.CELL, set(lfm.EVERY_CELL + lfm.LFM_METRICS
                       + tuple(lfm.NAMED_AT)), False),
        (afmoe.CELL, set(afmoe.EVERY_CELL + afmoe.AFM_METRICS
                         + tuple(afmoe.NAMED_AT)), True),
    ]


@pytest.mark.parametrize("case", range(4))
def test_a_cell_is_listed_where_it_was_and_by_the_three(doc, case):
    """What four accepted tests hold beside the set this PR grew: among
    the entries that were there the cell is named by the metrics that
    named it (and, since PR 51, by the per-step time where kernel calls
    over layers IS a step); after them by this PR's three; it reports the
    list-less metrics and its end-to-end ones as before, and neither of
    the dense arithmetic's two shares (PR 51)."""
    cell, was_listed, a_kernel_a_layer = _cells_and_what_listed_them()[case]
    listed = {m["name"] for m in doc["per_layer"][:HBM_AT]
              if cell in m.get("workloads", ())}
    assert listed - {"decode_step_ms"} == was_listed
    assert ("decode_step_ms" in listed) == a_kernel_a_layer
    assert {m["name"] for m in doc["per_layer"][HBM_AT:HBM_AT + 3]
            if cell in m["workloads"]} == {m[0] for m in HBM_METRICS}
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        cell, "per_layer")}
    assert {"attn_share_pct", "hbm_peak_gb", "device_idle_pct",
            "decode_rows_per_step"} <= reported
    assert not {"decode_roofline_pct", "prefill_mfu_pct"} & reported
    assert ("decode_step_ms" in reported) == a_kernel_a_layer
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        cell, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


def test_nothing_that_was_in_the_manifest_changed(doc):
    """Against the recorded manifest: what it had is there as it was, the
    three entries follow what PR 49's parent had of ``per_layer``, name
    the nine cells it had, and no list that names cells lost or moved one
    (a later PR may append)."""
    was = recorded()
    assert grown_from(doc, was) == []
    assert [w["name"] for w in was["workloads"]] == NINE_CELLS
    assert [m["name"] for m in was["per_layer"][HBM_AT:]] == [
        m[0] for m in HBM_METRICS]
    assert len(was["per_layer"]) == HBM_AT + 3
