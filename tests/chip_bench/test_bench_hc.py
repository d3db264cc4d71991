"""What PR 38 appended to ``BENCHMARK.json`` (a configuration, a cell, three
per-layer metrics of the stream mix, and the cell's name at the end of the
lists that name every cell), and what
``test_bench_issue.py::test_the_six_are_the_last_of_per_layer_and_list_every_cell``
asserts of PR 36's six except that they are LAST, which no appending PR can
keep (``tests/conftest.py`` marks that one test): here the block is pinned
to the indices it has. The reader of the three metrics on hand-built
contexts: nothing without a capture, for a model with one stream, or on
the capture recorded before the scopes existed."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import hc_trace  # noqa: E402
from test_bench_issue import ISSUE_METRICS, SPAN_METRIC  # noqa: E402

CELL = "xing4.0-29b-a4b-d7.chat-saturated"
CONFIG = "xing4.0-29b-a4b-d7"
HC_METRICS = ("hc_decode_roofline_pct", "hc_mix_roofline_pct",
              "hc_share_pct")
# Where PR 36's six start in ``per_layer`` (PR 35's list had 40).
PR36_AT = 40
# Lists that named all five cells before this PR: PR 24's five span and
# scope metrics and PR 36's six.
EVERY_CELL = ("prefill_device_wait_ms", "fetch_lag_ms", "sample_share_pct",
              "kv_write_share_pct", "unscoped_share_pct") + ISSUE_METRICS
# Metrics of kanana-2-30b-a3b-d8's whose arithmetic reads widths from
# config.json and is right for this model too.
SHARED = ("moe_gmm_roofline_pct", "mla_decode_roofline_pct", "moe_share_pct",
          "moe_experts_touched")


@pytest.fixture(scope="module")
def doc():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_pr36_block_is_where_it_was_and_lists_every_cell(doc):
    assert validate(doc, REPO) == []
    cells = [w["name"] for w in doc["workloads"]]
    block = doc["per_layer"][PR36_AT:PR36_AT + len(ISSUE_METRICS)]
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
    # What follows the block was appended later and lists its cells.
    for metric in doc["per_layer"][PR36_AT + len(ISSUE_METRICS):]:
        assert metric.get("workloads"), metric["name"]


def test_the_new_entries_are_the_last_of_their_lists(doc):
    assert doc["configs"][-1]["name"] == CONFIG
    assert doc["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert doc["configs"][-1]["file"] == \
        f"benchmarks/chip/configs/{CONFIG}/config.json"
    assert doc["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "chat-saturated",
        "chips": 1, "why": doc["workloads"][-1]["why"]}
    assert len(doc["workloads"][-1]["why"]) <= 200
    last = doc["per_layer"][-len(HC_METRICS):]
    assert [m["name"] for m in last] == list(HC_METRICS)
    for metric in last:
        assert metric == {
            "name": metric["name"], "unit": "%",
            "better": "lower" if metric["name"] == "hc_share_pct"
            else "higher", "source": "device_trace",
            "layer": "model and attention kernels", "moves": "tpot_p50_ms",
            "workloads": [CELL]}


def test_the_cell_is_named_last_where_its_readers_find_something(doc):
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in EVERY_CELL + SHARED + ("out_tok_s",):
        assert by_name[name]["workloads"][-1] == CELL, name
    # lib/shapes_moe.py counts a full-rank W_q (22.0 M where q_a + q_b are
    # 7.5 M): kanana's whole-step share would read HIGH here;
    # hc_decode_roofline_pct stands in.
    assert by_name["moe_decode_roofline_pct"]["workloads"] == [
        "kanana-2-30b-a3b-d8.chat-saturated"]
    listed = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(EVERY_CELL + SHARED + HC_METRICS) | {"out_tok_s"}
    # Those without a list are reported in every cell, this one too.
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "per_layer")}
    assert {"decode_roofline_pct", "prefill_mfu_pct", "hbm_peak_gb",
            "decode_rows_per_step"} <= reported
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


def test_the_manifest_only_grew_at_the_ends_since_the_parent(doc):
    """Against the committed parent where git has one (a checkout the
    driver made has no history: skipped there)."""
    try:
        was = json.loads(subprocess.run(
            ["git", "show", "545084e5e735792696ba8dc263f42309c2a91a83:"
             "BENCHMARK.json"], cwd=REPO, capture_output=True, check=True,
            text=True).stdout)
    except (subprocess.CalledProcessError, OSError):
        pytest.skip("no git history here")
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert doc[key] == was[key]
    for key in ("configs", "workloads"):
        assert doc[key][:len(was[key])] == was[key]
        assert len(doc[key]) == len(was[key]) + 1
    assert len(doc["per_layer"]) == len(was["per_layer"]) + len(HC_METRICS)
    for now, then in zip(doc["per_layer"], was["per_layer"]):
        grown = dict(then)
        if now != then:
            grown["workloads"] = then["workloads"] + [CELL]
        assert now == grown, then["name"]


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert set(flags) == {"--max-model-len", "--max-num-seqs",
                          "--max-num-batched-tokens", "--attn-impl",
                          "--num-kv-blocks"}
    assert all(f["why"] and "TO BE FILLED" not in f["why"]
               for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "xing4_0"
    assert manifest.traffic("chat-saturated")["users"] == 48
    for name in HC_METRICS:
        fn, args = manifest.reader(name)
        assert fn is hc_trace.read and set(args) == {"field"}


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=()):
    return {"model_config": cfg, "trace_info": {"dirs": list(dirs)},
            "trace": {"notes": []}, "results": []}


@pytest.mark.parametrize("name", HC_METRICS)
def test_nothing_without_a_capture_or_with_one_stream(name):
    fn, args = Manifest(REPO).reader(name)
    xing = Manifest(REPO).model_config(CONFIG)
    assert fn(_ctx(xing), **args) is None
    assert fn(_ctx(xing, ["/no/such/dir"]), **args) is None
    kanana = Manifest(REPO).model_config("kanana-2-30b-a3b-d8")
    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    assert fn(_ctx(kanana, [recorded]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share():
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    no ``hc_*`` scope, no latent kernel: no metric, and no exception."""
    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    xing = Manifest(REPO).model_config(CONFIG)
    ctx = _ctx(xing, [recorded])
    for name in HC_METRICS:
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert ctx["trace"]["notes"] == []
    inner = hc_trace.scope_seconds(
        __import__("benchmarks.chip.lib.xplane", fromlist=["find"]).find(
            recorded))
    assert inner["hc"] == 0 and inner["mix_decode"] == 0
    assert inner["busy_s"] > 0


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(Manifest(REPO).model_config(CONFIG), [str(tmp_path)])
    assert hc_trace.read(ctx, "hc_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "hc_trace: capture not read (ValueError: truncated)"]


def test_scope_seconds_sorts_the_mix_from_the_rest(monkeypatch):
    """Self-time by ``tf_op`` path: the mix of the decode program apart
    from the prefill's, ``hc_head`` in the share and not in the mix."""
    from benchmarks.chip.lib import spans

    paths = {
        "a": "jit(_decode_impl)/while/body/attn_proj/hc_pre/dot_general",
        "b": "jit(_decode_impl)/while/body/ffn/hc_post/add",
        "c": "jit(_decode_impl)/while/body/ffn/moe_experts/moe_gmm/x",
        "d": "jit(_prefill_impl)/attn_proj/hc_pre/mul",
        "e": "jit(_decode_impl)/logits/hc_head/reduce_sum",
        "f": None,
    }
    seconds = {"a": 0.5, "b": 0.25, "c": 4.0, "d": 1.0, "e": 0.125, "f": 2.0}
    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: {"ops": []})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)
    got = hc_trace.scope_seconds("x")
    assert got == {"hc": 1.875, "mix_decode": 0.75, "busy_s": 7.875}
