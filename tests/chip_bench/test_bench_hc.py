"""What PR 38 appended to ``BENCHMARK.json`` (a configuration, a cell, three
per-layer metrics of the stream mix, and the cell's name at the end of the
lists that name every cell). PR 38 held its entries to be the LAST of their
lists, which the next appending PR could not keep: they are held where the
ONE recorded manifest has them (``data/manifest.recorded.json``, PR 51),
from which the live one may only have grown (``bench_helpers.grown_from``);
``test_bench_ssm.py`` holds them at the same indices on the live manifest.
PR 36's six likewise, on the live manifest here. The reader of the three
metrics on hand-built contexts: nothing without a capture, for a model
with one stream, or on the capture recorded before the scopes existed."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import hc_trace  # noqa: E402
from test_bench_issue import (ISSUE_METRICS, PR36_AT,  # noqa: E402
                              SPAN_METRIC)

CELL = "xing4.0-29b-a4b-d7.chat-saturated"
CONFIG = "xing4.0-29b-a4b-d7"
HC_METRICS = ("hc_decode_roofline_pct", "hc_mix_roofline_pct",
              "hc_share_pct")
# Where PR 38's entries stand: PR 36's lists had 4, 5 and 46 entries.
CONFIG_AT, CELL_AT, HC_AT = 4, 5, 46
# Lists that named all five cells before this PR: PR 24's five span and
# scope metrics and PR 36's six.
EVERY_CELL = ("prefill_device_wait_ms", "fetch_lag_ms", "sample_share_pct",
              "kv_write_share_pct", "unscoped_share_pct") + ISSUE_METRICS
# Metrics of kanana-2-30b-a3b-d8's whose arithmetic reads widths from
# config.json and is right for this model too.
SHARED = ("moe_gmm_roofline_pct", "mla_decode_roofline_pct", "moe_share_pct",
          "moe_experts_touched")
# What names the cell since: PR 49's three of the device's memory, and
# (PR 51) the per-step time, right where every layer calls the paged
# decode kernel once a step, as this model's do.
SINCE = ("hbm_high_water_gb", "hbm_headroom_pct", "hbm_unexplained_gb",
         "decode_step_ms")
# The dense arithmetic's two (``lib/shapes.py``) list the dense cells only
# since PR 51: here they read 0.71 x and 1.58 x the true shares.
NOT_THIS_MODELS = ("decode_roofline_pct", "prefill_mfu_pct")


@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.fixture(scope="module")
def was():
    return recorded()


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


def test_the_new_entries_are_where_the_record_has_them(was):
    assert was["configs"][CONFIG_AT]["name"] == CONFIG
    assert was["configs"][CONFIG_AT]["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert was["configs"][CONFIG_AT]["file"] == \
        f"benchmarks/chip/configs/{CONFIG}/config.json"
    assert was["workloads"][CELL_AT] == {
        "name": CELL, "config": CONFIG, "traffic": "chat-saturated",
        "chips": 1, "why": was["workloads"][CELL_AT]["why"]}
    assert len(was["workloads"][CELL_AT]["why"]) <= 200
    block = was["per_layer"][HC_AT:HC_AT + len(HC_METRICS)]
    assert [m["name"] for m in block] == list(HC_METRICS)
    for metric in block:
        assert metric == {
            "name": metric["name"], "unit": "%",
            "better": "lower" if metric["name"] == "hc_share_pct"
            else "higher", "source": "device_trace",
            "layer": "model and attention kernels", "moves": "tpot_p50_ms",
            "workloads": [CELL]}


def test_the_cell_is_named_where_the_record_names_it(was):
    """Last of each list as PR 38 left it: the place it has in the record,
    whatever followed it."""
    by_name = {m["name"]: m for m in was["per_layer"]}
    for name in EVERY_CELL + SHARED + ("out_tok_s",):
        at = {"out_tok_s": 3}.get(name, 1 if name in SHARED else CELL_AT)
        assert by_name[name]["workloads"][at] == CELL, name
    # lib/shapes_moe.py counts a full-rank W_q (22.0 M where q_a + q_b are
    # 7.5 M): kanana's whole-step share would read HIGH here;
    # hc_decode_roofline_pct stands in.
    assert by_name["moe_decode_roofline_pct"]["workloads"] == [
        "kanana-2-30b-a3b-d8.chat-saturated"]
    listed = {m["name"] for m in was["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(EVERY_CELL + SHARED + HC_METRICS + SINCE) \
        | {"out_tok_s"}
    # Those without a list are reported in every cell, this one too; the
    # dense arithmetic's two are not this model's, and its own stands in.
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "per_layer")}
    assert listed | {"attn_share_pct", "hbm_peak_gb", "device_idle_pct",
                     "decode_rows_per_step"} <= reported
    assert not set(NOT_THIS_MODELS) & reported
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


def test_the_manifest_only_grew_at_the_ends_from_the_record(doc, was):
    """What PR 38 appended stands behind what its parent had, in the
    record, and the live manifest holds the record as its head."""
    assert grown_from(doc, was) == []
    assert [c["name"] for c in was["configs"][:CONFIG_AT]] == [
        "qwen2.5-3b", "mistral-7b-d16", "olmo-hybrid-7b-d16",
        "kanana-2-30b-a3b-d8"]
    assert [w["name"] for w in was["workloads"][:CELL_AT]] == [
        "qwen2.5-3b.chat-steady", "mistral-7b-d16.agent-prefix",
        "qwen2.5-3b.chat-saturated", "olmo-hybrid-7b-d16.chat-saturated",
        "kanana-2-30b-a3b-d8.chat-saturated"]
    # PR 36's six were the last of its parent's ``per_layer``.
    assert PR36_AT + len(ISSUE_METRICS) == HC_AT


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
