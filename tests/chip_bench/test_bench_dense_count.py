"""A share of a roofline, and a per-step time, is reported in a cell only
where the arithmetic behind it is that cell's model's (PR 51).

``decode_roofline_pct`` and ``prefill_mfu_pct`` hold a model to a dense
decoder's count (``lib/shapes.py``) and list the three dense cells;
``decode_step_ms`` divides the paged decode kernel's calls by the layers and
lists the six cells in which every layer calls it once a step;
``attn_share_pct`` is a share of busy time by an operation's name and lists
none. Here: every share a cell reports is computed by the count that
describes its model; the cell that motivated the rule (one chip's share of
an expert-parallel decoder, whose published ``intermediate_size`` is its one
dense layer's) counted by the dense arithmetic, and appended to a copy of
the manifest with no other edit; the reduction asked for no share on a
``config.json`` without a dense decoder's keys; and the readings of a listed
cell bit for bit what the parent read on a recorded capture."""

import inspect
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip import run as bench_run  # noqa: E402
from benchmarks.chip.lib import roofline, shapes  # noqa: E402
from benchmarks.chip.lib.client import Result  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.lib.traffic import Request  # noqa: E402
from benchmarks.chip.readers import trace_field  # noqa: E402
from test_bench_memory import NINE_CELLS  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
THE_THREE = ("decode_step_ms", "decode_roofline_pct", "prefill_mfu_pct")
# The count (a module of ``benchmarks/chip/lib``) that describes a model.
COUNT_OF = {
    "qwen2": {"shapes"}, "mistral": {"shapes"},
    "olmo_hybrid": {"shapes_hybrid"}, "deepseek_v3": {"shapes_moe"},
    # Its latent attention and experts are kanana's module's, by widths
    # read from config.json; the stream mix and the whole step its own.
    "xing4_0": {"shapes_moe", "shapes_hc"},
    "granitemoehybrid": {"shapes_ssm"}, "lfm2_moe": {"shapes_lfm"},
    "afmoe": {"shapes_afmoe"},
}


def count_behind(read) -> set:
    """The ``lib/shapes*.py`` modules a metric's reader counts with: the
    family's own where it imports one (``shapes`` itself is then only the
    roofline's ``least_seconds``), else the dense one; the reduction's
    where the reader hands over a field of it."""
    module = roofline if read is trace_field.read else \
        inspect.getmodule(read)
    found = {value.__name__.rsplit(".", 1)[1]
             for value in vars(module).values()
             if inspect.ismodule(value)
             and value.__name__.startswith("benchmarks.chip.lib.shapes")}
    return found - {"shapes"} or found


# ------------------------------ (a) every share by the count of its model
@pytest.mark.parametrize("cell", NINE_CELLS)
def test_every_share_a_cell_reports_is_by_its_models_count(cell):
    manifest = Manifest(REPO)
    model_type = manifest.model_config(
        manifest.cell(cell)["config"])["model_type"]
    shares = [m["name"] for m in manifest.metrics_of(cell, "per_layer")
              if m["name"].endswith(("_roofline_pct", "_mfu_pct"))]
    # a whole step's share is among them, in every cell
    assert [n for n in shares if "decode_roofline" in n], cell
    for name in shares:
        read, _ = manifest.reader(name)
        assert count_behind(read) and \
            count_behind(read) <= COUNT_OF[model_type], (cell, name)


def test_the_reduction_counts_with_the_dense_arithmetic_alone():
    assert count_behind(trace_field.read) == {"shapes"}


@pytest.mark.parametrize("cell", NINE_CELLS)
def test_the_three_are_reported_where_their_arithmetic_holds(cell):
    manifest = Manifest(REPO)
    cfg = manifest.model_config(manifest.cell(cell)["config"])
    reported = {m["name"] for m in manifest.metrics_of(cell, "per_layer")}
    dense = cfg["model_type"] in ("qwen2", "mistral")
    assert ({"decode_roofline_pct", "prefill_mfu_pct"} <= reported) == dense
    assert not dense or "decode_step_ms" in reported
    # kernel calls / layers is a step where no layer is of another kind
    # (a recurrence, a convolution) that never calls the kernel.
    kinds = set(cfg.get("layer_types") or ["full_attention"])
    a_kernel_a_layer = kinds <= {"full_attention", "sliding_attention"}
    assert ("decode_step_ms" in reported) == a_kernel_a_layer
    assert "attn_share_pct" in reported
    assert "prefill_dev_us_per_token" in reported


# What stands in for a delisted name, a cell (PERF.md section 7's table; the
# old readings are history in its section 6): where 4 of a model's layers
# call the paged kernel, kernel calls / layers read a step 4 x or 10 x too
# long; the dense count read a hybrid's or a sparse model's step at
# 0.09-0.71 x its true share, and one prefill 1.58 x HIGH.
PREFILL = ("prefill_mfu_pct", "prefill_dev_us_per_token")
STANDS_IN = {
    "olmo-hybrid-7b-d16.chat-saturated": [
        ("decode_step_ms", "hyb_decode_step_ms"),
        ("decode_roofline_pct", "hyb_decode_roofline_pct"), PREFILL],
    "kanana-2-30b-a3b-d8.chat-saturated": [
        ("decode_roofline_pct", "moe_decode_roofline_pct"), PREFILL],
    "xing4.0-29b-a4b-d7.chat-saturated": [
        ("decode_roofline_pct", "hc_decode_roofline_pct"), PREFILL],
    "granite-4.0-h-micro.chat-saturated": [
        ("decode_step_ms", "ssm_decode_step_ms"),
        ("decode_roofline_pct", "ssm_decode_roofline_pct"), PREFILL],
    "lfm2-8b-a1b-d16.chat-saturated": [
        ("decode_step_ms", "lfm_decode_step_ms"),
        ("decode_roofline_pct", "lfm_decode_roofline_pct"), PREFILL],
    "trinity-mini-d8.longdoc-saturated": [
        ("decode_roofline_pct", "afm_decode_roofline_pct"), PREFILL],
}


@pytest.mark.parametrize("cell,name,stands_in", [
    (cell, *pair) for cell, pairs in STANDS_IN.items() for pair in pairs])
def test_a_delisted_name_is_not_reported_and_what_stands_in_is(
        cell, name, stands_in):
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        cell, "per_layer")}
    assert name not in reported and stands_in in reported


@pytest.mark.parametrize("cell", NINE_CELLS)
def test_what_a_cell_lost_is_the_tables_names_and_no_other(cell):
    """The parent reported the three in every cell (they listed none)."""
    now = {m["name"] for m in Manifest(REPO).metrics_of(cell, "per_layer")}
    assert set(THE_THREE) - now == {
        name for name, _ in STANDS_IN.get(cell, ())}


# ------------------------------------- (b) the cell that motivated the rule
# One chip's share of an expert-parallel sparse decoder, as its config.json
# would say it: 64 query heads of 192 lanes, 4 KV heads, the published
# ``intermediate_size`` the ONE leading dense layer's, 9 layers and an
# eighth of the vocabulary on the chip.
EP_SHARE = {"hidden_size": 4096, "num_attention_heads": 64, "head_dim": 192,
            "num_key_value_heads": 4, "intermediate_size": 16384,
            "num_hidden_layers": 9, "vocab_size": 19072}


def test_the_dense_count_reads_twice_what_an_expert_parallel_step_reads():
    """At 3.3 rows x 4.9 k keys the step reads ~3.0 GB (the dense layer
    0.58, 8 x 189 MB of attention weights, ~1.6 touched experts of 50 MB in
    each of 8 layers, the head 0.16, ~0.1 of keys and values: ISSUE 51).
    ``lib/shapes.py`` holds 9 dense FFNs of 16384: over 6 GB, so a step at
    80% of its true roofline would read over 160%."""
    work = shapes.decode_step(EP_SHARE, 3.3, 4900)
    assert work["bytes"] >= 6.0e9
    assert shapes.layer_params(EP_SHARE) == \
        4096 * (12288 + 2 * 768) + 12288 * 4096 + 3 * 4096 * 16384
    least = shapes.least_seconds(work, PEAK)
    assert least["bound"] == "memory"
    assert 0.80 * least["seconds"] / (3.0e9 / 819e9) > 1.6


@pytest.fixture(scope="module")
def with_a_tenth_cell(tmp_path_factory):
    """A copy of ``BENCHMARK.json`` with a configuration and a cell appended
    and NO other edit: what a ``model_config`` PR does at the least."""
    root = tmp_path_factory.mktemp("tenth")
    doc = live()
    doc["configs"].append({
        **doc["configs"][0], "name": "ep-share-d9",
        "file": "benchmarks/chip/configs/ep-share-d9/config.json"})
    doc["workloads"].append({
        "name": "ep-share-d9.longdoc-saturated", "config": "ep-share-d9",
        "traffic": "longdoc-saturated", "chips": 1, "why": "a tenth cell"})
    json.dump(doc, open(root / "BENCHMARK.json", "w"))
    return Manifest(str(root)), doc


def test_a_tenth_cell_appended_reports_none_of_the_three(with_a_tenth_cell):
    manifest, doc = with_a_tenth_cell
    assert grown_from(doc, live()) == []
    reported = [m["name"] for m in manifest.metrics_of(
        "ep-share-d9.longdoc-saturated", "per_layer")]
    assert not set(THE_THREE) & set(reported)
    # What lists no cells it reports, the share by a name among them; what
    # the nine report is what they reported.
    assert {"attn_share_pct", "device_idle_pct", "hbm_peak_gb",
            "decode_rows_per_step", "gen_late_p99_ms"} <= set(reported)
    assert reported == [m["name"] for m in doc["per_layer"]
                        if "workloads" not in m]
    for cell in NINE_CELLS:
        assert manifest.metrics_of(cell, "per_layer") == \
            Manifest(REPO).metrics_of(cell, "per_layer")


def test_a_model_config_pr_may_list_its_cell_where_the_count_fits(
        with_a_tenth_cell):
    """Appending the cell's name to the three lists is growing, and the
    manifest stays valid (the files of the made-up cell aside)."""
    _, doc = with_a_tenth_cell
    doc = json.loads(json.dumps(doc))
    for metric in doc["per_layer"]:
        if metric["name"] in THE_THREE:
            metric["workloads"].append("ep-share-d9.longdoc-saturated")
    assert grown_from(doc, live()) == []
    assert validate(doc, REPO) == [
        "ep-share-d9: no file benchmarks/chip/configs/ep-share-d9/"
        "config.json"]


# ------------------- (c) the reduction computes what an entry will report
def _answered(prompt_tokens, output_tokens=128):
    """A good answer, as the client records it."""
    request = Request(index=0, due_s=0.0, tenant=0, session="s",
                      prompt_tokens=prompt_tokens,
                      output_tokens=output_tokens, messages=())
    return Result(request=request, due=0.0, sent=0.0, first=0.1, last=1.0,
                  status=200, done=True, finish_reason="length",
                  usage={"prompt_tokens": prompt_tokens,
                         "completion_tokens": output_tokens,
                         "total_tokens": prompt_tokens + output_tokens})


@pytest.fixture(scope="module")
def capture():
    """``data/decode_window.xplane.pb`` (a TPU v5e, PR 23: one prefill and
    one 8-step decode of ``qwen2.5-3b``) and what PR 51's PARENT read from
    it, every digit (``data/decode_window.kernel_layer.json``)."""
    want = json.load(open(os.path.join(DATA, "decode_window.expected.json")))
    was = json.load(open(os.path.join(
        DATA, "decode_window.kernel_layer.json")))
    info = {"dirs": [DATA], "seconds": want["window_s"],
            "counters": want["counters"]}
    return info, want, was


def test_the_listed_cells_read_bit_for_bit_what_the_parent_read(capture):
    info, want, was = capture
    cfg = Manifest(REPO).model_config(want["config"])
    result = [_answered(want["prompt_tokens"])]
    for wanted in (None, {"decode_roofline", "prefill_mfu", "attn_share",
                          "decode_step_s", "idle_share"}):
        out = roofline.reduce(info, cfg, PEAK, result, want["counters"],
                              wanted=wanted)
        assert out == was["reduced"]
    assert set(was["reduced"]) >= {"decode_roofline", "prefill_mfu",
                                   "decode_step_s", "attn_share",
                                   "idle_share", "busy_s", "breakdown"}


@pytest.mark.parametrize("wanted,absent", [
    ({"attn_share", "decode_step_s", "idle_share"},
     {"decode_roofline", "prefill_mfu"}),
    ({"attn_share", "idle_share", "prefill_mfu"}, {"decode_roofline"}),
    (set(), {"decode_roofline", "prefill_mfu"})])
def test_a_share_nobody_asked_for_is_not_worked_out(capture, wanted, absent):
    info, want, was = capture
    cfg = Manifest(REPO).model_config(want["config"])
    out = roofline.reduce(info, cfg, PEAK, [_answered(want["prompt_tokens"])],
                          want["counters"], wanted=wanted)
    assert not absent & set(out)
    rest = {k: v for k, v in was["reduced"].items()
            if k not in absent and k != "notes"}
    assert {k: v for k, v in out.items() if k != "notes"} == rest
    # a note is the share's own: none of a share that was not worked out
    assert len(out["notes"]) == 2 - len(absent)


@pytest.mark.parametrize("cfg", [
    {"num_hidden_layers": 36, "model_type": "no_dense_keys"},
    {"num_hidden_layers": 36, "hidden_size": 4096, "vocab_size": 19072,
     "num_attention_heads": 64, "num_key_value_heads": [4, 8, 8, 8] * 9,
     "moe_intermediate_size": 2048}])
def test_a_config_without_a_dense_decoders_keys_raises_nothing_unasked(
        capture, cfg):
    """No ``intermediate_size``, a list for ``num_key_value_heads``: idle,
    busy, the breakdown, the share by a name and the per-step time need no
    count and are there; asked for a share, the count says what it lacks."""
    info, want, was = capture
    result = [_answered(want["prompt_tokens"])]
    out = roofline.reduce(info, cfg, PEAK, result, want["counters"],
                          wanted={"attn_share", "idle_share",
                                  "decode_step_s"})
    for key in ("busy_s", "window_s", "idle_share", "attn_share",
                "decode_step_s", "breakdown"):
        assert out[key] == was["reduced"][key], key
    assert not {"decode_roofline", "prefill_mfu"} & set(out)
    with pytest.raises((KeyError, TypeError)):
        roofline.reduce(info, cfg, PEAK, result, want["counters"])


# ------------------------------------ run.py hands over what the cell lists
class _Run:
    """What ``run.py:reduce_metrics`` reads of a ``CellRun``."""

    rehearse, setup_s, bytes_in_use = False, 1.0, 0
    device = {"kind": "TPU v5 lite"}

    def __init__(self, manifest, cell, model_config):
        self.manifest, self.model_config = manifest, model_config
        self.cell = manifest.cell(cell)
        self.spec = manifest.traffic(self.cell["traffic"])


def _window(capture):
    """The window of ``lib/cell.py:CellRun.window``, made of the capture:
    its counters with the one more series a reader indexes."""
    info, want, _ = capture
    counters = dict(want["counters"],
                    **{"vllm:time_to_first_token_seconds_sum": 0.05})
    return {"results": [_answered(want["prompt_tokens"])], "t0": 0.0,
            "window_s": want["window_s"], "span_s": want["window_s"],
            "counters": counters, "polls": [], "trace_info": info}


def test_a_dense_cells_traced_line_carries_all_four_as_before(capture):
    _, want, was = capture
    manifest = Manifest(REPO)
    run = _Run(manifest, "qwen2.5-3b.chat-saturated",
               manifest.model_config(want["config"]))
    metrics, trace = bench_run.reduce_metrics(run, _window(capture), True)
    # (the other readers say what they found behind the reduction's notes)
    assert trace["notes"][:2] == was["reduced"]["notes"]
    assert dict(trace, notes=None) == dict(was["reduced"], notes=None)
    for name, field, scale in (
            ("decode_step_ms", "decode_step_s", 1e3),
            ("decode_roofline_pct", "decode_roofline", 100.0),
            ("prefill_mfu_pct", "prefill_mfu", 100.0),
            ("attn_share_pct", "attn_share", 100.0),
            ("device_idle_pct", "idle_share", 100.0)):
        assert metrics[name]["value"] == scale * was["reduced"][field], name


@pytest.mark.parametrize("cell,step", [
    ("trinity-mini-d8.longdoc-saturated", True),
    ("granite-4.0-h-micro.chat-saturated", False)])
def test_a_sparse_or_hybrid_cells_line_carries_no_dense_share(
        capture, cell, step):
    """On a ``config.json`` cut to what no dense count could read: the
    cell never asked, so nothing raises; the per-step time where the cell
    lists it, the share by a name and the idle share in both."""
    _, _, was = capture
    manifest = Manifest(REPO)
    cfg = manifest.model_config(manifest.cell(cell)["config"])
    cfg = {k: v for k, v in cfg.items()
           if k not in ("intermediate_size", "num_key_value_heads")}
    metrics, trace = bench_run.reduce_metrics(
        _Run(manifest, cell, cfg), _window(capture), True)
    assert not {"decode_roofline_pct", "prefill_mfu_pct"} & set(metrics)
    assert not {"decode_roofline", "prefill_mfu"} & set(trace)
    assert ("decode_step_ms" in metrics) == step
    assert metrics["attn_share_pct"]["value"] == \
        100.0 * was["reduced"]["attn_share"]
    assert metrics["device_idle_pct"]["value"] == \
        100.0 * was["reduced"]["idle_share"]
