"""What PR 58 appended to ``BENCHMARK.json`` (a configuration, a cell, nine
per-layer metrics of the learned selection, of the ring of latent rows and
of the experts held, and the cell's name in the lists that name every cell),
pinned to the INDICES the entries have and to no end of a list, so that the
next appending PR needs no mark (``tests/chip_bench/test_bench_mimo.py`` did
the same for PR 52). The reader of seven of the nine on hand-built contexts:
nothing without a capture, for a model of another family, or on a capture
recorded before the scopes existed; its arithmetic on a made-up capture. The
live manifest may only have grown from the ONE recorded copy
(``data/manifest.recorded.json``, PR 51)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import shapes, shapes_dots  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import counter_ratio, dots_trace  # noqa: E402
from test_bench_ssm import EVERY_CELL, HBM_METRICS  # noqa: E402

CELL = "dots3-note-prev-ep16.longdoc-16k"
CONFIG = "dots3-note-prev-ep16"
TRACE_METRICS = ("dots_decode_roofline_pct", "dots_gmm_roofline_pct",
                 "dots_moe_share_pct", "dsa_index_roofline_pct",
                 "dsa_index_share_pct", "dsa_attn_roofline_pct",
                 "latent_ring_attn_roofline_pct")
COUNTER_METRICS = ("dsa_keys_read_pct", "latent_ring_keys_held_pct")
DOTS_METRICS = TRACE_METRICS + COUNTER_METRICS
LOWER = ("dots_moe_share_pct", "dsa_index_share_pct") + COUNTER_METRICS
# Where this PR's entries stand (and will, whatever is appended after).
CONFIG_AT, CELL_AT, DOTS_AT = 10, 11, 81
# The cell's place in the lists that name it.
NAMED_AT = {"out_tok_s": 9, "moe_experts_touched": 5}
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
# Every other architecture's arithmetic, mimo's ring metrics among them.
NOT_OURS = ("decode_roofline_pct", "prefill_mfu_pct", "decode_step_ms",
            "hyb_decode_roofline_pct", "gdn_share_pct",
            "moe_decode_roofline_pct", "moe_gmm_roofline_pct",
            "mla_decode_roofline_pct", "moe_share_pct",
            "hc_decode_roofline_pct", "ssm_decode_roofline_pct",
            "ssd_share_pct", "lfm_decode_roofline_pct", "lfm_moe_share_pct",
            "sconv_share_pct", "afm_decode_roofline_pct",
            "span_decode_attn_roofline_pct", "afm_moe_share_pct",
            "span_keys_read_pct", "mimo_decode_roofline_pct",
            "mimo_gmm_roofline_pct", "mimo_moe_share_pct",
            "ring_attn_roofline_pct", "ring_attn_share_pct",
            "ring_keys_held_pct", "sambay_decode_roofline_pct",
            "sambay_ring_keys_held_pct")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.fixture(scope="module")
def by_name(doc):
    return {m["name"]: m for m in doc["per_layer"]}


@pytest.fixture(scope="module")
def dots():
    return Manifest(REPO).model_config(CONFIG)


# ------------------------------------------------------ this PR's, by index
def test_the_configuration_and_the_cell_by_index(doc):
    assert validate(doc, REPO) == []
    assert doc["configs"][CONFIG_AT] == {
        "name": CONFIG,
        "source": "https://huggingface.co/dots-studio/dots3-note-prev/blob/"
                  "main/config.json",
        "file": f"benchmarks/chip/configs/{CONFIG}/config.json",
        "reduced": REDUCED, "why": doc["configs"][CONFIG_AT]["why"]}
    assert len(doc["configs"][CONFIG_AT]["why"]) <= 200
    cell = doc["workloads"][CELL_AT]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "longdoc-16k", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "index_topk" in cell["why"]
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 0


@pytest.mark.parametrize("i,name", list(enumerate(DOTS_METRICS)))
def test_the_nine_metrics_by_index(doc, i, name):
    assert doc["per_layer"][DOTS_AT + i] == {
        "name": name, "unit": "%",
        "better": "lower" if name in LOWER else "higher",
        "source": "program_counter" if name in COUNTER_METRICS
        else "device_trace",
        "layer": "model and attention kernels", "moves": "tpot_p50_ms",
        "workloads": [CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + tuple(NAMED_AT))
def test_the_cell_is_named_where_it_stands(by_name, name):
    cells = by_name[name]["workloads"]
    assert cells.index(CELL) == NAMED_AT.get(name, 11)
    assert cells.count(CELL) == 1


@pytest.mark.parametrize("name", NOT_OURS)
def test_another_models_arithmetic_is_not_this_cells(by_name, name):
    assert CELL not in by_name[name]["workloads"]


def test_the_manifest_only_grew_and_the_cell_reports_three_end_to_end(doc):
    assert grown_from(doc, recorded()) == []
    manifest = Manifest(REPO)
    assert {m["name"] for m in manifest.metrics_of(CELL, "end_to_end")} == \
        {"req_p50_ms", "tpot_p50_ms", "setup_s"}
    reported = {m["name"] for m in manifest.metrics_of(CELL, "per_layer")}
    assert set(DOTS_METRICS + EVERY_CELL) | {
        "attn_share_pct", "hbm_peak_gb", "device_idle_pct",
        "kv_usage_peak_pct", "prefix_hit_pct", "out_tok_s",
        "moe_experts_touched"} <= reported
    # ``tests/chip_bench/test_bench_memory.py`` holds the three ``hbm_*``
    # lists to the nine cells they had (PR 49's file, which this PR may not
    # edit): the cell is not named there though ISSUE 58 asked for it, and
    # ``hbm_peak_gb`` reads it.
    assert not set(HBM_METRICS) & reported


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    assert list(deployment["reduced"]) == REDUCED
    assert deployment["depth"] == 10
    assert "shared by 16 chips" in deployment["stands_for"]
    assert "4 : 6 here against the published 13 : 33" in \
        deployment["stands_for"]
    assert "about 4.6 times a deployment's" in deployment["stands_for"]
    assert "are not served" in deployment["stands_for"]
    assert deployment["deployment"]["ep_size"] == 16
    assert deployment["source"] == manifest.configs[CONFIG]["source"]
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert flags == {"--max-model-len": "17408", "--max-num-seqs": "16",
                     "--max-num-batched-tokens": "2048",
                     "--max-prefill-seqs": "1",
                     "--attn-impl": "paged", "--num-kv-blocks": "20480",
                     "--num-decode-steps": "16"}
    assert all(f["why"] for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "dots3_note"
    for name in ("source of the equations", "leaf names",
                 "the rescale (apply_mla_qkv_lora_rescale)", "the gate",
                 "the bound", "the indexer", "rope", "router", "float32",
                 "the paged row", "the ring's layout", "initialisation"):
        assert name in deployment["assumed"], name
    for name in ("reference.py", "check_reference.py"):
        assert os.path.exists(os.path.join(manifest.model_dir(CONFIG), name))


def test_the_traffic_is_issue_58s():
    mix = Manifest(REPO).traffic("longdoc-16k")
    assert (mix["loop"], mix["users"], mix["rounds_max"]) == \
        ("closed", 12, 24)
    assert mix["system"] == {"tokens": 64, "tenants": 1}
    assert mix["prompt"] == {"dist": "lognormal", "median": 8192,
                             "sigma": 0.5, "min": 4096, "max": 16384}
    assert mix["output"] == {"dist": "lognormal", "median": 192,
                             "sigma": 0.5, "min": 32, "max": 512}
    assert (mix["preload"], mix["warm_requests"]) == ("none", 4)
    assert mix["limits"] == {"ttft_ms": None, "tpot_ms": None}
    # Every context is 2 to 8 selections and 8 to 32 windows long, and the
    # longest fits the envelope.
    cfg = Manifest(REPO).model_config(CONFIG)
    assert mix["prompt"]["min"] == 2 * cfg["index_topk"]
    assert mix["prompt"]["max"] == 8 * cfg["index_topk"]
    assert mix["prompt"]["min"] >= 7 * cfg["sliding_window_size"]
    assert mix["prompt"]["max"] + mix["system"]["tokens"] \
        + mix["output"]["max"] <= 17408


def test_config_json_holds_the_catalogs_numbers():
    """Every key of the catalog's row under its name and with its value,
    but the four ``reduced`` lists; beside them the deployment's share and
    the published counts."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "dots3-note-prev"][0]
    cfg = Manifest(REPO).model_config(CONFIG)
    assert sorted(k for k, v in row["config"].items() if cfg.get(k) != v) \
        == sorted(REDUCED)
    assert set(cfg) - set(row["config"]) == {"ep_size", "ep_rank",
                                             "published"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["ep_size"], cfg["ep_rank"]) == \
        (10, 16, 19008, 16, 0)
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    assert row["source_url"] == Manifest(REPO).configs[CONFIG]["source"]


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=(), counters=None, results=()):
    return {"model_config": cfg, "trace": {"notes": []},
            "trace_info": {"dirs": list(dirs), "counters": counters or {}},
            "results": list(results)}


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_each_trace_metric_is_read_by_the_one_reader(name):
    fn, args = Manifest(REPO).reader(name)
    assert fn is dots_trace.read and set(args) == {"field"}


@pytest.mark.parametrize("name,num,den,value", [
    ("dsa_keys_read_pct", "pstpu:index_keys_selected_total",
     "pstpu:index_keys_visible_total", 100 * 2048 / 9000),
    ("latent_ring_keys_held_pct", "pstpu:ring_keys_held_total",
     "pstpu:ring_keys_context_total", 100 * 513 / 9000)])
def test_the_shares_of_keys_are_two_counters_each(name, num, den, value):
    fn, args = Manifest(REPO).reader(name)
    assert fn is counter_ratio.read
    ctx = {"counters": {num: value * 90.0 * 4, den: 9000.0 * 4}}
    assert fn(ctx, **args) == pytest.approx(value)
    # A program without the counters (the parent), or one that delivered
    # no decode row-step: nothing, and nothing raises.
    assert fn({"counters": {}}, **args) is None
    assert fn({"counters": {num: 0.0, den: 0.0}}, **args) is None


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_nothing_without_a_capture_or_for_another_family(name, dots):
    fn, args = Manifest(REPO).reader(name)
    assert fn(_ctx(dots), **args) is None
    assert fn(_ctx(dots, ["/no/such/dir"]), **args) is None
    recorded_dir = os.path.join(os.path.dirname(__file__), "data",
                                "loop_spans")
    for other in ("mimo-v2.5-ep16", "kanana-2-30b-a3b-d8", "qwen2.5-3b"):
        cfg = Manifest(REPO).model_config(other)
        assert fn(_ctx(cfg, [recorded_dir]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share(dots):
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    none of this family's scopes or counters: the shares are left out, and
    nothing raises."""
    from benchmarks.chip.lib import xplane

    recorded_dir = os.path.join(os.path.dirname(__file__), "data",
                                "loop_spans")
    ctx = _ctx(dots, [recorded_dir])
    for name in TRACE_METRICS:
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert not [n for n in ctx["trace"]["notes"] if "not read" in n]
    inner = dots_trace.scope_seconds(xplane.find(recorded_dir))
    assert inner["moe"] == inner["index"] == inner["ring_decode"] == 0 \
        and inner["busy_s"] > 0


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path, dots):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(dots, [str(tmp_path)])
    assert dots_trace.read(ctx, "moe_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "dots_trace: capture not read (ValueError: truncated)"]


def _made_up(monkeypatch, seconds, paths):
    from benchmarks.chip.lib import spans

    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: {
        "ops": [], "spans": [], "programs": {}})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)


BODY = "jit(_decode_impl)/while/body/closed_call/while/body/closed_call/"
PATHS = {
    "a": BODY + "ffn/moe_experts/moe_gmm/call",
    "b": "jit(_prefill_impl)/while/body/ffn/moe_experts/moe_gmm/call",
    "c": BODY + "ffn/moe_route/top_k",
    "d": BODY + "cond/branch_1_fun/attn_core/ring_attend/dot_general",
    "e": BODY + "attn_core/ring_write/select_n",
    "f": BODY + "cond/branch_0_fun/attn_core/attn_index/top_k",
    "g": BODY + "cond/branch_0_fun/attn_proj/attn_index/dot_general",
    "h": BODY + "cond/branch_0_fun/attn_core/attn_select/gather",
    "i": "jit(_prefill_impl)/while/body/cond/branch_0_fun/attn_core/"
         "attn_index/while/body/reduce_sum",
    "j": "jit(_prefill_impl)/while/body/cond/branch_0_fun/attn_core/"
         "attn_select/while/body/dot_general",
    "k": BODY + "ffn/dot_general",
    "l": None,
}
SECONDS = dict(zip("abcdefghijkl", (1.0, 0.5, 0.25, 0.125, 0.0625, 0.2, 0.05,
                                    0.4, 0.6, 1.5, 2.0, 4.0)))


def test_scope_seconds_sorts_the_indexer_the_selection_and_the_ring(
        monkeypatch):
    _made_up(monkeypatch, SECONDS, PATHS)
    assert dots_trace.scope_seconds("x") == {
        "moe": 1.75, "gmm_decode": 1.0, "index": pytest.approx(0.85),
        "index_decode": 0.25, "select_decode": 0.4, "ring_decode": 0.1875,
        "busy_s": sum(SECONDS.values())}


class _Request:
    prompt_tokens, output_tokens = 8900, 200


class _Result:
    ok, request = True, _Request


def test_the_arithmetic_on_a_made_up_capture(monkeypatch, dots):
    """100 decode steps (the program's own count) in 3.0 s of the decode
    program; of 11 row-steps a step 1 wasted; 4.5 of 16 held experts a
    sparse-layer call; of decode the grouped matmuls 1.0 s, the indexer
    0.25 s, the selected rows 0.4 s, the rings 0.1875 s."""
    from benchmarks.chip.lib import xplane
    from benchmarks.chip.readers import hybrid_trace

    _made_up(monkeypatch, SECONDS, PATHS)
    monkeypatch.setattr(xplane, "find", lambda d: "x.pb")
    monkeypatch.setattr(xplane, "reduce", lambda path: {
        "devices": 1, "busy_s": 10.68, "window_s": 11.0,
        "programs": {"jit__decode_impl": 3.0}, "ops": {}, "counts": {}})
    monkeypatch.setattr(hybrid_trace, "_peak", lambda: PEAK)
    monkeypatch.setattr(dots_trace, "_peak", lambda: PEAK)
    counters = {"pstpu:decode_steps_total": 100.0,
                "pstpu:decode_row_steps_total": 1100.0,
                "pstpu:decode_row_steps_wasted_total": 100.0,
                "pstpu:moe_layer_calls_total": 900.0,
                "pstpu:moe_experts_touched_total": 4050.0}
    ctx = _ctx(dots, ["d"], counters, [_Result()])
    got = {f: dots_trace.read(ctx, f) for f in (
        "decode_roofline_pct", "gmm_roofline_pct", "moe_share_pct",
        "index_roofline_pct", "index_share_pct", "attn_roofline_pct",
        "ring_attn_roofline_pct")}
    total = sum(SECONDS.values())
    assert got["moe_share_pct"] == pytest.approx(100 * 1.75 / total)
    assert got["index_share_pct"] == pytest.approx(100 * 0.85 / total)
    steps, rows, context, touched = 100, 10.0, 9000.0, 4.5
    least = shapes.least_seconds

    def share(work, seconds):
        return 100 * least(work, PEAK)["seconds"] / seconds

    assert got["decode_roofline_pct"] == pytest.approx(steps * share(
        shapes_dots.decode_step(dots, rows, context, touched), 3.0))
    assert got["index_roofline_pct"] == pytest.approx(share(
        shapes_dots.index_scan(dots, steps, steps * rows, context), 0.25))
    assert got["attn_roofline_pct"] == pytest.approx(share(
        shapes_dots.selected_attend(dots, steps * rows, context), 0.4))
    assert got["ring_attn_roofline_pct"] == pytest.approx(share(
        shapes_dots.ring_attend(dots, steps * rows, context), 0.1875))
    calls = steps * 9
    assert got["gmm_roofline_pct"] == pytest.approx(share(
        shapes_dots.moe_gmm(dots, calls, calls * rows * 8 / 16, touched),
        1.0))
    assert all(0 < v < 100 for v in got.values())
    assert "4.5 of 16 held experts" in ctx["trace"]["notes"][-1]
