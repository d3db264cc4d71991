"""What PR 52 appended to ``BENCHMARK.json`` (a configuration, a cell, six
per-layer metrics of the window ring and of the experts held, and the cell's
name in the lists that name every cell), pinned to the INDICES the entries
have and to no end of a list, so that the next appending PR needs no mark
(``tests/chip_bench/test_bench_afmoe.py`` did the same for PR 47). The reader
of five of the six on hand-built contexts: nothing without a capture, for a
model of another family, or on a capture recorded before the scopes existed;
its arithmetic on a made-up capture. The live manifest may only have grown
from the ONE recorded copy (``data/manifest.recorded.json``, PR 51)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import shapes, shapes_mimo  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import counter_ratio, mimo_trace  # noqa: E402
from test_bench_ssm import EVERY_CELL, HBM_METRICS  # noqa: E402

CELL = "mimo-v2.5-ep16.longctx-decode"
CONFIG = "mimo-v2.5-ep16"
TRACE_METRICS = ("mimo_decode_roofline_pct", "mimo_gmm_roofline_pct",
                 "mimo_moe_share_pct", "ring_attn_roofline_pct",
                 "ring_attn_share_pct")
MIMO_METRICS = TRACE_METRICS + ("ring_keys_held_pct",)
LOWER = ("mimo_moe_share_pct", "ring_attn_share_pct", "ring_keys_held_pct")
# Where this PR's entries stand (and will, whatever is appended after).
CONFIG_AT, CELL_AT, MIMO_AT = 8, 9, 69
# The cell's place in the lists that name it.
NAMED_AT = {"out_tok_s": 7, "moe_experts_touched": 4}
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]
# The dense arithmetic's three (PR 51) and every other architecture's.
NOT_OURS = ("decode_roofline_pct", "prefill_mfu_pct", "decode_step_ms",
            "hyb_decode_roofline_pct", "gdn_share_pct",
            "moe_decode_roofline_pct", "moe_gmm_roofline_pct",
            "mla_decode_roofline_pct", "moe_share_pct",
            "hc_decode_roofline_pct", "ssm_decode_roofline_pct",
            "ssd_share_pct", "lfm_decode_roofline_pct", "lfm_moe_share_pct",
            "sconv_share_pct", "afm_decode_roofline_pct",
            "span_decode_attn_roofline_pct", "afm_moe_share_pct",
            "span_keys_read_pct")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.fixture(scope="module")
def by_name(doc):
    return {m["name"]: m for m in doc["per_layer"]}


@pytest.fixture(scope="module")
def mimo():
    return Manifest(REPO).model_config(CONFIG)


# ------------------------------------------------------ this PR's, by index
def test_the_configuration_and_the_cell_by_index(doc):
    assert validate(doc, REPO) == []
    assert doc["configs"][CONFIG_AT] == {
        "name": CONFIG,
        "source": "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/"
                  "config.json",
        "file": f"benchmarks/chip/configs/{CONFIG}/config.json",
        "reduced": REDUCED, "why": doc["configs"][CONFIG_AT]["why"]}
    assert len(doc["configs"][CONFIG_AT]["why"]) <= 200
    cell = doc["workloads"][CELL_AT]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "longctx-decode", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "a sixteenth" in cell["why"]


@pytest.mark.parametrize("i,name", list(enumerate(MIMO_METRICS)))
def test_the_six_metrics_by_index(doc, i, name):
    assert doc["per_layer"][MIMO_AT + i] == {
        "name": name, "unit": "%",
        "better": "lower" if name in LOWER else "higher",
        "source": "program_counter" if name == "ring_keys_held_pct"
        else "device_trace",
        "layer": "model and attention kernels", "moves": "tpot_p50_ms",
        "workloads": [CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + tuple(NAMED_AT))
def test_the_cell_is_named_where_it_stands(by_name, name):
    cells = by_name[name]["workloads"]
    assert cells.index(CELL) == NAMED_AT.get(name, 9)
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
    assert set(MIMO_METRICS + EVERY_CELL) | {
        "attn_share_pct", "hbm_peak_gb", "device_idle_pct",
        "kv_usage_peak_pct", "prefix_hit_pct"} <= reported
    # ``tests/chip_bench/test_bench_memory.py`` holds the three ``hbm_*``
    # lists to the nine cells they had (PR 49's file, which this PR may not
    # edit): the cell is not named there, and ``hbm_peak_gb`` reads it.
    assert not set(HBM_METRICS) & reported


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    assert list(deployment["reduced"]) == REDUCED
    assert deployment["depth"] == 12
    assert "shared by 16 chips" in deployment["stands_for"]
    assert "about four times a deployment's" in deployment["stands_for"]
    assert deployment["deployment"]["ep_size"] == 16
    assert deployment["source"] == manifest.configs[CONFIG]["source"]
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert flags == {"--max-model-len": "10240", "--max-num-seqs": "32",
                     "--max-num-batched-tokens": "2048",
                     "--attn-impl": "paged", "--num-kv-blocks": "10240",
                     "--num-decode-steps": "16"}
    assert all(f["why"] for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "mimo_v2"
    for name in ("source of the equations", "leaf names", "the sink",
                 "the value scale", "rope", "the bound", "no QK norm",
                 "router", "float32", "the ring's layout", "the paged row",
                 "initialisation"):
        assert name in deployment["assumed"], name
    for name in ("reference.py", "check_reference.py"):
        assert os.path.exists(os.path.join(manifest.model_dir(CONFIG), name))


def test_the_traffic_is_issue_52s():
    mix = Manifest(REPO).traffic("longctx-decode")
    assert (mix["loop"], mix["users"]) == ("closed", 24)
    assert mix["system"] == {"tokens": 64, "tenants": 1}
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 0.5, "min": 2048, "max": 8192}
    assert mix["output"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.5, "min": 128, "max": 1024}
    assert mix["limits"] == {"ttft_ms": None, "tpot_ms": None}
    # Every context is many windows long, and the longest fits the envelope.
    cfg = Manifest(REPO).model_config(CONFIG)
    assert mix["prompt"]["min"] >= 16 * cfg["sliding_window"]
    assert mix["prompt"]["max"] + mix["system"]["tokens"] \
        + mix["output"]["max"] <= 10240


def test_config_json_holds_the_catalogs_numbers():
    """Every key of the catalog's row under its name and with its value,
    but the five ``reduced`` lists; beside them the deployment's share and
    the published counts."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5"][0]
    cfg = Manifest(REPO).model_config(CONFIG)
    assert sorted(k for k, v in row["config"].items() if cfg.get(k) != v) \
        == sorted(REDUCED)
    assert set(cfg) - set(row["config"]) == {"ep_size", "ep_rank",
                                             "published"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["ep_size"], cfg["ep_rank"]) == \
        (12, 16, 19072, 16, 0)
    assert cfg["hybrid_layer_pattern"] == \
        row["config"]["hybrid_layer_pattern"][:12]
    assert cfg["moe_layer_freq"] == row["config"]["moe_layer_freq"][:12]
    assert row["source_url"] == Manifest(REPO).configs[CONFIG]["source"]


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=(), counters=None, results=()):
    return {"model_config": cfg, "trace": {"notes": []},
            "trace_info": {"dirs": list(dirs), "counters": counters or {}},
            "results": list(results)}


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_each_trace_metric_is_read_by_the_one_reader(name):
    fn, args = Manifest(REPO).reader(name)
    assert fn is mimo_trace.read and set(args) == {"field"}


def test_the_share_of_keys_held_is_the_two_counters():
    fn, args = Manifest(REPO).reader("ring_keys_held_pct")
    assert fn is counter_ratio.read
    ctx = {"counters": {"pstpu:ring_keys_held_total": 128.0 * 9,
                        "pstpu:ring_keys_context_total": 4900.0 * 9}}
    assert fn(ctx, **args) == pytest.approx(100 * 128 / 4900)
    # A program without the counters (the parent), or one that delivered
    # no decode row-step: nothing, and nothing raises.
    assert fn({"counters": {}}, **args) is None
    assert fn({"counters": {"pstpu:ring_keys_held_total": 0.0,
                            "pstpu:ring_keys_context_total": 0.0}},
              **args) is None


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_nothing_without_a_capture_or_for_another_family(name, mimo):
    fn, args = Manifest(REPO).reader(name)
    assert fn(_ctx(mimo), **args) is None
    assert fn(_ctx(mimo, ["/no/such/dir"]), **args) is None
    recorded_dir = os.path.join(os.path.dirname(__file__), "data",
                                "loop_spans")
    for other in ("trinity-mini-d8", "kanana-2-30b-a3b-d8", "qwen2.5-3b"):
        cfg = Manifest(REPO).model_config(other)
        assert fn(_ctx(cfg, [recorded_dir]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share(mimo):
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    none of this family's scopes or counters: the shares are left out, and
    nothing raises."""
    from benchmarks.chip.lib import xplane

    recorded_dir = os.path.join(os.path.dirname(__file__), "data",
                                "loop_spans")
    ctx = _ctx(mimo, [recorded_dir])
    for name in TRACE_METRICS:
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert not [n for n in ctx["trace"]["notes"] if "not read" in n]
    inner = mimo_trace.scope_seconds(xplane.find(recorded_dir))
    assert inner["moe"] == inner["ring"] == 0 and inner["busy_s"] > 0


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path, mimo):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(mimo, [str(tmp_path)])
    assert mimo_trace.read(ctx, "moe_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "mimo_trace: capture not read (ValueError: truncated)"]


def _made_up(monkeypatch, seconds, paths):
    from benchmarks.chip.lib import spans

    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: {
        "ops": [], "spans": [], "programs": {}})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)


PATHS = {
    "a": "jit(_decode_impl)/while/body/ffn/moe_experts/moe_gmm/call",
    "b": "jit(_prefill_impl)/while/body/ffn/moe_experts/moe_gmm/call",
    "c": "jit(_decode_impl)/while/body/ffn/moe_route/top_k",
    "d": "jit(_decode_impl)/while/body/cond/branch_1_fun/attn_core/"
         "ring_attend/dot_general",
    "e": "jit(_decode_impl)/while/body/attn_core/ring_write/select_n",
    "f": "jit(_prefill_impl)/while/body/cond/branch_1_fun/attn_core/"
         "ring_attend/attn_sink/exp",
    "g": "jit(_decode_impl)/while/body/ffn/dot_general",
    "h": None,
}
SECONDS = dict(zip("abcdefgh", (1.0, 0.5, 0.25, 0.125, 0.0625, 0.03125, 2.0,
                                4.0)))


def test_scope_seconds_sorts_the_experts_and_the_ring_from_the_rest(
        monkeypatch):
    _made_up(monkeypatch, SECONDS, PATHS)
    assert mimo_trace.scope_seconds("x") == {
        "moe": 1.75, "gmm_decode": 1.0, "ring": 0.21875,
        "ring_decode": 0.1875, "busy_s": sum(SECONDS.values())}


class _Request:
    prompt_tokens, output_tokens = 4600, 600


class _Result:
    ok, request = True, _Request


def test_the_arithmetic_on_a_made_up_capture(monkeypatch, mimo):
    """100 decode steps (300 paged-kernel calls over the 3 FULL layers) in
    1.2 s of the decode program; of 21 row-steps a step 1 wasted; 7.5 of 16
    held experts a sparse-layer call; the grouped matmuls 1.0 s and the
    ring 0.1875 s of decode; busy 7.97 s."""
    from benchmarks.chip.lib import xplane
    from benchmarks.chip.readers import hybrid_trace

    _made_up(monkeypatch, SECONDS, PATHS)
    monkeypatch.setattr(xplane, "find", lambda d: "x.pb")
    monkeypatch.setattr(xplane, "reduce", lambda path: {
        "devices": 1, "busy_s": 7.97, "window_s": 8.0,
        "programs": {"jit__decode_impl": 1.2},
        "ops": {"paged_flash_decode.1": 0.1},
        "counts": {"paged_flash_decode.1": 300}})
    monkeypatch.setattr(hybrid_trace, "_peak", lambda: PEAK)
    monkeypatch.setattr(mimo_trace, "_peak", lambda: PEAK)
    counters = {"pstpu:decode_steps_total": 50.0,
                "pstpu:decode_row_steps_total": 1050.0,
                "pstpu:decode_row_steps_wasted_total": 50.0,
                "pstpu:moe_layer_calls_total": 550.0,
                "pstpu:moe_experts_touched_total": 4125.0}
    ctx = _ctx(mimo, ["d"], counters, [_Result()])
    got = {f: mimo_trace.read(ctx, f) for f in (
        "decode_roofline_pct", "gmm_roofline_pct", "moe_share_pct",
        "ring_attn_roofline_pct", "ring_attn_share_pct")}
    total = sum(SECONDS.values())
    assert got["moe_share_pct"] == pytest.approx(100 * 1.75 / total)
    assert got["ring_attn_share_pct"] == pytest.approx(
        100 * 0.21875 / total)
    steps, rows, context, touched = 100, 20.0, 4900.0, 7.5
    least = shapes.least_seconds

    def share(work, seconds):
        return 100 * least(work, PEAK)["seconds"] / seconds

    assert got["decode_roofline_pct"] == pytest.approx(steps * share(
        shapes_mimo.decode_step(mimo, rows, context, touched), 1.2))
    assert got["ring_attn_roofline_pct"] == pytest.approx(share(
        shapes_mimo.ring_attend(mimo, steps * rows, context), 0.1875))
    calls = steps * 11
    assert got["gmm_roofline_pct"] == pytest.approx(share(
        shapes_mimo.moe_gmm(mimo, calls, calls * rows * 8 / 16, touched),
        1.0))
    assert all(0 < v < 100 for v in got.values())
    assert "7.5 of 16 held experts" in ctx["trace"]["notes"][-1]
