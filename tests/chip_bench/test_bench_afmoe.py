"""What PR 47 appended to ``BENCHMARK.json`` (a configuration, a cell, six
per-layer metrics of a layer's span and of the sparse experts, and the
cell's name in the lists that name every cell), pinned to the INDICES the
entries have and to no end of a list, so that the next appending PR needs no
mark (``tests/chip_bench/test_bench_lfm.py`` did the same for PR 44). The
reader of five of the six on hand-built contexts: nothing without a capture,
for a model of another family, or on a capture recorded before the scopes
and the spans' fields existed; its arithmetic on a made-up capture. What
the cell REPORTS is held as the ONE recorded manifest has it
(``data/manifest.recorded.json``, PR 51), from which the live one may only
have grown."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import shapes, shapes_afmoe  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import afmoe_trace, counter_ratio  # noqa: E402
from test_bench_ssm import EVERY_CELL, HBM_METRICS  # noqa: E402

CELL = "trinity-mini-d8.longdoc-saturated"
CONFIG = "trinity-mini-d8"
TRACE_METRICS = ("afm_decode_roofline_pct", "span_decode_attn_roofline_pct",
                 "span_prefill_attn_roofline_pct", "afm_gmm_roofline_pct",
                 "afm_moe_share_pct")
AFM_METRICS = TRACE_METRICS + ("span_keys_read_pct",)
LOWER = ("afm_moe_share_pct", "span_keys_read_pct")
MOVES_REQ = ("span_prefill_attn_roofline_pct",)
# Where this PR's entries stand (and will, whatever is appended after).
CONFIG_AT, CELL_AT, AFM_AT = 7, 8, 60
# The cell's place in the lists that name it.
NAMED_AT = {"out_tok_s": 6, "moe_experts_touched": 3}
# Metrics of other architectures' arithmetic: never this cell's.
NOT_OURS = ("hyb_decode_step_ms", "hyb_decode_roofline_pct",
            "gdn_step_roofline_pct", "gdn_chunk_roofline_pct",
            "gdn_share_pct", "moe_decode_roofline_pct",
            "moe_gmm_roofline_pct", "mla_decode_roofline_pct",
            "moe_share_pct", "hc_decode_roofline_pct", "hc_mix_roofline_pct",
            "hc_share_pct", "ssm_decode_step_ms", "ssm_decode_roofline_pct",
            "ssd_step_roofline_pct", "ssd_chunk_roofline_pct",
            "ssd_share_pct", "lfm_decode_step_ms", "lfm_decode_roofline_pct",
            "lfm_gmm_roofline_pct", "lfm_moe_share_pct",
            "sconv_step_roofline_pct", "sconv_share_pct")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.fixture(scope="module")
def was():
    return recorded()


@pytest.fixture(scope="module")
def by_name(doc):
    return {m["name"]: m for m in doc["per_layer"]}


# ------------------------------------------------------ this PR's, by index
def test_the_configuration_and_the_cell_by_index(doc):
    assert validate(doc, REPO) == []
    assert doc["configs"][CONFIG_AT] == {
        "name": CONFIG,
        "source": "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/"
                  "config.json",
        "file": f"benchmarks/chip/configs/{CONFIG}/config.json",
        "reduced": ["num_hidden_layers", "layer_types"],
        "why": doc["configs"][CONFIG_AT]["why"]}
    assert len(doc["configs"][CONFIG_AT]["why"]) <= 200
    cell = doc["workloads"][CELL_AT]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "longdoc-saturated", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert "bounded layers still hold all keys" in cell["why"]


@pytest.mark.parametrize("i,name", list(enumerate(AFM_METRICS)))
def test_the_six_metrics_by_index(doc, i, name):
    assert doc["per_layer"][AFM_AT + i] == {
        "name": name, "unit": "%",
        "better": "lower" if name in LOWER else "higher",
        "source": "program_counter" if name == "span_keys_read_pct"
        else "device_trace",
        "layer": "model and attention kernels",
        "moves": "req_p50_ms" if name in MOVES_REQ else "tpot_p50_ms",
        "workloads": [CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + tuple(NAMED_AT))
def test_the_cell_is_named_where_it_stands(by_name, name):
    cells = by_name[name]["workloads"]
    assert cells.index(CELL) == NAMED_AT.get(name, 8)
    assert cells.count(CELL) == 1


@pytest.mark.parametrize("name", NOT_OURS)
def test_another_architectures_arithmetic_is_not_this_cells(by_name, name):
    assert CELL not in by_name[name]["workloads"]


def test_what_the_cell_reports_in_the_record(was):
    listed = {m["name"] for m in was["per_layer"]
              if CELL in m.get("workloads", ())}
    # Every one of the 8 layers calls the paged decode kernel once a step:
    # kernel calls / layers IS a step, so the per-step time names the cell.
    assert listed == set(EVERY_CELL + AFM_METRICS + tuple(NAMED_AT)
                         + HBM_METRICS + ("decode_step_ms",))
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "per_layer")}
    # Those without a list are reported in every cell, this one too.
    assert listed | {"attn_share_pct", "hbm_peak_gb",
                     "device_idle_pct"} <= reported
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


def test_the_manifest_only_grew_from_the_record(doc, was):
    """PR 47's configuration, cell and six metrics stand in the record at
    the ends of what its parent had, and the live manifest holds the
    record as its head."""
    assert grown_from(doc, was) == []
    assert (was["configs"][CONFIG_AT]["name"],
            was["workloads"][CELL_AT]["name"]) == (CONFIG, CELL)
    assert CELL_AT == CONFIG_AT + 1
    assert [m["name"] for m in was["per_layer"][
        AFM_AT:AFM_AT + len(AFM_METRICS)]] == list(AFM_METRICS)
    # Among what the parent had, the lists that named the cell when its PR
    # ended name it where it stood then.
    grew = [then["name"] for then in was["per_layer"][:AFM_AT]
            if CELL in then.get("workloads", ())
            and then["name"] in EVERY_CELL + tuple(NAMED_AT)]
    assert sorted(grew) == sorted(EVERY_CELL + tuple(NAMED_AT))


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    assert set(deployment["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert deployment["depth"] == 8
    assert "first of four pipeline stages" in deployment["stands_for"]
    assert deployment["source"] == manifest.configs[CONFIG]["source"]
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert flags == {"--max-model-len": "13312", "--max-num-seqs": "16",
                     "--max-num-batched-tokens": "2048",
                     "--attn-impl": "paged", "--num-kv-blocks": "8192"}
    assert all(f["why"] for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "afmoe"
    for name in ("source of the equations", "leaf names",
                 "qk norm before rope", "rope", "the bound", "the gate",
                 "sandwich norms", "mup_enabled", "router", "shared expert",
                 "float32", "initialisation"):
        assert name in deployment["assumed"], name
    for name in ("reference.py", "check_reference.py"):
        assert os.path.exists(os.path.join(manifest.model_dir(CONFIG), name))


def test_the_traffic_is_issue_47s():
    mix = Manifest(REPO).traffic("longdoc-saturated")
    assert (mix["loop"], mix["users"], mix["rounds_max"]) == ("closed", 12, 40)
    assert mix["system"] == {"tokens": 64, "tenants": 1}
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 0.5, "min": 2048, "max": 12288}
    assert mix["output"] == Manifest(REPO).traffic("chat-saturated")["output"]
    assert mix["limits"] == {"ttft_ms": None, "tpot_ms": None}
    # Every prompt is past the span, and the longest fits the envelope.
    cfg = Manifest(REPO).model_config(CONFIG)
    assert mix["prompt"]["min"] + mix["system"]["tokens"] \
        > cfg["sliding_window"]
    assert mix["prompt"]["max"] + mix["system"]["tokens"] \
        + mix["output"]["max"] <= 13312


def test_config_json_holds_the_catalogs_numbers():
    """Every number of the published config under its key (the catalog's
    row, copied whole), but the depth and the list cut with it."""
    cfg = Manifest(REPO).model_config(CONFIG)
    want = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.826,
        "sliding_window": 2048, "topk_group": 1, "vocab_size": 200192,
        "num_hidden_layers": 8,
    }
    assert {k: cfg[k] for k in want} == want
    assert cfg["mup_enabled"] and cfg["route_norm"] and cfg["use_grouped_mm"]
    assert not cfg["tie_word_embeddings"] and cfg["rope_scaling"] is None
    assert cfg["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert set(cfg) == set(want) | {
        "model_type", "layer_types", "mup_enabled", "route_norm",
        "use_grouped_mm", "tie_word_embeddings", "rope_scaling",
        "hidden_act", "score_func"}


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=(), counters=None, results=()):
    return {"model_config": cfg, "trace": {"notes": []},
            "trace_info": {"dirs": list(dirs), "counters": counters or {}},
            "results": list(results)}


@pytest.fixture(scope="module")
def afm():
    return Manifest(REPO).model_config(CONFIG)


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_each_trace_metric_is_read_by_the_one_reader(name):
    fn, args = Manifest(REPO).reader(name)
    assert fn is afmoe_trace.read and set(args) == {"field"}


def test_the_share_of_keys_read_is_the_two_counters():
    fn, args = Manifest(REPO).reader("span_keys_read_pct")
    assert fn is counter_ratio.read
    ctx = {"counters": {"pstpu:attn_keys_in_span_total": 58.0,
                        "pstpu:attn_keys_held_total": 100.0}}
    assert fn(ctx, **args) == pytest.approx(58.0)
    # A program without the counters (the parent), or one that held no
    # key: nothing, and nothing raises.
    assert fn({"counters": {}}, **args) is None
    assert fn({"counters": {"pstpu:attn_keys_in_span_total": 0.0,
                            "pstpu:attn_keys_held_total": 0.0}},
              **args) is None


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_nothing_without_a_capture_or_for_another_family(name, afm):
    fn, args = Manifest(REPO).reader(name)
    assert fn(_ctx(afm), **args) is None
    assert fn(_ctx(afm, ["/no/such/dir"]), **args) is None
    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    for other in ("lfm2-8b-a1b-d16", "kanana-2-30b-a3b-d8", "qwen2.5-3b"):
        cfg = Manifest(REPO).model_config(other)
        assert fn(_ctx(cfg, [recorded]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share(afm):
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    none of this family's scopes, spans without ``keys_in_span``: the
    shares are left out, and nothing raises."""
    from benchmarks.chip.lib import spans, xplane

    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    ctx = _ctx(afm, [recorded])
    for name in ("afm_gmm_roofline_pct", "afm_moe_share_pct",
                 "span_prefill_attn_roofline_pct",
                 "afm_decode_roofline_pct"):
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert not [n for n in ctx["trace"]["notes"] if "not read" in n]
    path = xplane.find(recorded)
    inner = afmoe_trace.scope_seconds(path)
    assert inner["moe"] == inner["gmm_decode"] == 0 and inner["busy_s"] > 0
    assert afmoe_trace.prefill_kernel(spans.read_events(path)) is None


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path, afm):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(afm, [str(tmp_path)])
    assert afmoe_trace.read(ctx, "moe_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "afmoe_trace: capture not read (ValueError: truncated)"]


def _made_up(monkeypatch, seconds, paths, events=None):
    from benchmarks.chip.lib import spans

    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: events or {
        "ops": [], "spans": [], "programs": {}})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)


def test_scope_seconds_sorts_the_experts_from_the_rest(monkeypatch):
    paths = {
        "a": "jit(_decode_impl)/while/body/ffn/moe_experts/moe_gmm/call",
        "b": "jit(_prefill_impl)/while/body/ffn/moe_experts/moe_gmm/call",
        "c": "jit(_decode_impl)/while/body/ffn/moe_route/top_k",
        "d": "jit(_decode_impl)/while/body/ffn/moe_shared/dot_general",
        "e": "jit(_decode_impl)/while/body/attn_core/attn_span/call",
        "f": "jit(_decode_impl)/while/body/ffn/dot_general",
        "g": None,
    }
    seconds = dict(zip("abcdefg", (1.0, 0.5, 0.25, 0.125, 0.0625, 2.0, 4.0)))
    _made_up(monkeypatch, seconds, paths)
    assert afmoe_trace.scope_seconds("x") == {
        "moe": 1.875, "gmm_decode": 1.0, "busy_s": sum(seconds.values())}


class _Request:
    prompt_tokens, output_tokens = 4000, 200


class _Result:
    ok, request = True, _Request


@pytest.fixture
def reduced(monkeypatch, afm):
    """A made-up capture: 100 decode steps (800 kernel calls over 8 layers)
    in 1.2 s of the decode program, the decode kernel 0.2 s of it; of 11
    row-steps a step 1 wasted; 60 experts a sparse-layer call; the grouped
    matmuls 0.8 s of decode; two prefill dispatches of which the capture
    holds one's run, its packed kernel 0.05 s; busy 4 s."""
    from benchmarks.chip.lib import xplane

    monkeypatch.setattr(xplane, "find", lambda d: "x.pb")
    monkeypatch.setattr(xplane, "reduce", lambda path: {
        "devices": 1, "programs": {"jit__decode_impl": 1.2},
        "ops": {"paged_flash_decode_stats.3": 0.15,
                "paged_flash_decode_stats.7": 0.05,
                "paged_flash_prefill_packed.2": 0.05},
        "counts": {"paged_flash_decode_stats.3": 600,
                   "paged_flash_decode_stats.7": 200,
                   "paged_flash_prefill_packed.2": 16}})
    issue = {"name": "pstpu.issue", "kind": "prefill", "tokens": 2048,
             "prog_rows": 1, "prog_t": 2048}
    events = {
        "spans": [dict(issue, step=3, start=1.0, end=1.001,
                       keys_in_span=40_000_000, keys_held=90_000_000),
                  dict(issue, step=9, start=3.9, end=3.901,
                       keys_in_span=30_000_000, keys_held=50_000_000)],
        "programs": {"jit__prefill_impl": [(1.01, 1.5)]},
        "ops": [("%paged_flash_prefill_packed.2 = bf16[1] custom-call()",
                 1.1, 1.13),
                ("%paged_flash_prefill_packed.2 = bf16[1] custom-call()",
                 1.3, 1.32),
                # Of a run the capture's start cut: not a paired one's.
                ("%paged_flash_prefill_packed.2 = bf16[1] custom-call()",
                 0.2, 0.3),
                ("%fusion.1 = bf16[1] fusion()", 1.2, 1.25)]}
    _made_up(monkeypatch,
             {"a": 0.8, "b": 0.2, "c": 0.1, "d": 2.9},
             {"a": "jit(_decode_impl)/ffn/moe_experts/moe_gmm/y",
              "b": "jit(_decode_impl)/ffn/moe_route/y",
              "c": "jit(_prefill_impl)/ffn/moe_shared/y",
              "d": "x/attn_proj/y"}, events)
    counters = {"pstpu:decode_steps_total": 50.0,
                "pstpu:decode_row_steps_total": 550.0,
                "pstpu:decode_row_steps_wasted_total": 50.0,
                "pstpu:moe_layer_calls_total": 300.0,
                "pstpu:moe_experts_touched_total": 18000.0}
    ctx = _ctx(afm, ["dir"], counters, [_Result()])
    return afmoe_trace.reduce(ctx), ctx


# 4100 keys of context: 2047 in each of the 6 sliding layers, all in 2 full.
KEYS = 6 * 2047 + 2 * 4100


def test_steps_are_kernel_calls_over_all_eight_layers(reduced):
    out, ctx = reduced
    assert ("100 steps, 10.00 live rows a step, %d keys a row-step over 8 "
            "layers, 60.0 experts a call" % KEYS) in ctx["trace"]["notes"][0]


def test_the_decode_kernel_is_held_to_the_keys_under_each_span(reduced, afm):
    out, _ = reduced
    # K and V, 4 KV heads x 128 lanes, bf16, of the keys a row-step sees.
    byts = 1000 * KEYS * 2 * 512 * 2
    work = shapes_afmoe.decode_attention(afm, 1000, KEYS)
    assert work["bytes"] == byts
    assert shapes.least_seconds(work, PEAK)["bound"] == "memory"
    assert out["decode_attn_roofline_pct"] == pytest.approx(
        100 * byts / 819e9 / 0.2)
    # Read with no bound the same steps would have moved 1.6 times that.
    assert shapes_afmoe.keys_seen(dict(afm, sliding_window=1 << 30), 4100) \
        == 8 * 4100


def test_the_prefill_kernel_is_held_to_the_paired_spans_keys(reduced):
    out, _ = reduced
    # The first span pairs with the one run; the kernel's two calls inside
    # it are 0.05 s; the second span's run lies behind the capture's end.
    flops = 4 * 32 * 128 * 40_000_000
    assert out["prefill_attn_roofline_pct"] == pytest.approx(
        100 * flops / 197e12 / 0.05)
    assert out["prefill_attn_roofline_pct"] < 100


def test_the_whole_step_is_held_to_the_experts_touched(reduced, afm):
    out, _ = reduced
    least = shapes.least_seconds(
        shapes_afmoe.decode_step(afm, 10.0, KEYS, 60.0), PEAK)
    assert least["bound"] == "memory"
    assert out["decode_roofline_pct"] == pytest.approx(
        100 * 100 * least["seconds"] / 1.2)
    assert 30 < out["decode_roofline_pct"] < 100


def test_the_grouped_matmul_by_the_shared_arithmetic(reduced, afm):
    from benchmarks.chip.lib import shapes_lfm

    out, _ = reduced
    least = shapes.least_seconds(shapes_lfm.moe_gmm(
        afm, 600, 600 * 10 * 8, 60.0), PEAK)
    assert out["gmm_roofline_pct"] == pytest.approx(
        100 * least["seconds"] / 0.8)
    assert out["gmm_roofline_pct"] < 100


def test_the_share_is_the_three_scopes_over_busy(reduced):
    out, _ = reduced
    assert out["moe_share_pct"] == pytest.approx(100 * 1.1 / 4.0)


@pytest.mark.parametrize("missing,left", [
    ("pstpu:decode_steps_total",
     {"moe_share_pct", "prefill_attn_roofline_pct"}),
    ("pstpu:moe_layer_calls_total",
     {"moe_share_pct", "prefill_attn_roofline_pct",
      "decode_attn_roofline_pct"}),
])
def test_without_the_counters_only_what_needs_none_is_read(reduced, missing,
                                                           left):
    _, ctx = reduced
    del ctx["trace_info"]["counters"][missing]
    assert set(afmoe_trace.reduce(ctx)) == left
