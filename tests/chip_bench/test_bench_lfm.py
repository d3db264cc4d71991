"""What PR 44 appended to ``BENCHMARK.json`` (a configuration, a cell, six
per-layer metrics of the sparse experts and the gated convolution, and the
cell's name in the lists that name every cell), pinned to the INDICES the
entries have and to no end of a list, so that the next appending PR needs no
mark (``tests/chip_bench/test_bench_ssm.py`` did the same for PR 40). The
reader of the six metrics on hand-built contexts: nothing without a capture,
for a model of another family, or on a capture recorded before the scopes
existed; its arithmetic on a made-up capture. What the cell REPORTS is
held as the ONE recorded manifest has it (``data/manifest.recorded.json``,
PR 51), from which the live one may only have grown."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import shapes, shapes_lfm  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import lfm_trace  # noqa: E402
from test_bench_ssm import EVERY_CELL, HBM_METRICS  # noqa: E402

CELL = "lfm2-8b-a1b-d16.chat-saturated"
CONFIG = "lfm2-8b-a1b-d16"
LFM_METRICS = ("lfm_decode_step_ms", "lfm_decode_roofline_pct",
               "lfm_gmm_roofline_pct", "lfm_moe_share_pct",
               "sconv_step_roofline_pct", "sconv_share_pct")
LOWER = ("lfm_decode_step_ms", "lfm_moe_share_pct", "sconv_share_pct")
# Where this PR's entries stand (and will, whatever is appended after).
CONFIG_AT, CELL_AT, LFM_AT = 6, 7, 54
# The cell's place in the lists that name it.
NAMED_AT = {"out_tok_s": 5, "moe_experts_touched": 2}
# Metrics of other architectures' arithmetic: never this cell's.
NOT_OURS = ("hyb_decode_step_ms", "hyb_decode_roofline_pct",
            "gdn_step_roofline_pct", "gdn_chunk_roofline_pct",
            "gdn_share_pct", "moe_decode_roofline_pct",
            "moe_gmm_roofline_pct", "mla_decode_roofline_pct",
            "moe_share_pct", "hc_decode_roofline_pct", "hc_mix_roofline_pct",
            "hc_share_pct", "ssm_decode_step_ms", "ssm_decode_roofline_pct",
            "ssd_step_roofline_pct", "ssd_chunk_roofline_pct",
            "ssd_share_pct")
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
        "source": "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/"
                  "config.json",
        "file": f"benchmarks/chip/configs/{CONFIG}/config.json",
        "reduced": ["num_hidden_layers", "layer_types"],
        "why": doc["configs"][CONFIG_AT]["why"]}
    assert len(doc["configs"][CONFIG_AT]["why"]) <= 200
    cell = doc["workloads"][CELL_AT]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "chat-saturated", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("i,name", list(enumerate(LFM_METRICS)))
def test_the_six_metrics_by_index(doc, i, name):
    assert doc["per_layer"][LFM_AT + i] == {
        "name": name, "unit": "ms" if name.endswith("_ms") else "%",
        "better": "lower" if name in LOWER else "higher",
        "source": "device_trace", "layer": "model and attention kernels",
        "moves": "tpot_p50_ms", "workloads": [CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + tuple(NAMED_AT))
def test_the_cell_is_named_where_it_stands(by_name, name):
    cells = by_name[name]["workloads"]
    assert cells.index(CELL) == NAMED_AT.get(name, 7)
    assert cells.count(CELL) == 1


@pytest.mark.parametrize("name", NOT_OURS)
def test_another_architectures_arithmetic_is_not_this_cells(by_name, name):
    assert CELL not in by_name[name]["workloads"]


def test_what_the_cell_reports_in_the_record(was):
    listed = {m["name"] for m in was["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(EVERY_CELL + LFM_METRICS + tuple(NAMED_AT)
                         + HBM_METRICS)
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "per_layer")}
    # Those without a list are reported in every cell, this one too.
    assert listed | {"attn_share_pct", "hbm_peak_gb",
                     "device_idle_pct"} <= reported
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


def test_the_manifest_only_grew_from_the_record(doc, was):
    """PR 44's configuration, cell and six metrics stand in the record at
    the ends of what its parent had, and the live manifest holds the
    record as its head."""
    assert grown_from(doc, was) == []
    assert (was["configs"][CONFIG_AT]["name"],
            was["workloads"][CELL_AT]["name"]) == (CONFIG, CELL)
    assert CELL_AT == CONFIG_AT + 1
    assert [m["name"] for m in was["per_layer"][
        LFM_AT:LFM_AT + len(LFM_METRICS)]] == list(LFM_METRICS)
    # Among what the parent had, the lists that named the cell when its PR
    # ended name it where it stood then.
    grew = [then["name"] for then in was["per_layer"][:LFM_AT]
            if CELL in then.get("workloads", ())
            and then["name"] in EVERY_CELL + tuple(NAMED_AT)]
    assert sorted(grew) == sorted(EVERY_CELL + tuple(NAMED_AT))


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    assert set(deployment["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert deployment["depth"] == 16
    assert "first of two pipeline stages" in deployment["stands_for"]
    assert deployment["source"] == manifest.configs[CONFIG]["source"]
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert flags == {"--max-model-len": "3072", "--max-num-seqs": "64",
                     "--max-num-batched-tokens": "1024",
                     "--attn-impl": "paged", "--num-kv-blocks": "12288"}
    assert all(f["why"] for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "lfm2_moe"
    assert manifest.traffic("chat-saturated")["users"] == 48
    for name in ("tie_word_embeddings", "head_dim", "in_proj thirds",
                 "no activation", "taps", "qk norm", "rope", "router",
                 "leaf names", "float32", "initialisation"):
        assert name in deployment["assumed"], name
    for name in ("reference.py", "check_reference.py"):
        assert os.path.exists(os.path.join(manifest.model_dir(CONFIG), name))


def test_config_json_holds_the_catalogs_numbers():
    """Every number of the published config under its key (the catalog's
    row, copied whole), but the depth and the list cut with it."""
    cfg = Manifest(REPO).model_config(CONFIG)
    want = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "vocab_size": 65536,
        "num_hidden_layers": 16,
    }
    assert {k: cfg[k] for k in want} == want
    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"]
    assert not cfg["conv_bias"]
    assert cfg["layer_types"] == [
        "conv", "conv", "full_attention", "conv"] * 4
    assert set(cfg) == set(want) | {
        "model_type", "layer_types", "norm_topk_prob", "use_expert_bias",
        "conv_bias"}


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=(), counters=None, results=()):
    return {"model_config": cfg, "trace": {"notes": []},
            "trace_info": {"dirs": list(dirs), "counters": counters or {}},
            "results": list(results)}


@pytest.fixture(scope="module")
def lfm():
    return Manifest(REPO).model_config(CONFIG)


@pytest.mark.parametrize("name", LFM_METRICS)
def test_each_metric_is_read_by_the_one_reader(name):
    fn, args = Manifest(REPO).reader(name)
    assert fn is lfm_trace.read and set(args) == {"field"}


@pytest.mark.parametrize("name", LFM_METRICS)
def test_nothing_without_a_capture_or_for_another_family(name, lfm):
    fn, args = Manifest(REPO).reader(name)
    assert fn(_ctx(lfm), **args) is None
    assert fn(_ctx(lfm, ["/no/such/dir"]), **args) is None
    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    for other in ("granite-4.0-h-micro", "kanana-2-30b-a3b-d8",
                  "qwen2.5-3b"):
        cfg = Manifest(REPO).model_config(other)
        assert fn(_ctx(cfg, [recorded]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share(lfm):
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    none of this family's scopes: the shares are left out, and nothing
    raises."""
    from benchmarks.chip.lib import xplane

    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    ctx = _ctx(lfm, [recorded])
    for name in ("lfm_gmm_roofline_pct", "lfm_moe_share_pct",
                 "sconv_step_roofline_pct", "sconv_share_pct",
                 "lfm_decode_roofline_pct"):
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert not [n for n in ctx["trace"]["notes"] if "not read" in n]
    inner = lfm_trace.scope_seconds(xplane.find(recorded))
    assert inner["moe"] == inner["gmm_decode"] == inner["conv_decode"] \
        == inner["conv_state"] == 0
    assert inner["busy_s"] > 0


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path, lfm):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(lfm, [str(tmp_path)])
    assert lfm_trace.read(ctx, "sconv_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "lfm_trace: capture not read (ValueError: truncated)"]


def _made_up(monkeypatch, seconds, paths):
    from benchmarks.chip.lib import spans

    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: {"ops": []})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)


def test_scope_seconds_sorts_experts_and_convolution_from_the_rest(
        monkeypatch):
    paths = {
        "a": "jit(_decode_impl)/while/body/ffn/moe_experts/moe_gmm/call",
        "b": "jit(_prefill_impl)/while/body/ffn/moe_experts/moe_gmm/call",
        "c": "jit(_decode_impl)/while/body/ffn/moe_route/top_k",
        "d": "jit(_decode_impl)/while/body/attn_core/short_conv/mul",
        "e": "jit(_prefill_impl)/while/body/attn_core/short_conv/add",
        "f": "jit(_decode_impl)/kv_write/state_write/scatter",
        "g": "jit(_decode_impl)/state_read/gather",
        "h": "jit(_decode_impl)/while/body/ffn/dot_general",
        "i": None,
    }
    seconds = dict(zip("abcdefghi", (1.0, 0.5, 0.25, 0.125, 0.0625,
                                     0.03125, 0.015625, 2.0, 4.0)))
    _made_up(monkeypatch, seconds, paths)
    assert lfm_trace.scope_seconds("x") == {
        "moe": 1.75, "gmm_decode": 1.0, "conv_decode": 0.125,
        "conv_state": 0.125 + 0.0625 + 0.03125 + 0.015625,
        "busy_s": sum(seconds.values())}


class _Request:
    prompt_tokens, output_tokens = 400, 200


class _Result:
    ok, request = True, _Request


@pytest.fixture
def reduced(monkeypatch, lfm):
    """A made-up capture: 100 decode steps in 1.4 s of the decode program,
    of 15 row-steps a step 2 wasted; 25 experts a sparse-layer call; the
    grouped matmuls 1.0 s of decode, the convolution 0.1 s; busy 4 s."""
    from benchmarks.chip.lib import xplane

    monkeypatch.setattr(xplane, "find", lambda d: "x.pb")
    monkeypatch.setattr(xplane, "reduce", lambda path: {
        "devices": 1, "programs": {"jit__decode_impl": 1.4},
        "counts": {"paged_flash_decode.3": 300, "paged_flash_decode.7": 100,
                   "paged_flash_prefill.2": 50}})
    _made_up(monkeypatch,
             {"a": 1.0, "b": 0.2, "c": 0.1, "d": 0.05, "e": 0.05, "f": 2.6},
             {"a": "jit(_decode_impl)/ffn/moe_experts/moe_gmm/y",
              "b": "jit(_decode_impl)/ffn/moe_route/y",
              "c": "jit(_decode_impl)/attn_core/short_conv/y",
              "d": "jit(_prefill_impl)/attn_core/short_conv/y",
              "e": "jit(_decode_impl)/kv_write/state_write/y",
              "f": "x/attn_proj/y"})
    counters = {"pstpu:decode_steps_total": 50.0,
                "pstpu:decode_row_steps_total": 750.0,
                "pstpu:decode_row_steps_wasted_total": 100.0,
                "pstpu:moe_layer_calls_total": 700.0,
                "pstpu:moe_experts_touched_total": 17500.0}
    ctx = _ctx(lfm, ["dir"], counters, [_Result()])
    return lfm_trace.reduce(ctx), ctx


def test_steps_are_kernel_calls_over_the_attention_layers(reduced):
    out, ctx = reduced
    assert out["decode_step_ms"] == pytest.approx(14.0)
    assert "100 steps, 13.00 live rows a step, 25.0 experts a call" in \
        ctx["trace"]["notes"][0]


def test_the_whole_step_is_held_to_the_experts_touched(reduced, lfm):
    out, _ = reduced
    least = shapes.least_seconds(
        shapes_lfm.decode_step(lfm, 13.0, 500.0, 25.0), PEAK)
    assert least["bound"] == "memory"
    assert out["decode_roofline_pct"] == pytest.approx(
        100 * 100 * least["seconds"] / 1.4)
    assert 70 < out["decode_roofline_pct"] < 100


def test_the_grouped_matmul_by_this_configurations_arithmetic(reduced, lfm):
    out, _ = reduced
    least = shapes.least_seconds(shapes_lfm.moe_gmm(
        lfm, 1400, 1400 * 13 * 4, 25.0), PEAK)
    assert out["gmm_roofline_pct"] == pytest.approx(
        100 * least["seconds"] / 1.0)
    assert out["gmm_roofline_pct"] < 100


def test_the_convolution_is_held_to_the_live_rows_bytes(reduced, lfm):
    out, _ = reduced
    byts = 12 * 2 * (1300 * 2048 * 8 + 100 * 3 * 2048)
    assert shapes_lfm.sconv_step(lfm, 1300, 100)["bytes"] == byts
    assert out["sconv_step_roofline_pct"] == pytest.approx(
        100 * byts / 819e9 / 0.1)


def test_the_shares_are_their_scopes_over_busy(reduced):
    out, _ = reduced
    assert out["moe_share_pct"] == pytest.approx(100 * 1.2 / 4.0)
    assert out["sconv_share_pct"] == pytest.approx(100 * 0.2 / 4.0)


def test_wasted_row_steps_are_not_counted_so_a_share_errs_low(reduced):
    out, ctx = reduced
    ctx["trace_info"]["counters"]["pstpu:decode_row_steps_wasted_total"] = 0.0
    more = lfm_trace.reduce(ctx)
    assert more["sconv_step_roofline_pct"] > out["sconv_step_roofline_pct"]
    assert more["gmm_roofline_pct"] > out["gmm_roofline_pct"]


@pytest.mark.parametrize("missing,left", [
    ("pstpu:decode_steps_total",
     {"decode_step_ms", "moe_share_pct", "sconv_share_pct"}),
    ("pstpu:moe_layer_calls_total",
     {"decode_step_ms", "moe_share_pct", "sconv_share_pct",
      "sconv_step_roofline_pct"}),
])
def test_without_the_counters_only_what_needs_none_is_read(reduced, missing,
                                                           left):
    _, ctx = reduced
    del ctx["trace_info"]["counters"][missing]
    assert set(lfm_trace.reduce(ctx)) == left
