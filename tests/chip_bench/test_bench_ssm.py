"""What PR 40 appended to ``BENCHMARK.json`` (a configuration, a cell, five
per-layer metrics of the state-space scan, and the cell's name in the lists
that name every cell), pinned to the INDICES the entries have and to no
end of a list, so that the next appending PR needs no mark; and PR 38's
entries at the indices they have. What a cell REPORTS is held as the ONE
recorded manifest has it (``data/manifest.recorded.json``, PR 51: the set
of metrics that name a cell grows whenever a PR appends one that lists
every cell), and the live manifest may only have grown from that record
(``bench_helpers.grown_from``). The reader of the five metrics on hand-built
contexts: nothing without a capture, for a model without state-space
layers, or on the capture recorded before the scopes existed; its
arithmetic on a made-up capture."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import shapes, shapes_ssm  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import ssm_trace  # noqa: E402
from test_bench_issue import ISSUE_METRICS  # noqa: E402

CELL = "granite-4.0-h-micro.chat-saturated"
CONFIG = "granite-4.0-h-micro"
SSM_METRICS = ("ssm_decode_step_ms", "ssm_decode_roofline_pct",
               "ssd_step_roofline_pct", "ssd_chunk_roofline_pct",
               "ssd_share_pct")
# Where this PR's entries stand (and will, whatever is appended after).
CONFIG_AT, CELL_AT, SSM_AT = 5, 6, 49
# PR 38's, as ``test_bench_hc.py`` names them, where they stand.
HC_CELL = "xing4.0-29b-a4b-d7.chat-saturated"
HC_CONFIG = "xing4.0-29b-a4b-d7"
HC_METRICS = ("hc_decode_roofline_pct", "hc_mix_roofline_pct",
              "hc_share_pct")
HC_CONFIG_AT, HC_CELL_AT, HC_AT = 4, 5, 46
# The lists that name every cell: PR 24's five span and scope metrics and
# PR 36's six; ``out_tok_s`` names the closed-loop cells.
EVERY_CELL = ("prefill_device_wait_ms", "fetch_lag_ms", "sample_share_pct",
              "kv_write_share_pct", "unscoped_share_pct") + ISSUE_METRICS
HC_SHARED = ("moe_gmm_roofline_pct", "mla_decode_roofline_pct",
             "moe_share_pct", "moe_experts_touched")
# Metrics of other architectures' arithmetic: never this cell's.
NOT_OURS = ("hyb_decode_step_ms", "hyb_decode_roofline_pct",
            "gdn_step_roofline_pct", "gdn_chunk_roofline_pct",
            "gdn_share_pct", "moe_decode_roofline_pct") + HC_SHARED \
    + HC_METRICS
# What names every cell since: PR 49's three of the device's memory.
HBM_METRICS = ("hbm_high_water_gb", "hbm_headroom_pct", "hbm_unexplained_gb")
# The dense arithmetic's two list the dense cells only (PR 51):
# ``lib/shapes.py``'s count is neither a hybrid's nor a sparse model's.
DENSE_ONLY = ("decode_roofline_pct", "prefill_mfu_pct")


@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.fixture(scope="module")
def was():
    return recorded()


@pytest.fixture(scope="module")
def by_name(doc):
    return {m["name"]: m for m in doc["per_layer"]}


# ------------------------------------------------ PR 38's block, where it is
def test_pr38_entries_are_where_they_were(doc):
    assert validate(doc, REPO) == []
    assert doc["configs"][HC_CONFIG_AT]["name"] == HC_CONFIG
    assert doc["configs"][HC_CONFIG_AT]["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert doc["configs"][HC_CONFIG_AT]["file"] == \
        f"benchmarks/chip/configs/{HC_CONFIG}/config.json"
    cell = doc["workloads"][HC_CELL_AT]
    assert cell == {"name": HC_CELL, "config": HC_CONFIG,
                    "traffic": "chat-saturated", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("i,name", list(enumerate(HC_METRICS)))
def test_pr38_metrics_are_where_they_were(doc, i, name):
    assert doc["per_layer"][HC_AT + i] == {
        "name": name, "unit": "%",
        "better": "lower" if name == "hc_share_pct" else "higher",
        "source": "device_trace", "layer": "model and attention kernels",
        "moves": "tpot_p50_ms", "workloads": [HC_CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + HC_SHARED + ("out_tok_s",))
def test_pr38_cell_is_named_where_it_was(by_name, name):
    """Last of the list as PR 38 left it: the place it still has."""
    cells = by_name[name]["workloads"]
    assert cells.index(HC_CELL) == (
        {"out_tok_s": 3}.get(name, 1 if name in HC_SHARED else 5))


def test_pr38_cell_reports_what_the_record_says(was):
    by_name = {m["name"]: m for m in was["per_layer"]}
    assert by_name["moe_decode_roofline_pct"]["workloads"] == [
        "kanana-2-30b-a3b-d8.chat-saturated"]
    listed = {m["name"] for m in was["per_layer"]
              if HC_CELL in m.get("workloads", ())}
    # Every layer of this model calls the latent decode kernel once a
    # step: kernel calls / layers IS a step, so the per-step time names it.
    assert listed == set(EVERY_CELL + HC_SHARED + HC_METRICS + HBM_METRICS) \
        | {"out_tok_s", "decode_step_ms"}
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        HC_CELL, "per_layer")}
    assert listed | {"attn_share_pct", "hbm_peak_gb",
                     "decode_rows_per_step"} <= reported
    # ``hc_decode_roofline_pct`` stands in for the dense count's share;
    # ``prefill_dev_us_per_token`` judges prefill.
    assert not set(DENSE_ONLY) & reported
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        HC_CELL, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


# ------------------------------------------------------ this PR's, by index
def test_the_configuration_and_the_cell_by_index(doc):
    assert doc["configs"][CONFIG_AT] == {
        "name": CONFIG,
        "source": "https://huggingface.co/ibm-granite/granite-4.0-h-micro/"
                  "blob/main/config.json",
        "file": f"benchmarks/chip/configs/{CONFIG}/config.json",
        "reduced": [], "why": doc["configs"][CONFIG_AT]["why"]}
    assert len(doc["configs"][CONFIG_AT]["why"]) <= 200
    cell = doc["workloads"][CELL_AT]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "chat-saturated", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("i,name", list(enumerate(SSM_METRICS)))
def test_the_five_metrics_by_index(doc, i, name):
    assert doc["per_layer"][SSM_AT + i] == {
        "name": name, "unit": "ms" if name.endswith("_ms") else "%",
        "better": "lower" if name in ("ssm_decode_step_ms", "ssd_share_pct")
        else "higher", "source": "device_trace",
        "layer": "model and attention kernels",
        "moves": "req_p50_ms" if name == "ssd_chunk_roofline_pct"
        else "tpot_p50_ms", "workloads": [CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + ("out_tok_s",))
def test_the_cell_is_named_after_pr38s(by_name, name):
    cells = by_name[name]["workloads"]
    assert cells.index(CELL) == cells.index(HC_CELL) + 1
    assert cells.count(CELL) == 1


@pytest.mark.parametrize("name", NOT_OURS)
def test_another_architectures_arithmetic_is_not_this_cells(by_name, name):
    assert CELL not in by_name[name]["workloads"]


def test_what_the_cell_reports_in_the_record(was):
    listed = {m["name"] for m in was["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(EVERY_CELL + SSM_METRICS + HBM_METRICS) \
        | {"out_tok_s"}
    reported = {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "per_layer")}
    # Those without a list are reported in every cell, this one too.
    assert listed | {"attn_share_pct", "hbm_peak_gb",
                     "device_idle_pct"} <= reported
    assert {m["name"] for m in Manifest(REPO).metrics_of(
        CELL, "end_to_end")} == {"req_p50_ms", "tpot_p50_ms", "setup_s"}


def test_the_manifest_only_grew_from_the_record(doc, was):
    """PR 40's configuration, cell and five metrics stand in the record
    behind what its parent had (PR 38's last), and the live manifest holds
    the record as its head."""
    assert grown_from(doc, was) == []
    assert (HC_CONFIG_AT + 1, HC_CELL_AT + 1, HC_AT + len(HC_METRICS)) == \
        (CONFIG_AT, CELL_AT, SSM_AT)
    assert (was["configs"][CONFIG_AT]["name"],
            was["workloads"][CELL_AT]["name"]) == (CONFIG, CELL)
    assert [m["name"] for m in was["per_layer"][
        SSM_AT:SSM_AT + len(SSM_METRICS)]] == list(SSM_METRICS)
    # Among what the parent had, the lists that name the cell name it
    # right behind PR 38's.
    for then in was["per_layer"][:SSM_AT]:
        cells = then.get("workloads", ())
        if CELL in cells:
            assert cells[cells.index(CELL) - 1] == HC_CELL, then["name"]
            assert then["name"] in EVERY_CELL + ("out_tok_s",)


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    assert deployment["reduced"] == {} and deployment["depth"] == 40
    assert deployment["source"] == manifest.configs[CONFIG]["source"]
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert set(flags) == {"--max-model-len", "--max-num-seqs",
                          "--max-num-batched-tokens", "--attn-impl",
                          "--num-kv-blocks"}
    assert all(f["why"] for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "granitemoehybrid"
    assert manifest.traffic("chat-saturated")["users"] == 48
    for name in ("leaf names", "in_proj columns", "gated norm", "head_dim",
                 "float32", "initialisation"):
        assert name in deployment["assumed"], name


def test_config_json_holds_the_catalogs_numbers():
    """Every number of the published config under its key (the catalog's
    row, copied whole)."""
    cfg = Manifest(REPO).model_config(CONFIG)
    want = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "vocab_size": 100352, "max_position_embeddings": 131072,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_chunk_size": 256, "num_local_experts": 0,
        "num_experts_per_tok": 0, "embedding_multiplier": 12,
        "attention_multiplier": 0.015625, "residual_multiplier": 0.22,
        "logits_scaling": 8, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    }
    assert {k: cfg[k] for k in want} == want
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["tie_word_embeddings"] and cfg["mamba_conv_bias"]
    assert not cfg["mamba_proj_bias"] and not cfg["attention_bias"]
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert set(cfg["layer_types"]) == {"mamba", "attention"}


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=(), counters=None, results=()):
    return {"model_config": cfg, "trace": {"notes": []},
            "trace_info": {"dirs": list(dirs), "counters": counters or {}},
            "results": list(results)}


@pytest.fixture(scope="module")
def granite():
    return Manifest(REPO).model_config(CONFIG)


@pytest.mark.parametrize("name", SSM_METRICS)
def test_each_metric_is_read_by_the_one_reader(name):
    fn, args = Manifest(REPO).reader(name)
    assert fn is ssm_trace.read and set(args) == {"field"}


@pytest.mark.parametrize("name", SSM_METRICS)
def test_nothing_without_a_capture_or_without_state_space_layers(
        name, granite):
    fn, args = Manifest(REPO).reader(name)
    assert fn(_ctx(granite), **args) is None
    assert fn(_ctx(granite, ["/no/such/dir"]), **args) is None
    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    for other in ("olmo-hybrid-7b-d16", "qwen2.5-3b"):
        cfg = Manifest(REPO).model_config(other)
        assert fn(_ctx(cfg, [recorded]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share(granite):
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    no ``ssd_*`` scope: the three shares of the scan are left out, and
    nothing raises (what the PARENT of this PR gives the new reader)."""
    from benchmarks.chip.lib import xplane

    recorded = os.path.join(os.path.dirname(__file__), "data", "loop_spans")
    ctx = _ctx(granite, [recorded])
    for name in ("ssd_step_roofline_pct", "ssd_chunk_roofline_pct",
                 "ssd_share_pct"):
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert not [n for n in ctx["trace"]["notes"] if "not read" in n]
    inner = ssm_trace.inner_seconds(xplane.find(recorded))
    assert all(inner[s] == 0 for s in ssm_trace.INNER)
    assert inner["busy_s"] > 0


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path, granite):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(granite, [str(tmp_path)])
    assert ssm_trace.read(ctx, "ssd_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "ssm_trace: capture not read (ValueError: truncated)"]


def _made_up(monkeypatch, seconds, paths):
    from benchmarks.chip.lib import spans

    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: {"ops": []})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)


def test_inner_seconds_sorts_the_scan_from_the_rest(monkeypatch):
    paths = {
        "a": "jit(_decode_impl)/while/body/attn_core/ssd_step/mul",
        "b": "jit(_prefill_impl)/while/body/attn_core/ssd_chunk/dot_general",
        "c": "jit(_decode_impl)/kv_write/state_write/dynamic_update_slice",
        "d": "jit(_decode_impl)/kv_write/state_read/gather",
        "e": "jit(_decode_impl)/while/body/attn_core/reduce_sum",
        "f": None,
    }
    seconds = {"a": 0.5, "b": 0.25, "c": 0.125, "d": 0.0625, "e": 1.0,
               "f": 2.0}
    _made_up(monkeypatch, seconds, paths)
    assert ssm_trace.inner_seconds("x") == {
        "ssd_step": 0.5, "ssd_chunk": 0.25, "state_write": 0.125,
        "state_read": 0.0625, "busy_s": 3.9375}


class _Request:
    prompt_tokens, output_tokens = 400, 200


class _Result:
    ok, request = True, _Request


@pytest.fixture
def reduced(monkeypatch, granite):
    """A made-up capture: 100 decode steps in 1.2 s of the decode program,
    of 20 row-steps a step 3 wasted; the scan 0.4 s of decode and 0.3 s of
    prefill over 6000 paired tokens; busy 4 s."""
    from benchmarks.chip.lib import xplane
    from benchmarks.chip.readers import prefill_tokens

    monkeypatch.setattr(xplane, "find", lambda d: "x.pb")
    monkeypatch.setattr(xplane, "reduce", lambda path: {
        "devices": 1, "programs": {"jit__decode_impl": 1.2},
        "counts": {"paged_flash_decode.3": 300, "paged_flash_decode.7": 100,
                   "paged_flash_prefill.2": 50}})
    _made_up(monkeypatch,
             {"a": 0.4, "b": 0.3, "c": 0.05, "d": 0.05, "e": 3.2},
             {"a": "x/attn_core/ssd_step/y", "b": "x/attn_core/ssd_chunk/y",
              "c": "x/kv_write/state_write/y", "d": "x/kv_write/state_read/y",
              "e": "x/ffn/y"})
    monkeypatch.setattr(prefill_tokens, "of",
                        lambda ctx: {"tokens": 6000, "device_s": 1.0})
    counters = {"pstpu:decode_steps_total": 50.0,
                "pstpu:decode_row_steps_total": 1000.0,
                "pstpu:decode_row_steps_wasted_total": 150.0}
    ctx = _ctx(granite, ["dir"], counters, [_Result()])
    return ssm_trace.reduce(ctx), ctx


PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def test_steps_are_kernel_calls_over_the_attention_layers(reduced):
    out, ctx = reduced
    assert out["decode_step_ms"] == pytest.approx(12.0)
    assert "100 steps, 17.00 live rows a step" in ctx["trace"]["notes"][0]


def test_the_whole_step_is_held_to_its_live_rows_bytes(reduced, granite):
    out, _ = reduced
    least = shapes.least_seconds(
        shapes_ssm.decode_step(granite, 17.0, 500.0), PEAK)
    assert least["bound"] == "memory"
    assert out["decode_roofline_pct"] == pytest.approx(
        100 * 100 * least["seconds"] / 1.2)
    assert 70 < out["decode_roofline_pct"] < 100


def test_the_step_is_held_to_the_live_rows_state(reduced, granite):
    out, _ = reduced
    state = 1700 * 36 * 2 * 64 * 64 * 128 * 4
    assert shapes_ssm.ssd_step(granite, 1700)["bytes"] == state
    assert out["ssd_step_roofline_pct"] == pytest.approx(
        100 * state / 819e9 / 0.4)


def test_the_chunk_is_held_to_the_paired_spans_tokens(reduced, granite):
    out, _ = reduced
    least = shapes.least_seconds(shapes_ssm.ssd_chunk(granite, 6000), PEAK)
    assert out["ssd_chunk_roofline_pct"] == pytest.approx(
        100 * least["seconds"] / 0.3)
    assert out["ssd_chunk_roofline_pct"] < 100


def test_the_share_is_the_four_scopes_over_busy(reduced):
    out, _ = reduced
    assert out["ssd_share_pct"] == pytest.approx(100 * 0.8 / 4.0)


def test_wasted_row_steps_are_not_counted_so_a_share_errs_low(
        monkeypatch, reduced, granite):
    out, ctx = reduced
    ctx["trace_info"]["counters"]["pstpu:decode_row_steps_wasted_total"] = 0.0
    more = ssm_trace.reduce(ctx)
    assert more["ssd_step_roofline_pct"] == pytest.approx(
        out["ssd_step_roofline_pct"] * 20 / 17)


@pytest.mark.parametrize("missing", [
    "pstpu:decode_steps_total", "pstpu:decode_row_steps_total"])
def test_without_the_counters_only_what_needs_none_is_read(
        reduced, granite, missing):
    _, ctx = reduced
    del ctx["trace_info"]["counters"][missing]
    ctx["trace_info"]["counters"].pop(
        "pstpu:decode_row_steps_wasted_total")
    out = ssm_trace.reduce(ctx)
    assert set(out) == {"decode_step_ms", "ssd_share_pct",
                        "ssd_chunk_roofline_pct"}
