"""What PR 54 appended to ``BENCHMARK.json`` (a configuration, a cell, six
per-layer metrics of the selective scan, of the one paged layer's eight
readers and of the rings, and the cell's name in the lists that name every
cell), pinned to the INDICES the entries have and to no end of a list, so
that the next appending PR needs no mark (``test_bench_mimo.py`` did the
same for PR 52). The reader of five of the six on hand-built contexts:
nothing without a capture, for a model of another family, or on a capture
recorded before the scopes existed; its arithmetic on a made-up capture. The
live manifest may only have grown from the ONE recorded copy
(``data/manifest.recorded.json``, PR 51)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from bench_helpers import REPO, grown_from, live, recorded  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmarks.chip.lib import shapes, shapes_sambay  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest, validate  # noqa: E402
from benchmarks.chip.readers import counter_ratio, sambay_trace  # noqa: E402
from test_bench_ssm import EVERY_CELL, HBM_METRICS  # noqa: E402

CELL = "phi-4-mini-flash.reasoning-saturated"
CONFIG = "phi-4-mini-flash"
TRACE_METRICS = ("sambay_decode_roofline_pct", "s6_step_roofline_pct",
                 "s6_chunk_roofline_pct", "s6_share_pct",
                 "shared_kv_attn_roofline_pct")
SAMBAY_METRICS = TRACE_METRICS + ("sambay_ring_keys_held_pct",)
LOWER = ("s6_share_pct", "sambay_ring_keys_held_pct")
# Where this PR's entries stand (and will, whatever is appended after).
CONFIG_AT, CELL_AT, SAMBAY_AT = 9, 10, 75
# The cell's place in the lists that name it.
NAMED_AT = {"out_tok_s": 8}
# The dense arithmetic's three (PR 51), every other architecture's, and the
# lists ``tests/chip_bench`` holds to the cells they had.
NOT_OURS = ("decode_roofline_pct", "prefill_mfu_pct", "decode_step_ms",
            "hyb_decode_roofline_pct", "gdn_share_pct",
            "moe_decode_roofline_pct", "moe_gmm_roofline_pct",
            "mla_decode_roofline_pct", "moe_share_pct",
            "moe_experts_touched", "hc_decode_roofline_pct",
            "ssm_decode_roofline_pct", "ssd_share_pct",
            "lfm_decode_roofline_pct", "sconv_share_pct",
            "afm_decode_roofline_pct", "span_decode_attn_roofline_pct",
            "mimo_decode_roofline_pct", "mimo_gmm_roofline_pct",
            "ring_attn_roofline_pct", "ring_attn_share_pct",
            "ring_keys_held_pct")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def doc():
    return live()


@pytest.fixture(scope="module")
def by_name(doc):
    return {m["name"]: m for m in doc["per_layer"]}


@pytest.fixture(scope="module")
def phi():
    return Manifest(REPO).model_config(CONFIG)


def test_the_configuration_and_the_cell_by_index(doc):
    config = doc["configs"][CONFIG_AT]
    assert (config["name"], config["reduced"]) == (CONFIG, [])
    assert config["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert config["file"] == f"benchmarks/chip/configs/{CONFIG}/config.json"
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    cell = doc["workloads"][CELL_AT]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "reasoning-saturated", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert validate(doc, REPO) == []


@pytest.mark.parametrize("i,name", list(enumerate(SAMBAY_METRICS)))
def test_the_six_metrics_by_index(doc, i, name):
    assert doc["per_layer"][SAMBAY_AT + i] == {
        "name": name, "unit": "%",
        "better": "lower" if name in LOWER else "higher",
        "source": "program_counter" if name == "sambay_ring_keys_held_pct"
        else "device_trace",
        "layer": "model and attention kernels",
        "moves": "req_p50_ms" if name == "s6_chunk_roofline_pct"
        else "tpot_p50_ms",
        "workloads": [CELL]}


@pytest.mark.parametrize("name", EVERY_CELL + tuple(NAMED_AT))
def test_the_cell_is_named_where_it_stands(by_name, name):
    cells = by_name[name]["workloads"]
    assert cells.index(CELL) == NAMED_AT.get(name, 10)
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
    assert set(SAMBAY_METRICS + EVERY_CELL) | {
        "attn_share_pct", "hbm_peak_gb", "device_idle_pct",
        "kv_usage_peak_pct", "prefix_hit_pct", "decode_rows_per_step"} \
        <= reported
    # ``test_bench_memory.py`` holds the three ``hbm_*`` lists to the nine
    # cells they had and ``test_bench_mimo.py`` ``ring_keys_held_pct`` to
    # its one (files this PR may not edit): the cell is not named there;
    # ``hbm_peak_gb`` reads its memory and ``sambay_ring_keys_held_pct`` the
    # rings' two counters.
    assert not (set(HBM_METRICS) | {"ring_keys_held_pct"}) & reported


def test_the_cells_files_are_beside_the_others():
    manifest = Manifest(REPO)
    deployment = manifest.deployment(CONFIG)
    assert deployment["reduced"] == {} and deployment["depth"] == 32
    assert "WHOLE" in deployment["stands_for"]
    assert deployment["source"] == manifest.configs[CONFIG]["source"]
    flags = {f["flag"]: f["value"] for f in deployment["engine_flags"]}
    assert flags == {"--max-model-len": "4224", "--max-num-seqs": "48",
                     "--max-num-batched-tokens": "2048",
                     "--attn-impl": "paged", "--num-kv-blocks": "16384"}
    assert all(f["why"] for f in deployment["engine_flags"])
    assert manifest.model_config(CONFIG)["model_type"] == "phi4flash"
    for name in ("source of the equations", "mamba_d_state", "mamba_d_conv",
                 "mamba_expand", "mamba_dt_rank", "which layers are which",
                 "the memory", "head pairing", "lambda", "biases",
                 "the window's bound", "no position embedding", "norms",
                 "leaf names", "float32", "initialisation"):
        assert name in deployment["assumed"], name
    for name in ("reference.py", "check_reference.py"):
        assert os.path.exists(os.path.join(manifest.model_dir(CONFIG), name))


def test_the_traffic_is_issue_54s():
    mix = Manifest(REPO).traffic("reasoning-saturated")
    assert (mix["loop"], mix["users"]) == ("closed", 48)
    assert mix["system"] == {"tokens": 64, "tenants": 1}
    assert mix["prompt"] == Manifest(REPO).traffic("chat-saturated")["prompt"]
    assert mix["prompt"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["output"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.5, "min": 256, "max": 2048}
    assert mix["limits"] == {"ttft_ms": None, "tpot_ms": None}
    # Every user decodes, and the longest request fits the envelope.
    flags = {f["flag"]: int(f["value"]) for f in Manifest(REPO).deployment(
        CONFIG)["engine_flags"] if f["value"].isdigit()}
    assert flags["--max-num-seqs"] >= mix["users"]
    longest = mix["prompt"]["max"] + mix["system"]["tokens"] \
        + mix["output"]["max"]
    assert longest <= flags["--max-model-len"]
    assert mix["users"] * longest <= flags["--num-kv-blocks"] * 16


# ----------------------------------------------------------------- the reader
def _ctx(cfg, dirs=(), counters=None, results=()):
    return {"model_config": cfg, "trace": {"notes": []},
            "trace_info": {"dirs": list(dirs), "counters": counters or {}},
            "results": list(results)}


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_each_trace_metric_is_read_by_the_one_reader(name):
    fn, args = Manifest(REPO).reader(name)
    assert fn is sambay_trace.read and set(args) == {"field"}


def test_the_share_of_keys_held_is_the_two_counters():
    fn, args = Manifest(REPO).reader("sambay_ring_keys_held_pct")
    assert fn is counter_ratio.read
    ctx = {"counters": {"pstpu:ring_keys_held_total": 512.0 * 8,
                        "pstpu:ring_keys_context_total": 1100.0 * 8}}
    assert fn(ctx, **args) == pytest.approx(100 * 512 / 1100)
    # A program without the counters (the parent), or one that delivered
    # no decode row-step: nothing, and nothing raises.
    assert fn({"counters": {}}, **args) is None
    assert fn({"counters": {"pstpu:ring_keys_held_total": 0.0,
                            "pstpu:ring_keys_context_total": 0.0}},
              **args) is None


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_nothing_without_a_capture_or_for_another_family(name, phi):
    fn, args = Manifest(REPO).reader(name)
    assert fn(_ctx(phi), **args) is None
    assert fn(_ctx(phi, ["/no/such/dir"]), **args) is None
    recorded_dir = os.path.join(os.path.dirname(__file__), "data",
                                "loop_spans")
    for other in ("mimo-v2.5-ep16", "granite-4.0-h-micro", "qwen2.5-3b"):
        cfg = Manifest(REPO).model_config(other)
        assert fn(_ctx(cfg, [recorded_dir]), **args) is None


def test_a_capture_without_the_scopes_reads_as_no_share(phi):
    """``data/loop_spans`` (PR 24, a dense model on a v5e): a device plane,
    none of this family's scopes: the shares of the scan are left out, and
    nothing raises."""
    from benchmarks.chip.lib import xplane

    recorded_dir = os.path.join(os.path.dirname(__file__), "data",
                                "loop_spans")
    ctx = _ctx(phi, [recorded_dir])
    for name in ("s6_step_roofline_pct", "s6_chunk_roofline_pct",
                 "s6_share_pct"):
        fn, args = Manifest(REPO).reader(name)
        assert fn(ctx, **args) is None
    assert not [n for n in ctx["trace"]["notes"] if "not read" in n]
    inner = sambay_trace.scope_seconds(xplane.find(recorded_dir))
    assert inner["s6_step"] == inner["s6_chunk"] == inner["s6_conv"] == 0
    assert inner["busy_s"] > 0


def test_a_capture_that_cannot_be_read_is_a_note_not_an_exception(
        monkeypatch, tmp_path, phi):
    from benchmarks.chip.lib import xplane

    def broken(path):
        raise ValueError("truncated")

    monkeypatch.setattr(xplane, "find", lambda d: str(tmp_path / "x.pb"))
    monkeypatch.setattr(xplane, "reduce", broken)
    ctx = _ctx(phi, [str(tmp_path)])
    assert sambay_trace.read(ctx, "s6_share_pct") is None
    assert ctx["trace"]["notes"] == [
        "sambay_trace: capture not read (ValueError: truncated)"]


def _made_up(monkeypatch, seconds, paths):
    from benchmarks.chip.lib import spans

    monkeypatch.setattr(spans, "op_scopes", lambda path: paths)
    monkeypatch.setattr(spans, "read_events", lambda path: {
        "ops": [], "spans": [], "programs": {}})
    monkeypatch.setattr(spans, "exclusive_seconds", lambda ops: seconds)


DEC = "jit(_decode_impl)/while/body/closed_call/"
PATHS = {
    "a": DEC + "while/body/closed_call/attn_core/s6_step/exp",
    "b": DEC + "attn_core/s6_step/dynamic_update_slice",
    "c": DEC + "while/body/closed_call/attn_core/s6_conv/reduce_sum",
    "d": "jit(_prefill_impl)/while/body/closed_call/attn_core/s6_chunk/"
         "jit(s6_chunk_kernel)/pallas_call",
    "e": "jit(_prefill_impl)/attn_core/s6_conv/add",
    "%paged_flash_decode_stats.19 = (bf16[48,40,128])":
        DEC + "while/body/closed_call/attn_core/xdec_attend/"
        "jit(paged_flash_decode_stats)/pallas_call",
    "%paged_flash_decode_stats.18 = (bf16[48,40,128])":
        DEC + "attn_core/jit(paged_flash_decode_stats)/pallas_call",
    "g": DEC + "ffn/dot_general",
    "h": None,
}
SECONDS = dict(zip(PATHS, (0.25, 0.125, 0.0625, 0.5, 0.03125, 0.875, 0.125,
                           2.0, 4.0)))


def test_scope_seconds_sorts_the_scan_and_the_paged_kernel_from_the_rest(
        monkeypatch):
    _made_up(monkeypatch, SECONDS, PATHS)
    assert sambay_trace.scope_seconds("x") == {
        "s6_step": 0.375, "s6_chunk": 0.5, "s6_conv": 0.09375,
        "s6_conv_decode": 0.0625, "s6_decode": 0.4375, "paged": 1.0,
        "busy_s": sum(SECONDS.values())}


class _Request:
    prompt_tokens, output_tokens = 400, 1400


class _Result:
    ok, request = True, _Request


def test_the_arithmetic_on_a_made_up_capture(monkeypatch, phi):
    """100 decode steps (800 paged-kernel calls: the full layer and seven
    cross layers) in 2.0 s of the decode program; of 48 row-steps a step 2
    wasted; the scan 0.375 s and its conv 0.0625 s of decode, the paged
    kernel 1.0 s; 3000 prompt tokens in the capture's paired prefill
    dispatches, whose scan took 0.5 s."""
    from benchmarks.chip.lib import xplane
    from benchmarks.chip.readers import hybrid_trace, prefill_tokens

    _made_up(monkeypatch, SECONDS, PATHS)
    monkeypatch.setattr(xplane, "find", lambda d: "x.pb")
    monkeypatch.setattr(xplane, "reduce", lambda path: {
        "devices": 1, "busy_s": 7.97, "window_s": 8.0,
        "programs": {"jit__decode_impl": 2.0},
        "ops": {"paged_flash_decode_stats.19": 0.1},
        "counts": {"paged_flash_decode_stats.19 bf16[48,40,128]": 700,
                   "paged_flash_decode_stats.18 bf16[48,40,128]": 100,
                   "paged_flash_prefill.7 bf16[1,10,16,512,128]": 8}})
    monkeypatch.setattr(hybrid_trace, "_peak", lambda: PEAK)
    monkeypatch.setattr(sambay_trace, "_peak", lambda: PEAK)
    monkeypatch.setattr(prefill_tokens, "of", lambda ctx: {"tokens": 3000})
    counters = {"pstpu:decode_steps_total": 50.0,
                "pstpu:decode_row_steps_total": 2400.0,
                "pstpu:decode_row_steps_wasted_total": 100.0}
    ctx = _ctx(phi, ["d"], counters, [_Result()])
    got = {f: sambay_trace.read(ctx, f) for f in (
        "decode_roofline_pct", "s6_step_roofline_pct",
        "s6_chunk_roofline_pct", "s6_share_pct",
        "shared_kv_attn_roofline_pct")}
    assert got["s6_share_pct"] == pytest.approx(100 * 0.4375 / 2.0)
    steps, rows, context = 100, 46.0, 1100.0

    def share(work, seconds):
        return 100 * shapes.least_seconds(work, PEAK)["seconds"] / seconds

    assert got["decode_roofline_pct"] == pytest.approx(steps * share(
        shapes_sambay.decode_step(phi, rows, context), 2.0))
    assert got["s6_step_roofline_pct"] == pytest.approx(share(
        shapes_sambay.s6_step(phi, steps * rows), 0.4375))
    assert got["s6_chunk_roofline_pct"] == pytest.approx(share(
        shapes_sambay.s6_chunk(phi, 3000), 0.5))
    assert got["shared_kv_attn_roofline_pct"] == pytest.approx(share(
        shapes_sambay.shared_kv_attend(phi, steps * rows, context), 1.0))
    assert all(0 < v < 100 for v in got.values())
    assert "46.00 live rows a step" in ctx["trace"]["notes"][-1]


def test_a_parent_without_the_program_fails_cleanly_and_soon():
    """The parent of this PR refuses the configuration at once
    (``Unsupported model_type``); what the harness then does is exit with
    another code than 0: ``ModelConfig.from_hf_config`` of a model type the
    program does not know raises before any process is started."""
    from production_stack_tpu.models.config import ModelConfig

    cfg = dict(Manifest(REPO).model_config(CONFIG), model_type="phi5flash")
    with pytest.raises(ValueError, match="Unsupported model_type"):
        ModelConfig.from_hf_config(cfg)
