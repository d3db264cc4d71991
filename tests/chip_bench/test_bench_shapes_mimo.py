"""``lib/shapes_mimo.py`` against the figures ISSUE 52 reckoned by hand for
MiMo-V2.5 (the published widths; whole at 48 layers and 256 experts, and one
chip's share of a 16-way expert-parallel deployment cut to 12 layers) and
against the tree the program serves; the benchmark's copy of the reference
against the tests'; and what the ring saves against one pool."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes  # noqa: E402
from benchmarks.chip.lib import shapes_mimo as sm  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "mimo-v2.5-ep16")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
FULL_AT = (0, 5, 11, 17, 23, 29, 35, 41, 47)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def whole(cfg):
    return {**cfg, "num_hidden_layers": 48, "n_routed_experts": 256,
            "ep_size": 1, "vocab_size": 152576,
            "hybrid_layer_pattern": [int(i not in FULL_AT)
                                     for i in range(48)],
            "moe_layer_freq": [0] + [1] * 47}


def test_the_benchmarks_reference_is_the_tests_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "mimo_v2_ref.py"),
        os.path.join(CONFIG_DIR, "reference.py"), shallow=False)


def test_dims(cfg, whole):
    d = sm.dims(cfg)
    assert (d["full"], d["windowed"], d["dense"], d["sparse"]) == \
        (3, 9, 1, 11)
    assert (d["heads"], d["kv_full"], d["kv_window"], d["dk"], d["dv"],
            d["window"]) == (64, 4, 8, 192, 128, 128)
    assert (d["held"], d["ep_size"], d["experts"], d["top_k"],
            d["expert_ffn"], d["ffn"]) == (16, 16, 256, 8, 2048, 16384)
    assert d["vocab"] == 19072 == 152576 // 8
    w = sm.dims(whole)
    assert (w["full"], w["windowed"], w["dense"], w["sparse"],
            w["held"], w["experts"]) == (9, 39, 1, 47, 256, 256)


def test_the_parts_by_hand(cfg):
    # q 4096 x 12288, k 4096 x 768, v 4096 x 512, o 8192 x 4096.
    assert sm.attention_params(cfg, 4) == 4096 * (12288 + 768 + 512) \
        + 8192 * 4096 == 89_128_960
    # 8 KV heads: k 4096 x 1536, v 4096 x 1024.
    assert sm.attention_params(cfg, 8) == 4096 * (12288 + 1536 + 1024) \
        + 8192 * 4096 == 94_371_840
    assert sm.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    assert sm.router_params(cfg) == 4096 * 256
    assert sm.dense_ffn_params(cfg) == 3 * 4096 * 16384 == 201_326_592
    assert sm.embedding_params(cfg) == 2 * 19072 * 4096 == 156_237_824


def test_the_whole_model_and_the_cut(cfg, whole):
    sparse_window = 94_371_840 + 16 * 25_165_824 + 4096 * 256
    sparse_full = 89_128_960 + 16 * 25_165_824 + 4096 * 256
    assert sm.matrix_params(cfg) == (89_128_960 + 201_326_592) \
        + 9 * sparse_window + 2 * sparse_full + 156_237_824 \
        == 5_915_017_216
    assert round(2 * sm.matrix_params(cfg) / 1e9, 2) == 11.83
    assert round(100 * 2 * sm.matrix_params(cfg) / 2 ** 34, 1) == 68.9
    assert round(sm.matrix_params(whole) / 1e9, 1) == 308.8
    # One layer's 256 experts: 12.9 GB, no chip holds them.
    assert round(256 * sm.expert_params(cfg) * 2 / 1e9, 1) == 12.9
    # The one other depth inside the floors: layers 0-5.
    six = {**cfg, "num_hidden_layers": 6,
           "hybrid_layer_pattern": cfg["hybrid_layer_pattern"][:6],
           "moe_layer_freq": cfg["moe_layer_freq"][:6]}
    assert round(2 * sm.matrix_params(six) / 1e9, 2) == 5.86


@pytest.mark.parametrize("which", ["cut", "whole"])
def test_the_served_tree_has_these_parameters(cfg, whole, which):
    import jax

    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig

    doc = cfg if which == "cut" else whole
    mc = ModelConfig.from_hf_config(doc)
    tree = jax.eval_shape(
        lambda: get_model(mc).init_params(mc, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(tree)
    assert sum(x.size for x in leaves) == sm.param_count(doc)
    # bf16 but for the routers' matrices and biases and the sinks.
    d = sm.dims(doc)
    f32 = d["sparse"] * (sm.router_params(doc) + d["experts"]) \
        + d["windowed"] * d["heads"]
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        2 * sm.param_count(doc) + 2 * f32
    specs = get_model(mc).cache_specs(mc)
    kv = specs.paged_kv
    # The pool pads a row of 192 + 128 payload lanes to 2 x 256.
    assert (kv.layers, kv.kv_heads, kv.head_dim) == (d["full"], 4, 256)
    assert sm.paged_bytes_per_token(doc) == d["full"] * 4 * 320 * 2
    assert sum(s.layers * s.shape[0] * s.shape[1] * s.shape[2] * 2
               for s in specs.state) == sm.ring_bytes_per_seq(doc)


def test_what_a_sequence_keeps_and_what_one_pool_would(cfg):
    # 2.5 KB a full layer-token, 5 KB a window layer-token (payload).
    assert sm.paged_bytes_per_token(cfg) == 3 * 2560 == 7680
    assert sm.ring_row_bytes(cfg) == 5120
    assert sm.ring_bytes_per_seq(cfg) == 9 * 128 * 5120 == 5_898_240
    # One pool of every key of every layer: 52.5 KB a token; the 131072
    # tokens cell 9's pool holds would be 6.9 GB.
    assert sm.pool_bytes_per_token_if_paged(cfg) == 7680 + 9 * 5120 \
        == 53_760
    assert round(131072 * 53_760 / 1e9, 1) == 7.0
    # At the cell's mean context the window layers keep 128 of ~4900 keys.
    assert round(100 * 128 / 4900, 1) == 2.6


@pytest.mark.parametrize("rows", [1, 20, 24])
def test_a_decode_step_is_bound_by_its_bytes(cfg, rows):
    """ISSUE 52's count at about 20 rows: the held experts touched, the
    attention and dense weights, the three full layers' keys and the
    rings."""
    touched = sm.expected_experts_touched(cfg, rows)
    context = 4900
    work = sm.decode_step(cfg, rows, context, touched)
    fixed = sm.step_fixed_weight_bytes(cfg)
    assert fixed == 2 * (3 * 89_128_960 + 9 * 94_371_840 + 201_326_592
                         + 19072 * 4096) + 4 * 11 * 4096 * 256
    experts = 11 * touched * 25_165_824 * 2
    full = rows * (context + 1) * 7680
    ring = rows * 9 * 129 * 5120
    assert work["bytes"] == pytest.approx(fixed + experts + full + ring)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    if rows == 20:
        assert round(touched, 1) == 7.5
        assert round(fixed / 1e9, 2) == 2.84
        assert round(experts / 1e9, 2) == 4.16
        assert round(full / 1e9, 2) == 0.75
        assert round(ring / 1e9, 2) == 0.12
        assert round(work["bytes"] / 819e9 * 1e3, 1) == 9.6
        # Window layers that kept every key would read 4.5 GB more.
        assert round(rows * 9 * context * 5120 / 1e9, 1) == 4.5


def test_active_parameters_count_a_sixteenth_of_the_choices(cfg, whole):
    d = sm.dims(cfg)
    assert sm.active_params(cfg) == sm.all_attention_params(cfg) \
        + 201_326_592 + 11 * (0.5 * 25_165_824 + 4096 * 256) \
        + 19072 * 4096
    # The uncut model: all 8 choices a layer are here.
    assert sm.active_params(whole) > 8 * 47 * 25_165_824
    assert d["top_k"] / d["ep_size"] == 0.5


def test_the_grouped_matmul_reads_the_touched_experts_and_its_pairs(cfg):
    """``lib/shapes_lfm.py:moe_gmm``'s count (that file reads a
    ``layer_types`` key this config has not): the touched experts' matrices
    once a call, a pair's row in (bf16) and out (float32) of both
    products."""
    work = sm.moe_gmm(cfg, 11, 11 * 10, 7.5)
    assert work["flops"] == 110 * 2 * 25_165_824
    assert work["bytes"] == 11 * 7.5 * 25_165_824 * 2 \
        + 110 * ((4096 + 2048) * 2 + (2 * 2048 + 4096) * 4)


def test_the_ring_statement_reads_128_keys_whatever_the_context(cfg):
    short = sm.ring_attend(cfg, 1, 50)
    long = sm.ring_attend(cfg, 1, 8000)
    assert short["bytes"] == 9 * 51 * 5120
    assert long["bytes"] == 9 * 129 * 5120 == \
        sm.ring_attend(cfg, 1, 4000)["bytes"]
    assert long["flops"] == 9 * 128 * 2 * 64 * 320
    # Memory-bound by far: 0.6 MB and 0.6 MFLOP... a row-step.
    assert long["flops"] / 197e12 < long["bytes"] / 819e9


def test_the_dense_arithmetic_would_read_twice_this_cells_truth(cfg):
    """What ``lib/shapes.py``'s count WOULD read here (it is listed in
    none of its three metrics: PR 51): every layer a dense llama layer of
    ``intermediate_size`` 16384 with 4 KV heads of 192 lanes and every key
    read: its step's bytes are not this model's."""
    dense = shapes.decode_step(cfg, 20, 4900)
    true = sm.decode_step(cfg, 20, 4900,
                          sm.expected_experts_touched(cfg, 20))
    assert dense["bytes"] != pytest.approx(true["bytes"], rel=0.2)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = "mimo-v2.5-ep16.longctx-decode"
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in ("decode_roofline_pct", "prefill_mfu_pct",
                 "decode_step_ms"):
        assert cell not in by_name[name]["workloads"]
    for name in ("mimo_decode_roofline_pct", "mimo_gmm_roofline_pct",
                 "mimo_moe_share_pct", "ring_attn_roofline_pct",
                 "ring_attn_share_pct", "ring_keys_held_pct"):
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["moves"] == "tpot_p50_ms"
