"""``lib/shapes_lfm.py`` against the figures ISSUE 44 reckoned by hand for
LFM2-8B-A1B (the published widths; whole at 24 layers and cut to 16) and
against the tree the program serves; the benchmark's copy of the reference
against the tests'."""

import filecmp
import json
import math
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes_lfm as sl  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "lfm2-8b-a1b-d16")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
PUBLISHED_TYPES = ["conv", "conv", "full_attention", "conv"] * 5 \
    + ["conv", "full_attention", "conv", "conv"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def whole(cfg):
    return {**cfg, "num_hidden_layers": 24, "layer_types": PUBLISHED_TYPES}


def test_the_benchmarks_reference_is_the_tests_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "lfm2_moe_ref.py"),
        os.path.join(CONFIG_DIR, "reference.py"), shallow=False)


def test_dims(cfg, whole):
    d = sl.dims(cfg)
    assert (d["conv"], d["attention"], d["dense"], d["sparse"]) == \
        (12, 4, 2, 14)
    assert (d["q"], d["kv"], d["head_dim"], d["taps"]) == (2048, 512, 64, 3)
    assert (d["experts"], d["top_k"], d["expert_ffn"], d["ffn"]) == \
        (32, 4, 1792, 7168)
    assert d["tied"] and d["vocab"] == 65536
    w = sl.dims(whole)
    assert (w["conv"], w["attention"], w["dense"], w["sparse"]) == \
        (18, 6, 2, 22)


def test_the_parts_by_hand(cfg):
    assert sl.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    assert 32 * sl.expert_params(cfg) + sl.router_params(cfg) == 352_387_072
    assert sl.dense_ffn_params(cfg) == 44_040_192
    assert sl.conv_params(cfg) == 12_582_912 + 4_194_304 + 6_144 \
        == 16_783_360
    assert sl.attention_params(cfg) == 10_485_760
    assert sl.embedding_params(cfg) == 134_217_728


def test_the_whole_model_and_the_cut(cfg, whole):
    """ISSUE 44's hand count, norms aside: 8.34 B whole (16.68 GB: more
    than a chip), 5.40 B cut (10.80 GB, 63% of 17.18 GB)."""
    assert sl.matrix_params(whole) == 8_339_828_736
    assert sl.matrix_params(cfg) == 5_399_060_480
    assert round(2 * sl.matrix_params(whole) / 1e9, 2) == 16.68
    assert round(2 * sl.matrix_params(cfg) / 1e9, 2) == 10.80
    assert sl.small_params(cfg) == 33 * 2048 + 4 * 128 + 14 * 32
    assert sl.param_count(cfg) == sl.matrix_params(cfg) + 68_544


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
    assert sum(x.size for x in leaves) == sl.param_count(doc)
    # bf16 but for the router's matrix and bias.
    d = sl.dims(doc)
    f32 = d["sparse"] * (sl.router_params(doc) + d["experts"])
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        2 * sl.param_count(doc) + 2 * f32
    specs = get_model(mc).cache_specs(mc)
    assert sum(s.layers * math.prod(s.shape) * 2 for s in specs.state) == \
        sl.conv_state_bytes_per_seq(doc)
    kv = specs.paged_kv
    assert 2 * kv.layers * kv.kv_heads * kv.head_dim * 2 == \
        sl.kv_bytes_per_token(doc)


def test_state_and_kv(cfg):
    # 2 tokens x 2048 channels bf16 = 8 KiB a layer, 98 KB over 12; K/V of
    # the 4 attention layers only: 4 x 2 x 8 x 64 x 2 B = 8 KiB a token.
    assert sl.conv_state_bytes_per_seq(cfg) == 12 * 2 * 2048 * 2 == 98_304
    assert sl.kv_bytes_per_token(cfg) == 8 * 1024
    assert round(12288 * 16 * sl.kv_bytes_per_token(cfg) / 1e9, 2) == 1.61
    assert round(65 * sl.conv_state_bytes_per_seq(cfg) / 1e6, 1) == 6.4


@pytest.mark.parametrize("rows", [1, 13, 32])
def test_a_decode_step_is_bound_by_its_bytes(cfg, rows):
    """ISSUE 44's step: 13 rows x 4 of 32 touch 26 experts a layer in
    expectation: about nine tenths of a step's bytes are expert weights,
    ~10 ms at 819 GB/s."""
    touched = sl.expected_experts_touched(cfg, rows)
    work = sl.decode_step(cfg, rows=rows, context=490, experts_touched=touched)
    fixed = sl.step_fixed_weight_bytes(cfg)
    assert fixed == 2 * (12 * 16_783_360 + 4 * 10_485_760 + 2 * 44_040_192
                         + 134_217_728) + 4 * 14 * 65_536
    experts = 14 * touched * 11_010_048 * 2
    state = rows * 2 * 98_304
    kv = rows * 491 * 8 * 1024
    assert work["bytes"] == pytest.approx(fixed + experts + state + kv)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    if rows == 13:
        assert round(touched, 1) == 26.4
        assert round(experts / 1e9, 2) == 8.13
        assert round(100 * experts / work["bytes"]) == 89
        assert round(work["bytes"] / 819e9 * 1e3, 1) == 11.1


def test_the_grouped_matmul_reads_the_touched_experts(cfg):
    work = sl.moe_gmm(cfg, calls=14, pairs=14 * 13 * 4, experts_touched=26)
    assert work["flops"] == 14 * 13 * 4 * 2 * 11_010_048
    assert work["bytes"] == 14 * 26 * 11_010_048 * 2 + 14 * 52 * (
        (2048 + 1792) * 2 + (2 * 1792 + 2048) * 4)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9


def test_the_convolution_is_its_rows_gates_and_state(cfg):
    work = sl.sconv_step(cfg, row_steps=1300, steps=100)
    # B, C, x in and y out (4 x 2048), the state read and written (2 x 2 x
    # 2048), bf16, a live row-step a conv layer; the taps a layer a step.
    assert work["bytes"] == 12 * 2 * (1300 * 2048 * 8 + 100 * 3 * 2048)
    assert work["flops"] == 1300 * 12 * 2048 * 8
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    # 5.3 MB a 13-row step against 9 GB (6 us of 11 ms): the convolution's
    # cost is not its bytes.
    assert work["bytes"] / 100 < 6e6


def test_prefill_counts_the_chosen_experts_only(cfg):
    work = sl.prefill(cfg, new_tokens=1024, context=512, rows=4)
    per_token = 12 * 16_783_360 + 4 * 10_485_760 + 2 * 44_040_192 \
        + 14 * (4 * 11_010_048 + 65_536)
    assert work["flops"] == 1024 * (2 * per_token + 4 * 4 * 2048 * 512) \
        + 4 * 2 * 65536 * 2048
