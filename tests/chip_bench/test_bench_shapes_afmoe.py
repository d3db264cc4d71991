"""``lib/shapes_afmoe.py`` against the figures ISSUE 47 reckoned by hand for
Trinity-Mini (the published widths; whole at 32 layers and cut to 8) and
against the tree the program serves; the benchmark's copy of the reference
against the tests'; and why no share that another file's arithmetic reads in
this cell can pass 100."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes  # noqa: E402
from benchmarks.chip.lib import shapes_afmoe as sa  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "trinity-mini-d8")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def whole(cfg):
    return {**cfg, "num_hidden_layers": 32, "layer_types": PERIOD * 8}


def test_the_benchmarks_reference_is_the_tests_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "afmoe_ref.py"),
        os.path.join(CONFIG_DIR, "reference.py"), shallow=False)


def test_dims(cfg, whole):
    d = sa.dims(cfg)
    assert (d["sliding"], d["full"], d["dense"], d["sparse"]) == (6, 2, 2, 6)
    assert (d["q"], d["kv"], d["head_dim"], d["span"]) == \
        (4096, 512, 128, 2048)
    assert (d["experts"], d["top_k"], d["shared"], d["expert_ffn"],
            d["ffn"]) == (128, 8, 1, 1024, 6144)
    assert d["vocab"] == 200192
    w = sa.dims(whole)
    assert (w["sliding"], w["full"], w["dense"], w["sparse"]) == \
        (24, 8, 2, 30)


def test_the_parts_by_hand(cfg):
    # q 2048 x 4096, k and v 2048 x 512 each, o 4096 x 2048, and the output
    # gate 2048 x 4096.
    assert sa.attention_params(cfg) == 2 * 2048 * 4096 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27_262_976
    assert sa.expert_params(cfg) == 3 * 2048 * 1024 == 6_291_456
    assert sa.sparse_ffn_params(cfg) == 128 * 6_291_456 + 6_291_456 \
        + 2048 * 128 == 811_859_968
    assert sa.dense_ffn_params(cfg) == 3 * 2048 * 6144 == 37_748_736
    assert sa.embedding_params(cfg) == 2 * 200192 * 2048 == 819_986_432


def test_the_whole_model_and_the_cut(cfg, whole):
    assert sa.matrix_params(cfg) == 8 * 27_262_976 + 6 * 811_859_968 \
        + 2 * 37_748_736 + 819_986_432 == 5_984_747_520
    assert round(2 * sa.matrix_params(cfg) / 1e9, 2) == 11.97
    assert round(100 * 2 * sa.matrix_params(cfg) / 2 ** 34, 1) == 69.7
    assert round(sa.matrix_params(whole) / 1e9, 2) == 26.12
    assert round(2 * sa.matrix_params(whole) / 1e9, 1) == 52.2
    # One period less: 2 dense + 2 sparse, 2.63 B; one more does not fit.
    more = {**cfg, "num_hidden_layers": 12, "layer_types": PERIOD * 3}
    assert 2 * sa.matrix_params(more) > 16e9


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
    assert sum(x.size for x in leaves) == sa.param_count(doc)
    # bf16 but for the router's matrix and bias.
    d = sa.dims(doc)
    f32 = d["sparse"] * (sa.router_params(doc) + d["experts"])
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        2 * sa.param_count(doc) + 2 * f32
    specs = get_model(mc).cache_specs(mc)
    assert not specs.state and specs.latent is None
    kv = specs.paged_kv
    assert 2 * kv.layers * kv.kv_heads * kv.head_dim * 2 == \
        sa.kv_bytes_per_token(doc)


def test_kv_held_and_keys_seen(cfg):
    # 8 layers x 2 x 4 KV heads x 128 x 2 B = 16 KiB a token held.
    assert sa.kv_bytes_per_token(cfg) == 16 * 1024
    assert round(8192 * 16 * sa.kv_bytes_per_token(cfg) / 1e9, 2) == 2.15
    # A decode query behind 4700 keys sees 2047 of them in a sliding layer
    # (itself is the 2048th) and all in a full one; under the bound, all.
    assert sa.keys_seen(cfg, 4700) == 6 * 2047 + 2 * 4700
    assert sa.keys_seen(cfg, 1000) == 8 * 1000
    assert sa.mean_keys_seen(cfg, [1000, 4700]) == \
        (8 * 1000 + 6 * 2047 + 2 * 4700) / 2
    # ISSUE 47's "43 of 74 MB a row-step" at the cell's mean context.
    held = 4700 * sa.kv_bytes_per_token(cfg)
    seen = sa.decode_attention(cfg, 1, sa.keys_seen(cfg, 4700))["bytes"]
    assert (round(seen / 1e6), round(held / 1e6)) == (44, 77)


@pytest.mark.parametrize("rows", [1, 10, 12])
def test_a_decode_step_is_bound_by_its_bytes(cfg, rows):
    """10 rows x 8 of 128 touch 61 experts a layer in expectation: the
    experts are most of a step's bytes, the K/V under the spans a tenth."""
    touched = sa.expected_experts_touched(cfg, rows)
    keys = sa.keys_seen(cfg, 4700)
    work = sa.decode_step(cfg, rows, keys, touched)
    fixed = sa.step_fixed_weight_bytes(cfg)
    assert fixed == 2 * (8 * 27_262_976 + 2 * 37_748_736 + 6 * 6_291_456
                         + 200192 * 2048) + 4 * 6 * 2048 * 128
    experts = 6 * touched * 6_291_456 * 2
    kv = rows * keys * 2 * 512 * 2 + rows * 16 * 1024
    assert work["bytes"] == pytest.approx(fixed + experts + kv)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    if rows == 10:
        assert round(touched, 1) == 60.9
        assert round(fixed / 1e9, 2) == 1.49
        assert round(experts / 1e9, 2) == 4.6
        assert round(kv / 1e9, 2) == 0.44
        assert round(work["bytes"] / 819e9 * 1e3, 1) == 8.0


def test_the_grouped_matmul_is_the_shared_arithmetic(cfg):
    from benchmarks.chip.lib import shapes_lfm

    assert sa.moe_gmm(cfg, 6, 6 * 80, 61.0) == \
        shapes_lfm.moe_gmm(cfg, 6, 6 * 80, 61.0)
    work = sa.moe_gmm(cfg, 1, 80, 61.0)
    assert work["flops"] == 80 * 2 * 6_291_456
    assert work["bytes"] == 61 * 6_291_456 * 2 \
        + 80 * ((2048 + 1024) * 2 + (2 * 1024 + 2048) * 4)


def test_prefill_attention_is_the_keys_scored(cfg):
    # A 4096-token prompt from position 0 in one sliding layer: the band.
    band = sum(min(p + 1, 2048) for p in range(4096))
    triangle = 4096 * 4097 // 2
    assert sa.prefill_attention(cfg, band)["flops"] == band * 4 * 4096
    assert round(band / triangle, 2) == 0.75


def test_the_dense_arithmetic_cannot_pass_100_in_this_cell(cfg):
    """What ``lib/shapes.py``'s count WOULD read in this cell, where two
    metrics reported it until PR 51 gave them the list of the dense cells
    (PERF.md section 7). Its decode step holds 8 dense
    FFNs and every key of every layer: at 12 rows x 12.8 k keys 4.2 GB,
    where the true step (this file's) reads at least the fixed weights and
    the touched experts: its share of a roofline errs LOW. Its prefill
    FLOPs a token (8 dense layers without the gate) are under this
    model's (the gate, 8 experts and the shared one)."""
    dense = shapes.decode_step(cfg, 12, 12800)
    assert round(dense["bytes"] / 1e9, 1) == 4.2
    true = sa.decode_step(cfg, 12, sa.keys_seen(cfg, 12800),
                          sa.expected_experts_touched(cfg, 12))
    assert true["bytes"] > 1.5 * dense["bytes"]
    assert round(true["bytes"] / 1e9, 1) == 7.6
    per_token_dense = 2 * 8 * shapes.layer_params(cfg)
    per_token_true = 2 * (sa.active_params(cfg) - 200192 * 2048)
    assert per_token_dense < per_token_true
