"""``lib/shapes_dots.py`` against the figures ISSUE 58 reckoned by hand for
dots3-note-prev (the published widths; whole at 46 layers and 256 experts,
and one chip's share of a 16-way expert-parallel deployment cut to 10
layers) and against the tree the program serves; the benchmark's copy of the
reference against the tests'; what the selection and the ring save."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes  # noqa: E402
from benchmarks.chip.lib import shapes_dots as sd  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "dots3-note-prev-ep16")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
FULL_AT = (0, 1, 5, 9, 13, 17, 21, 25, 29, 33, 37, 41, 45)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def whole(cfg):
    return {**cfg, "num_hidden_layers": 46, "n_routed_experts": 256,
            "ep_size": 1, "vocab_size": 152064,
            "layer_types": [sd.FULL if i in FULL_AT else sd.SLIDING
                            for i in range(46)]}


def test_the_benchmarks_reference_is_the_tests_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "dots3_ref.py"),
        os.path.join(CONFIG_DIR, "reference.py"), shallow=False)


def test_dims(cfg, whole):
    d = sd.dims(cfg)
    assert (d["full"], d["sliding"], d["dense"], d["sparse"]) == (4, 6, 1, 9)
    assert (d["window"], d["topk"], d["index_heads"], d["index_dim"]) == \
        (513, 2048, 64, 128)
    assert (d["held"], d["ep_size"], d["experts"], d["shared"], d["top_k"],
            d["expert_ffn"], d["ffn"]) == (16, 16, 256, 1, 8, 1536, 13824)
    assert d["vocab"] == 19008 == 152064 // 8
    assert sd.kind(cfg, False) == {"heads": 128, "q_rank": 1024,
                                   "rank": 512, "nope": 128, "rope": 64,
                                   "v": 128}
    assert sd.kind(cfg, True) == {"heads": 64, "q_rank": 1024, "rank": 1024,
                                  "nope": 192, "rope": 64, "v": 128}
    w = sd.dims(whole)
    assert (w["full"], w["sliding"], w["dense"], w["sparse"], w["held"],
            w["experts"]) == (13, 33, 1, 45, 256, 256)


PARTS = {
    # q_a 5120 x 1024, q_b 1024 x 24576, kv_a 5120 x 576, kv_b 512 x 32768,
    # o 16384 x 5120, the gate 5120 x 128.
    "full latent attention": (
        lambda c: sd.latent_attention_params(c, False),
        5120 * 1024 + 1024 * 24576 + 5120 * 576 + 512 * 32768
        + 16384 * 5120 + 5120 * 128),
    # wq_b 1024 x 8192, wk 5120 x 128, weights_proj 5120 x 64.
    "indexer": (sd.indexer_params,
                1024 * 8192 + 5120 * 128 + 5120 * 64),
    "a full layer's attention": (
        lambda c: sd.attention_params(c, False), 144_048_128),
    # 5120 x 1024, 1024 x 16384, 5120 x 1088, 1024 x 20480, 8192 x 5120,
    # 5120 x 64.
    "a sliding layer's attention": (
        lambda c: sd.attention_params(c, True),
        5120 * 1024 + 1024 * 16384 + 5120 * 1088 + 1024 * 20480
        + 8192 * 5120 + 5120 * 64),
    "a sliding layer's attention, the issue's": (
        lambda c: sd.attention_params(c, True), 90_832_896),
    "an expert": (sd.expert_params, 3 * 5120 * 1536),
    "the router": (sd.router_params, 5120 * 256),
    "layer 0's FFN": (sd.dense_ffn_params, 3 * 5120 * 13824),
    "table and head": (sd.embedding_params, 2 * 19008 * 5120),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_the_parts_by_hand(cfg, part):
    fn, want = PARTS[part]
    assert fn(cfg) == want


def test_the_whole_model_and_the_cut(cfg, whole):
    expert, router = 23_592_960, 1_310_720
    full = 144_048_128 + 17 * expert + router          # 546.4 M
    sliding = 90_832_896 + 17 * expert + router        # 493.2 M
    layer0 = 144_048_128 + 212_336_640                 # 356.4 M
    assert (round(full / 1e6, 1), round(sliding / 1e6, 1),
            round(layer0 / 1e6, 1)) == (546.4, 493.2, 356.4)
    assert sd.matrix_params(cfg) == layer0 + full \
        + 2 * (3 * sliding + full) + 194_641_920 == 5_149_687_808
    assert round(sd.matrix_params(cfg) * 2 / 1e9, 2) == 10.30
    assert round(sd.matrix_params(cfg) * 2 / (16 * 2 ** 30), 2) == 0.60
    # 256 experts a layer are 12.1 GB: no chip holds one layer whole.
    assert round(256 * expert * 2 / 1e9, 1) == 12.1
    # The language model whole: 279.6 B (the shared expert counted).
    assert round(sd.matrix_params(whole) / 1e9, 1) == 279.6


def test_the_count_is_the_served_trees(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig

    mc = ModelConfig.from_hf_config(cfg)
    tree = jax.eval_shape(lambda: get_model(mc).init_params(
        mc, jax.random.PRNGKey(0), jnp.bfloat16))
    served = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert served == sd.param_count(cfg) == 5_149_817_088


def test_what_a_token_and_a_sequence_keep(cfg):
    # 4 full layers x (640 + 128 lanes) x 2 B = 6 KiB as laid out.
    assert sd.paged_bytes_per_token(cfg, padded=True) == 6144
    assert sd.paged_bytes_per_token(cfg) == 4 * (576 + 128) * 2
    # About 20 KB if all ten layers paged their latent rows, about 620 KB
    # for the expanded keys and values.
    assert sd.pool_bytes_per_token_if_paged(cfg) == 6144 + 6 * 1152 * 2 \
        == 19_968
    assert sd.expanded_bytes_per_token(cfg) == \
        4 * 128 * 320 * 2 + 6 * 64 * 384 * 2 == 622_592
    # A sequence's rings: 6 x 513 x 1152 x 2 B as laid out.
    assert sd.ring_bytes_per_seq(cfg, padded=True) == 7_091_712
    assert sd.ring_bytes_per_seq(cfg) == 6 * 513 * 1088 * 2


@pytest.mark.parametrize("context,share", [(4160, 49.2), (9000, 22.8),
                                           (16448, 12.5)])
def test_a_full_layer_reads_what_it_selected(cfg, context, share):
    """The rooflines count min(L, 2048) latent rows and L index keys a full
    layer-row, 513 rows a sliding one, whatever implements them."""
    rows = sd.selected_attend(cfg, 1, context)["bytes"] / (4 * 576 * 2) - 1
    assert rows == 2048
    assert round(100 * rows / context, 1) == share
    index = sd.index_scan(cfg, 0, 1, context)
    assert index["bytes"] == 4 * (context + 1) * 256
    assert index["flops"] == 4 * (2 * sd.indexer_params(cfg)
                                  + context * 2 * 64 * 128)
    ring = sd.ring_attend(cfg, 1, context)
    assert ring["bytes"] == 6 * 514 * 1088 * 2
    assert ring["flops"] == 6 * 513 * 2 * 64 * (1088 + 1024)
    # At 9 k tokens 4.9 MB a layer-row against 11.5 MB for every row.
    one = (sd.index_scan(cfg, 0, 1, 9000)["bytes"]
           + sd.selected_attend(cfg, 1, 9000)["bytes"]) / 4
    assert round(one / 1e6, 1) == 4.7 and round(9000 * 640 * 2 / 1e6, 1) \
        == 11.5
    # Before the selection binds, every key is read.
    assert sd.selected_attend(cfg, 1, 1000)["bytes"] == 4 * 1001 * 576 * 2


def test_a_prompt_token_costs_what_the_issue_says(cfg):
    at4k, at16k = (sd.prefill_token_flops(cfg, n) for n in (4096, 16384))
    assert round(at4k["matrices"] / 1e9, 1) == 3.5
    assert 3.0e9 < at4k["attention"] < at16k["attention"] < 6.0e9


def test_a_decode_step_is_bound_by_its_bytes(cfg):
    work = sd.decode_step(cfg, 12, 9000, 8.0)
    fixed = sd.step_fixed_weight_bytes(cfg)
    assert round(fixed / 1e9, 2) == 3.33
    assert work["bytes"] == fixed + 9 * 8.0 * sd.expert_params(cfg) * 2 \
        + sd.index_scan(cfg, 0, 12, 9000)["bytes"] \
        + sd.selected_attend(cfg, 12, 9000)["bytes"] \
        + sd.ring_attend(cfg, 12, 9000)["bytes"]
    least = shapes.least_seconds(work, PEAK)
    assert least["bound"] == "memory" and 8e-3 < least["seconds"] < 9e-3
    # A sixteenth of a token's choices fall here; the shared expert always.
    assert sd.active_params(cfg) == sd.all_attention_params(cfg) \
        + sd.dense_ffn_params(cfg) + 9 * (1.5 * sd.expert_params(cfg)
                                          + sd.router_params(cfg)) \
        + 19008 * 5120
    assert round(sd.expected_experts_touched(cfg, 12), 1) == 5.1
    gmm = sd.moe_gmm(cfg, 9, 9 * 12 * 8 / 16, 5.0)
    assert gmm["flops"] == 54 * 2 * sd.expert_params(cfg)
