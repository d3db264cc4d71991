"""``lib/shapes_hc.py`` against the figures ISSUE 38 reckoned by hand for
Xing4.0-29B-A4B (the published widths) and its 7-layer cut, against the
parameter tree the program serves, and the configuration's files against
the catalog's rule: every published key as published but the depth and the
next-token-prediction layers."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes, shapes_hc as sh, shapes_moe as sm  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "xing4.0-29b-a4b-d7")
V5E = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


def test_layer_parameters(cfg):
    # q_a 3584 x 768 = 2.75M, q_b 768 x 32 x 192 = 4.72M, W_kva 3584 x 576
    # = 2.06M, W_kvb 512 x 32 x 256 = 4.19M, W_o 4096 x 3584 = 14.68M.
    assert sh.attention_params(cfg) == 2_752_512 + 4_718_592 + 2_064_384 \
        + 4_194_304 + 14_680_064 == 28_409_856
    # What shapes_moe counts for the same file: one full-rank W_q of 22.0M.
    assert sm.attention_params(cfg) - sh.attention_params(cfg) \
        == 3584 * 32 * 192 - 2_752_512 - 4_718_592 == 14_548_992
    assert sm.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert sm.shared_params(cfg) == 11_010_048
    assert sm.router_params(cfg) == 3584 * 64
    assert 64 * sm.expert_params(cfg) == 704_643_072
    # 99.09M of dense FFN; a dense layer 127.5M; a sparse layer 744.3M,
    # 39.6M of it beside its experts, 83.7M active a token.
    assert sh.dense_layer_params(cfg) == 28_409_856 + 3 * 3584 * 9216 \
        == 127_500_288
    assert sh.sparse_layer_params(cfg) == 28_409_856 + 704_643_072 \
        + 11_010_048 + 229_376 == 744_292_352
    assert sh.sparse_layer_active_params(cfg) == 28_409_856 \
        + 4 * 11_010_048 + 11_010_048 + 229_376 == 83_689_472
    # A sublayer's mix: phi 14336 x 24, b 24, a 3; two a layer = 0.69M.
    assert sh.mix_params(cfg) == 14336 * 24 + 24 + 3 == 344_091
    assert round(2 * sh.mix_params(cfg) / 1e6, 2) == 0.69


def test_the_cut_and_the_whole_model(cfg):
    d = sh.dims(cfg)
    assert (d["dense"], d["sparse"], d["streams"], d["sublayers"],
            d["q_rank"]) == (2, 5, 4, 14, 768)
    assert sm.embedding_params(cfg) == 2 * 131072 * 3584 == 939_524_096
    # 2 x 127.5M + 5 x 744.3M + 939.5M (+ 4.8M of mix) = 4.92B, 9.83 GB.
    assert sh.param_count(cfg) == 2 * 127_500_288 + 5 * 744_292_352 \
        + 939_524_096 + 14 * 344_091 == 4_920_803_706
    assert round(sh.weight_bytes(cfg) / 1e9, 2) == 9.85
    assert round((sh.param_count(cfg) - 14 * 344_091) * 2 / 1e9, 2) == 9.83
    whole = dict(cfg, num_hidden_layers=40)
    assert round(sh.param_count(whole) / 1e9, 2) == 29.51
    assert round((sh.param_count(whole) - 80 * 344_091) / 1e9, 2) == 29.48


def test_the_served_parameter_tree_has_these_counts(cfg):
    """``init_params`` at the published widths, shapes only: every matrix
    the arithmetic counts, and beside them the norms, the router's bias."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig

    mc = ModelConfig.from_hf_config(cfg)
    tree = jax.eval_shape(lambda: get_model(mc).init_params(
        mc, jax.random.PRNGKey(0), jnp.bfloat16))
    sizes = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = path[-1].key
        sizes[name] = sizes.get(name, 0) + leaf.size
    small = {"attn_norm": 7 * 3584, "mlp_norm": 7 * 3584,
             "kv_norm": 7 * 512, "q_norm": 7 * 768, "final_norm": 3584,
             "router_bias": 5 * 64}
    assert {k: sizes[k] for k in small} == small
    assert sum(sizes.values()) - sum(small.values()) == sh.param_count(cfg)
    f32 = sum(leaf.size for leaf in jax.tree.leaves(tree)
              if leaf.dtype == jnp.float32)
    assert f32 == 5 * 229_376 + 14 * 344_091 + 5 * 64
    nbytes = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(tree))
    assert nbytes == sh.weight_bytes(cfg) + 2 * (
        sum(small.values()) - 5 * 64) + 4 * 5 * 64
    assert tree["layers"]["sparse"]["hc_ffn_phi"].shape == (5, 14336, 24)
    assert tree["layers"]["dense"]["wq_a"].shape == (2, 3584, 768)
    assert tree["layers"]["sparse"]["w_gate_up"].shape == (5, 64, 3584, 2048)


def test_a_cached_token_is_one_row_a_layer(cfg):
    d = sh.dims(cfg)
    assert (d["row"], d["pool_row"]) == (576, 640)
    assert sm.latent_bytes_per_token(cfg) == 7 * 576 * 2 == 8_064
    assert sm.pool_bytes_per_token(cfg) == 8_960
    # 16384 blocks of 16 tokens: 2.35 GB.
    assert round(16384 * 16 * 8_960 / 1e9, 2) == 2.35


def test_a_decode_step_at_16_rows(cfg):
    """ISSUE 38's step: 16 rows touch ~41 of 64 experts a layer; most of
    its bytes are expert weights, and it is memory-bound."""
    touched = sm.expected_experts_touched(cfg, 16)
    assert round(touched) == 41
    work = sh.decode_step(cfg, rows=16, context=500, experts_touched=touched)
    experts = 5 * touched * 22_020_096
    fixed = sh.step_fixed_weight_bytes(cfg)
    # 7 x 28.41M + 2 x 99.09M + 5 x 11.01M + 469.8M (head) in bf16; the
    # router and the mix float32.
    assert fixed == 2 * (7 * 28_409_856 + 2 * 99_090_432 + 5 * 11_010_048
                         + 131072 * 3584) + 4 * (5 * 229_376 + 14 * 344_091) \
        == 1_867_580_904
    cache = 16 * 7 * 500 * 1280
    streams = 16 * 14 * 3 * 14336 * 2
    assert work["bytes"] == pytest.approx(experts + fixed + cache
                                          + 16 * 8_960 + streams)
    assert round(experts / 1e9, 1) == 4.5 and round(streams / 1e6) == 19
    assert 0.68 < experts / work["bytes"] < 0.72
    least = shapes.least_seconds(work, V5E)
    assert least["bound"] == "memory" and 7.5e-3 < least["seconds"] < 8.2e-3
    # kanana's arithmetic on the same step counts 204 MB more (W_q whole)
    # and no mix: 2.8% HIGH.
    moe = sm.decode_step(cfg, rows=16, context=500, experts_touched=touched)
    assert moe["bytes"] - work["bytes"] == pytest.approx(
        2 * 7 * 14_548_992 - 4 * 14 * 344_091 - streams)
    assert 1.02 < moe["bytes"] / work["bytes"] < 1.04


def test_the_mix_alone(cfg):
    """Per token and sublayer: the streams read once and read and written
    once (3 x 14336 x 2 B = 86 KB); ``phi`` once a call."""
    assert sh.stream_bytes_per_token(cfg) == 86_016
    step = sh.mix(cfg, tokens=16, calls=1)
    assert step["bytes"] == 14 * (16 * 86_016 + 4 * 344_091)
    # 2 x 14336 x 24 (x~ phi) + 2 x 14336 (H_pre x) + 2 x 20 x 3584.
    assert step["flops"] == 16 * 14 * (688_128 + 28_672 + 143_360)
    # 10 FLOPs a byte at 16 rows, 20 at a 1024-token chunk: memory-bound.
    assert shapes.least_seconds(step, V5E)["bound"] == "memory"
    chunk = sh.mix(cfg, tokens=1024, calls=1)
    assert shapes.least_seconds(chunk, V5E)["bound"] == "memory"
    assert 1.5e-3 < shapes.least_seconds(chunk, V5E)["seconds"] < 1.6e-3
    # One stream: nothing to mix.
    plain = dict(cfg, hc_mult=1)
    assert sh.mix(plain, 16, 1) == {"flops": 0.0, "bytes": 0.0}
    assert sh.mix_params(plain) == 0
    assert sh.param_count(plain) == sh.param_count(cfg) - 14 * 344_091


def test_one_stream_and_a_full_rank_query_are_kananas_arithmetic():
    with open(os.path.join(os.path.dirname(CONFIG_DIR),
                           "kanana-2-30b-a3b-d8", "config.json")) as f:
        kanana = json.load(f)
    assert sh.attention_params(kanana) == sm.attention_params(kanana)
    assert sh.param_count(kanana) == sm.param_count(kanana)
    assert sh.step_fixed_weight_bytes(kanana) == \
        sm.step_fixed_weight_bytes(kanana)
    assert sh.decode_step(kanana, 24, 500, 88) == \
        sm.decode_step(kanana, 24, 500, 88)


def test_the_configuration_is_the_published_one_but_for_its_cuts(cfg):
    """The catalog's row (model-configs guide), key for key."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
    }
    assert {k for k in published if cfg.get(k) != published[k]} \
        == {"num_hidden_layers", "num_nextn_predict_layers"}
    assert set(cfg) == set(published)
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]) \
        == (7, 0)
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in doc["configs"] if c["name"] == "xing4.0-29b-a4b-d7"]
    assert entry[0]["reduced"] == ["num_hidden_layers",
                                   "num_nextn_predict_layers"]
    deployment = json.load(open(os.path.join(CONFIG_DIR, "deployment.json")))
    assert deployment["depth"] == 7
    assert deployment["reduced"]["num_hidden_layers"]["from"] == 40
    assert deployment["reduced"]["num_nextn_predict_layers"]["from"] == 1
    assert set(deployment["reduced"]) == set(entry[0]["reduced"])
    for assumed in ("hc_streams_enter_and_leave", "hc_equations",
                    "hc_eps_and_norm_eps", "hc_sinkhorn_order",
                    "hc_leaf_names", "hc_precision", "hc_init"):
        assert deployment["assumed"][assumed]


def test_the_reference_beside_the_configuration_is_the_tests_copy():
    assert filecmp.cmp(
        os.path.join(CONFIG_DIR, "reference.py"),
        os.path.join(ROOT, "tests", "reference", "xing4_ref.py"),
        shallow=False)
