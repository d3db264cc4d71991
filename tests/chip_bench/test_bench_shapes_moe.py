"""``lib/shapes_moe.py`` against the figures ISSUE 33 reckoned by hand for
kanana-2-30b-a3b-instruct-2601 (the published widths) and its 8-layer cut,
and the configuration's files against the catalog's rule: every published
key as published but the depth."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes, shapes_moe as sm  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "kanana-2-30b-a3b-d8")
V5E = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


def test_layer_parameters(cfg):
    # W_q 2048 x 32 x 192 = 12.58M, W_kva 2048 x 576 = 1.18M, W_kvb 512 x
    # 32 x 256 = 4.19M, W_o 4096 x 2048 = 8.39M.
    assert sm.attention_params(cfg) == 12_582_912 + 1_179_648 + 4_194_304 \
        + 8_388_608 == 26_345_472
    assert sm.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert sm.shared_params(cfg) == 2 * 4_718_592
    assert sm.router_params(cfg) == 2048 * 128
    # 128 experts 603.98M; sparse layer 640.0M; dense layer 64.1M.
    assert 128 * sm.expert_params(cfg) == 603_979_776
    assert sm.sparse_layer_params(cfg) == 26_345_472 + 603_979_776 \
        + 9_437_184 + 262_144 == 640_024_576
    assert sm.dense_layer_params(cfg) == 26_345_472 + 3 * 2048 * 6144 \
        == 64_094_208
    # What one token multiplies in a sparse layer: 64.4M.
    assert sm.sparse_layer_active_params(cfg) == 26_345_472 \
        + 6 * 4_718_592 + 9_437_184 + 262_144 == 64_356_352


def test_the_cut_and_the_whole_model(cfg):
    d = sm.dims(cfg)
    assert (d["dense"], d["sparse"]) == (1, 7)
    assert sm.embedding_params(cfg) == 2 * 128256 * 2048 == 525_336_576
    # 64.1M + 7 x 640.0M + 525.3M = 5.07B, 10.14 GB in bf16.
    assert sm.param_count(cfg) == 64_094_208 + 7 * 640_024_576 \
        + 525_336_576 == 5_069_602_816
    assert round(sm.param_count(cfg) * 2 / 1e9, 2) == 10.14
    whole = dict(cfg, num_hidden_layers=48)
    assert round(sm.param_count(whole) / 1e9, 2) == 30.67


def test_a_cached_token_is_one_row_a_layer(cfg):
    d = sm.dims(cfg)
    assert (d["row"], d["pool_row"]) == (576, 640)
    # 8 x 576 x 2 B = 9.2 KB of payload, 10.2 KB as the pool keeps it.
    assert sm.latent_bytes_per_token(cfg) == 9_216
    assert sm.pool_bytes_per_token(cfg) == 10_240
    # What lib/shapes.py reckons for the same file: a dense llama's K/V of
    # 32 heads x 64, seven times the payload (PERF.md section 7, PR 33).
    assert shapes.kv_bytes_per_token(cfg) == 65_536


def test_a_decode_step_at_24_rows(cfg):
    """ISSUE 33's step: 24 rows touch ~88 experts a layer; four fifths of
    its bytes are expert weights, and it is memory-bound."""
    touched = sm.expected_experts_touched(cfg, 24)
    assert round(touched) == 88
    work = sm.decode_step(cfg, rows=24, context=500, experts_touched=touched)
    experts = 7 * touched * 9_437_184
    fixed = sm.step_fixed_weight_bytes(cfg)
    # 8 x 26.35M + 37.75M + 7 x 9.44M + 262.7M (head) in bf16, router f32.
    assert fixed == 2 * (8 * 26_345_472 + 37_748_736 + 7 * 9_437_184
                         + 128256 * 2048) + 4 * 7 * 262_144 == 1_161_822_208
    cache = 24 * 8 * 500 * 1280
    assert work["bytes"] == pytest.approx(experts + fixed + cache
                                          + 24 * 10_240)
    assert round(experts / 1e9, 1) == 5.8 and round(cache / 1e9, 2) == 0.12
    assert 0.78 < experts / work["bytes"] < 0.85
    least = shapes.least_seconds(work, V5E)
    assert least["bound"] == "memory" and 8.5e-3 < least["seconds"] < 9.0e-3


def test_the_kernels_own_work(cfg):
    # The latent kernel: a cached token's 1280 B once, 32 heads x (576 +
    # 512) multiply-adds.
    work = sm.mla_decode(cfg, row_steps=24, context=500)
    assert work["bytes"] == 24 * 8 * 500 * 1280
    assert work["flops"] == 24 * 8 * 500 * 2 * 32 * 1088
    # 54 FLOPs a byte: under the v5e's 240, so its roofline is its bytes.
    assert shapes.least_seconds(work, V5E)["bound"] == "memory"
    # The grouped matmuls of 7 calls: the touched experts' matrices, never
    # all 128 of them.
    gmm = sm.moe_gmm(cfg, calls=7, pairs=7 * 24 * 6, experts_touched=88)
    assert gmm["flops"] == 7 * 24 * 6 * 2 * 4_718_592
    assert 7 * 88 * 9_437_184 < gmm["bytes"] < 1.01 * 7 * 88 * 9_437_184
    assert gmm["bytes"] < 0.7 * 7 * 128 * 9_437_184


def test_prefill_counts_six_experts_a_token(cfg):
    work = sm.prefill(cfg, new_tokens=1000, context=0, rows=0)
    assert work["flops"] == 1000 * 2 * (64_094_208 + 7 * 64_356_352)
    # Every expert for every token would be 21 x the experts' FLOPs.
    assert 128 / 6 > 21


def test_the_configuration_is_the_published_one_but_for_its_depth(cfg):
    """The catalog's row (model-configs guide), key for key."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
    }
    assert {k for k in published if cfg.get(k) != published[k]} \
        == {"num_hidden_layers"}
    assert set(cfg) == set(published) and cfg["num_hidden_layers"] == 8
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in doc["configs"] if c["name"] == "kanana-2-30b-a3b-d8"]
    assert entry[0]["reduced"] == ["num_hidden_layers"]
    deployment = json.load(open(os.path.join(CONFIG_DIR, "deployment.json")))
    assert deployment["depth"] == 8
    assert deployment["reduced"]["num_hidden_layers"]["from"] == 48


def test_the_reference_beside_the_configuration_is_the_tests_copy():
    assert filecmp.cmp(
        os.path.join(CONFIG_DIR, "reference.py"),
        os.path.join(ROOT, "tests", "reference", "deepseek_v3_ref.py"),
        shallow=False)
