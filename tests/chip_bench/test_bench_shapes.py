"""The FLOP and byte arithmetic against values worked by hand, and the two
committed config.json files against the sizes their sources publish."""

import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chip.lib import shapes  # noqa: E402
from benchmarks.chip.lib.manifest import Manifest  # noqa: E402

PUBLISHED = {
    "qwen2.5-3b": dict(
        model_type="qwen2", num_hidden_layers=36, hidden_size=2048,
        intermediate_size=11008, num_attention_heads=16,
        num_key_value_heads=2, vocab_size=151936, tie_word_embeddings=True,
        rope_theta=1000000.0, max_position_embeddings=32768,
        rms_norm_eps=1e-06),
    "mistral-7b-d16": dict(
        model_type="mistral", num_hidden_layers=16, hidden_size=4096,
        intermediate_size=14336, num_attention_heads=32,
        num_key_value_heads=8, vocab_size=32768, tie_word_embeddings=False,
        rope_theta=1000000.0, max_position_embeddings=32768,
        sliding_window=None, rms_norm_eps=1e-05),
}
# Worked by hand from the widths above.
#  qwen layer: 2048*(2048+2*256) + 2048*2048 + 3*2048*11008 = 77,070,336
#  mistral layer: 4096*(4096+2*1024) + 4096*4096 + 3*4096*14336 = 218,103,808
HAND = {
    "qwen2.5-3b": dict(
        layer=77_070_336,
        params=36 * 77_070_336 + 151936 * 2048,
        kv_per_token=2 * 36 * 256 * 2,
        step_weight_bytes=(36 * 77_070_336 + 151936 * 2048) * 2),
    "mistral-7b-d16": dict(
        layer=218_103_808,
        params=16 * 218_103_808 + 2 * 32768 * 4096,
        kv_per_token=2 * 16 * 1024 * 2,
        step_weight_bytes=(16 * 218_103_808 + 32768 * 4096) * 2),
}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(REPO)


@pytest.mark.parametrize("config", sorted(PUBLISHED))
def test_config_json_holds_the_published_sizes(manifest, config):
    cfg = manifest.model_config(config)
    for key, want in PUBLISHED[config].items():
        assert cfg[key] == want, key
    entry = manifest.configs[config]
    deployment = manifest.deployment(config)
    assert sorted(deployment["reduced"]) == sorted(entry["reduced"])
    assert deployment["source"] == entry["source"]
    assert deployment["depth"] == cfg["num_hidden_layers"]
    # What the engine ignores is said, not left to be found out.
    for key in ("sliding_window", "max_position_embeddings"):
        assert any(key in k for k in deployment["assumed"])


def test_the_engine_reads_both_as_llama_shaped(manifest):
    from production_stack_tpu.models.config import resolve_model_config

    qwen = resolve_model_config(manifest.model_dir("qwen2.5-3b"))
    mistral = resolve_model_config(manifest.model_dir("mistral-7b-d16"))
    assert (qwen.arch, qwen.head_dim_, qwen.attention_bias) == (
        "llama", 128, True)
    assert (mistral.arch, mistral.head_dim_, mistral.attention_bias) == (
        "llama", 128, False)
    assert (qwen.num_layers, mistral.num_layers) == (36, 16)


@pytest.mark.parametrize("config", sorted(HAND))
def test_parameter_and_byte_counts(manifest, config):
    cfg, hand = manifest.model_config(config), HAND[config]
    assert shapes.layer_params(cfg) == hand["layer"]
    assert shapes.param_count(cfg) == hand["params"]
    assert shapes.kv_bytes_per_token(cfg) == hand["kv_per_token"]
    assert shapes.step_weight_bytes(cfg) == hand["step_weight_bytes"]


def test_sizes_the_issue_states(manifest):
    qwen = manifest.model_config("qwen2.5-3b")
    mistral = manifest.model_config("mistral-7b-d16")
    assert shapes.param_count(qwen) / 1e9 == pytest.approx(3.09, abs=0.01)
    assert shapes.param_count(mistral) / 1e9 == pytest.approx(3.76, abs=0.01)
    assert shapes.kv_bytes_per_token(qwen) == 36 * 1024
    assert shapes.kv_bytes_per_token(mistral) == 64 * 1024


@pytest.mark.parametrize("config,rows,context", [
    ("qwen2.5-3b", 8, 400), ("mistral-7b-d16", 6, 6300)])
def test_decode_step_work(manifest, config, rows, context):
    cfg, hand = manifest.model_config(config), HAND[config]
    d = shapes.dims(cfg)
    work = shapes.decode_step(cfg, rows, context)
    matmul = 2 * (d["layers"] * hand["layer"] + d["vocab"] * d["hidden"])
    attention = 4 * d["layers"] * d["q"] * context
    assert work["flops"] == rows * (matmul + attention)
    assert work["bytes"] == hand["step_weight_bytes"] + rows * (
        context + 1) * hand["kv_per_token"]


def test_decode_is_memory_bound_and_prefill_counts_its_context(manifest):
    peak = manifest.peaks("TPU v5 lite")
    mistral = manifest.model_config("mistral-7b-d16")
    least = shapes.least_seconds(shapes.decode_step(mistral, 6, 6300), peak)
    assert least["bound"] == "memory"
    # 7.25 GB of weights + 6 * 6301 * 64 KiB of KV at 819 GB/s: 11.9 ms.
    assert least["seconds"] == pytest.approx(0.01187, rel=0.01)
    d = shapes.dims(mistral)
    work = shapes.prefill(mistral, 200, 6344, 1)
    assert work["flops"] == 200 * (
        2 * 16 * 218_103_808 + 4 * 16 * d["q"] * 6344) + 2 * 32768 * 4096
    big = shapes.least_seconds({"flops": 197e12, "bytes": 1.0}, peak)
    assert big == {"seconds": pytest.approx(1.0), "bound": "compute"}
