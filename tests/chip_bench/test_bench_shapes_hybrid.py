"""``lib/shapes_hybrid.py`` against the figures ISSUE 31 reckoned by hand for
Olmo-Hybrid-7B (the published widths) and its 16-layer cut."""

import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes_hybrid as sh  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "olmo-hybrid-7b-d16", "config.json")) as f:
        return json.load(f)


def test_layer_parameters(cfg):
    # q, k 3840 x 2880 each, v, z 3840 x 5760 each: 66.36M; b, a 0.23M;
    # conv 11520 x 4; out 5760 x 3840 = 22.12M; FFN 3 x 3840 x 11008.
    assert sh.ffn_params(cfg) == 126_812_160
    assert sh.linear_layer_params(cfg) == 66_355_200 + 230_400 + 46_080 \
        + 22_118_400 + 126_812_160 == 215_562_240
    assert sh.full_layer_params(cfg) == 4 * 3840 ** 2 + 126_812_160 \
        == 185_794_560


def test_the_cut_and_the_whole_model(cfg):
    d = sh.dims(cfg)
    assert (d["linear"], d["full"]) == (12, 4)
    # 12 x 215.56M + 4 x 185.79M + 770.7M = 4.10B, 8.20 GB in bf16.
    assert sh.param_count(cfg) == 12 * 215_562_240 + 4 * 185_794_560 \
        + 2 * 100352 * 3840 == 4_100_628_480
    whole = dict(cfg, num_hidden_layers=32,
                 layer_types=cfg["layer_types"] * 2)
    assert round(sh.param_count(whole) / 1e9, 2) == 7.43
    # A step reads every layer and the head, not the embedding table.
    assert sh.step_weight_bytes(cfg) == 2 * (4_100_628_480 - 100352 * 3840)


def test_state_and_kv(cfg):
    # 30 heads x 96 x 192 float32 = 2.21 MB a layer, 26.5 MB over 12;
    # conv 11520 channels x 3 tokens bf16, 0.83 MB over 12.
    assert sh.recurrent_bytes_per_seq_layer(cfg) == 30 * 96 * 192 * 4
    assert sh.conv_bytes_per_seq_layer(cfg) == 11520 * 3 * 2
    assert sh.state_bytes_per_seq(cfg) == 12 * (2_211_840 + 69_120) \
        == 27_371_520
    assert sh.state_step_bytes_per_row_layer(cfg) == 2 * (2_211_840 + 69_120)
    # K/V of the 4 full layers only: 4 x 2 x 30 x 128 x 2 B = 60 KiB.
    assert sh.kv_bytes_per_token(cfg) == 60 * 1024


def test_a_decode_step_at_20_rows(cfg):
    """ISSUE 31's least time: weights + 20 rows' state + their K/V."""
    work = sh.decode_step(cfg, rows=20, context=490)
    weights = sh.step_weight_bytes(cfg)
    state = 20 * 12 * 2 * (2_211_840 + 69_120)
    kv = 20 * 491 * 60 * 1024
    assert work["bytes"] == weights + state + kv
    assert round(state / 1e9, 2) == 1.09 and round(kv / 1e9, 2) == 0.60
    assert sh.gdn_step(cfg, row_steps=20)["bytes"] == state


def test_the_chunked_recurrence_is_memory_bound_by_this_count(cfg):
    work = sh.gdn_chunk(cfg, tokens=2048)
    per_head = 6 * 64 * 96 + 4 * 64 * 192 + 6 * 96 * 192 + 2 * 64 * 64 / 3
    assert work["flops"] == pytest.approx(2048 * 12 * 30 * per_head)
    assert work["bytes"] == 2048 * 12 * 30 * (2 * 96 + 2 * 192) * 4
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
