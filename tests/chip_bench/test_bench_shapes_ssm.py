"""``lib/shapes_ssm.py`` against the figures ISSUE 40 reckoned by hand for
granite-4.0-h-micro (the published widths, all 40 layers) and against the
tree the program serves; the benchmark's copy of the reference against the
tests'."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.lib import shapes_ssm as ss  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "granite-4.0-h-micro")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


def test_the_benchmarks_reference_is_the_tests_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "granite_hybrid_ref.py"),
        os.path.join(CONFIG_DIR, "reference.py"), shallow=False)


def test_dims(cfg):
    d = ss.dims(cfg)
    assert (d["mamba"], d["attention"]) == (36, 4)
    assert (d["q"], d["kv"]) == (2048, 512)
    assert (d["mh"], d["p"], d["n"], d["inner"]) == (64, 64, 128, 4096)
    assert (d["conv_channels"], d["conv_width"], d["chunk"]) == \
        (4352, 4, 256)
    assert d["tied"] and d["ffn"] == 8192


def test_layer_parameters(cfg):
    # in_proj 2048 x 8512, out_proj 4096 x 2048, conv 4352 x 4 + 4352,
    # norm 4096 + A_log, D, dt_bias 3 x 64, FFN 2048 x 16384 + 8192 x 2048,
    # two block norms.
    assert ss.ffn_params(cfg) == 50_331_648
    m = ss.mamba_layer_params(cfg)
    assert m == {"bf16": 17_432_576 + 8_388_608 + 21_760 + 4_096
                 + 50_331_648 + 4_096, "f32": 192}
    assert sum(m.values()) == 76_182_976
    assert ss.attention_layer_params(cfg) == 10_485_760 + 50_331_648 + 4_096 \
        == 60_821_504


def test_the_whole_model(cfg):
    assert ss.param_count(cfg) == 36 * 76_182_976 + 4 * 60_821_504 \
        + 205_520_896 + 2_048 == 3_191_396_096
    # bf16 but for A_log, D and dt_bias: 6.38 GB, 40% of a chip. A step
    # reads all of it: the tied table IS the head.
    assert ss.step_weight_bytes(cfg) == 2 * 3_191_396_096 + 2 * 36 * 192
    assert round(ss.step_weight_bytes(cfg) / 1e9, 2) == 6.38


def test_the_served_tree_has_these_parameters_and_bytes(cfg):
    import jax

    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig

    mc = ModelConfig.from_hf_config(cfg)
    tree = jax.eval_shape(
        lambda: get_model(mc).init_params(mc, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(tree)
    assert sum(x.size for x in leaves) == ss.param_count(cfg)
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        ss.step_weight_bytes(cfg)
    specs = get_model(mc).cache_specs(mc)
    assert sum(
        s.layers * int(__import__("math").prod(s.shape))
        * (4 if s.dtype == "float32" else 2) for s in specs.state) == \
        ss.state_bytes_per_seq(cfg)
    kv = specs.paged_kv
    assert 2 * kv.layers * kv.kv_heads * kv.head_dim * 2 == \
        ss.kv_bytes_per_token(cfg)


def test_state_and_kv(cfg):
    # 64 heads x 64 x 128 float32 = 2.10 MB a layer, 75.5 MB over 36; conv
    # 4352 channels x 3 tokens bf16, 0.94 MB over 36.
    assert ss.ssm_bytes_per_seq_layer(cfg) == 2_097_152
    assert ss.conv_bytes_per_seq_layer(cfg) == 26_112
    assert 36 * 2_097_152 == 75_497_472 and 36 * 26_112 == 940_032
    assert ss.state_bytes_per_seq(cfg) == 76_437_504
    # 33 slots: 2.52 GB of pools; 32 rows carried again: 2.42 GB of state.
    assert round(33 * ss.state_bytes_per_seq(cfg) / 1e9, 2) == 2.52
    # K/V of the 4 attention layers only: 4 x 2 x 8 x 64 x 2 B = 8 KiB.
    assert ss.kv_bytes_per_token(cfg) == 8 * 1024
    assert round(6144 * 16 * ss.kv_bytes_per_token(cfg) / 1e9, 2) == 0.81


@pytest.mark.parametrize("rows", [1, 17, 32])
def test_a_decode_step_is_bound_by_its_bytes(cfg, rows):
    """ISSUE 40's least time at 17 rows: 6.38 GB of weights, 17 x 153 MB
    of state and conv state, 0.07 GB of K/V: 9.05 GB, 11.1 ms."""
    work = ss.decode_step(cfg, rows=rows, context=490)
    weights = ss.step_weight_bytes(cfg)
    state = rows * 2 * 76_437_504
    kv = rows * 491 * 8 * 1024
    assert work["bytes"] == weights + state + kv
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    if rows == 17:
        assert round(state / 1e9, 2) == 2.60 and round(kv / 1e9, 2) == 0.07
        assert round(work["bytes"] / 819e9 * 1e3, 1) == 11.1
        # The state is 29% of a step's bytes.
        assert round(100 * state / work["bytes"]) == 29


def test_the_step_counts_the_scans_state_only(cfg):
    work = ss.ssd_step(cfg, row_steps=17)
    assert work["bytes"] == 17 * 36 * 2 * 2_097_152
    assert work["flops"] == 17 * 36 * 6 * 64 * 64 * 128
    assert work["flops"] / 197e12 < work["bytes"] / 819e9


def test_the_chunked_scan_at_the_published_chunk(cfg):
    work = ss.ssd_chunk(cfg, tokens=2048)
    per_token = 256 * 128 + 64 * (256 * 64 + 4 * 64 * 128)
    assert per_token == 3_178_496
    assert work["flops"] == 2048 * 36 * per_token
    assert work["bytes"] == 2048 * 36 * (2 * 4096 + 2 * 128 + 64) * 4
    # Bound by its float32 operands' bytes by this count (3.1 ms against
    # 1.2 ms of arithmetic at the bf16 peak), and a fiftieth of the model's
    # 6 GFLOP a token: the scan is not where a prefill's arithmetic is.
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    assert round(work["bytes"] / 819e9 * 1e3, 1) == 3.1
    assert 36 * per_token < 6.4e9 / 50
