"""``benchmarks/chip/lib/shapes_sambay.py`` against hand counts at
phi-4-mini-flash's published widths and against the served tree: the
operations and bytes of a SambaY decoder, which ``lib/shapes.py`` cannot
count (it reckons every layer a dense llama layer with its own K/V)."""

import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.chip.lib import shapes  # noqa: E402
from benchmarks.chip.lib import shapes_sambay as ss  # noqa: E402

CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "phi-4-mini-flash")
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


def test_the_benchmarks_reference_is_the_tests_reference():
    assert filecmp.cmp(
        os.path.join(ROOT, "tests", "reference", "phi4flash_ref.py"),
        os.path.join(CONFIG_DIR, "reference.py"), shallow=False)


def test_dims(cfg):
    d = ss.dims(cfg)
    assert (d["s6"], d["ring"], d["full"], d["gmu"], d["cross"],
            d["readers"]) == (9, 8, 1, 7, 7, 8)
    assert d["s6"] + d["ring"] + d["full"] + d["gmu"] + d["cross"] == \
        d["layers"] == 32
    assert (d["hidden"], d["ffn"], d["vocab"], d["q"], d["kv"]) == \
        (2560, 10240, 200064, 2560, 1280)
    assert (d["inner"], d["n"], d["conv_width"], d["rank"], d["window"]) == \
        (5120, 16, 4, 160, 512)


def test_the_parts_by_hand(cfg):
    """ISSUE 54's count: FFN 78.64 M a layer, an S6 mixer 41.2 M,
    self-attention 19.7 M, cross attention 13.1 M, a memory unit 26.2 M, the
    table 512.2 M."""
    assert ss.ffn_params(cfg) == 3 * 2560 * 10240 + 2 * 2560 == 78_648_320
    s6 = ss.s6_params(cfg)
    assert s6["bf16"] == (2560 * 10240 + 5120 * 5 + 5120 * 192
                          + 160 * 5120 + 5120 * 2560 + 2 * 2560)
    assert s6["f32"] == 5120 * 16 + 2 * 5120
    assert sum(s6.values()) == pytest.approx(41.2e6, rel=0.005)
    att = ss.self_attention_params(cfg)
    assert att["bf16"] == (2560 * 5120 + 5120 + 2560 * 2560 + 2560
                           + 2 * 2560 + 128)
    assert att["f32"] == 256
    assert sum(att.values()) == pytest.approx(19.7e6, rel=0.005)
    assert sum(ss.cross_attention_params(cfg).values()) == \
        2 * (2560 * 2560 + 2560) + 2 * 2560 + 128 + 256
    assert ss.gmu_params(cfg) == 2 * 2560 * 5120 + 2 * 2560
    assert ss.param_count(cfg) == 3_852_562_944
    assert ss.step_weight_bytes(cfg) == pytest.approx(7.70e9, rel=0.002)


def test_the_served_tree_has_these_parameters(cfg):
    import jax

    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import ModelConfig

    mc = ModelConfig.from_hf_config(cfg)
    tree = jax.eval_shape(
        lambda: get_model(mc).init_params(mc, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(tree)
    assert sum(x.size for x in leaves) == ss.param_count(cfg)
    # bf16 but for A_log, D, b_dt and the lambda vectors.
    f32 = ss.mixer_params(cfg)["f32"]
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        2 * ss.param_count(cfg) + 2 * f32
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        ss.step_weight_bytes(cfg)
    specs = get_model(mc).cache_specs(mc)
    kv = specs.paged_kv
    assert (kv.layers, kv.kv_heads, kv.head_dim) == (1, 10, 128)
    assert ss.kv_bytes_per_token(cfg) == 2 * 10 * 128 * 2 == 5120
    state = {s.name: s for s in specs.state}
    itemsize = {"float32": 4, None: 2}
    held = sum(s.layers * itemsize[s.dtype]
               * (s.shape[0] * s.shape[1] * (s.shape[2]
                                             if len(s.shape) > 2 else 1))
               for s in specs.state)
    assert held == ss.state_bytes_per_seq(cfg) == 24_197_120
    assert state["s6"].layers * 16 * 5120 * 4 == \
        9 * ss.s6_state_bytes_per_seq_layer(cfg)
    assert state["ring_k"].layers * 2 * 10 * 512 * 128 * 2 == \
        ss.ring_bytes_per_seq(cfg)


def test_what_a_sequence_keeps_and_what_a_dense_model_would(cfg):
    """5 KB a token paged (a dense model of this shape pages 32 layers: 160
    KB); 21 MB of rings, 2.9 MB of scan state and 0.3 MB of conv inputs a
    sequence whatever its length."""
    assert ss.kv_bytes_per_token(cfg) * 32 == \
        shapes.kv_bytes_per_token(cfg) == 163_840
    assert ss.ring_bytes_per_seq(cfg) == 8 * 512 * 5120 == 20_971_520
    assert 9 * ss.s6_state_bytes_per_seq_layer(cfg) == 2_949_120
    assert 9 * ss.conv_bytes_per_seq_layer(cfg) == 276_480


@pytest.mark.parametrize("rows,context", [(48, 1100), (1, 300), (32, 4000)])
def test_a_decode_step_is_bound_by_its_bytes(cfg, rows, context):
    """ISSUE 54's count at 48 rows and a context of 1.1 k: 7.70 GB of
    weights and 72 MB a row of what this architecture adds (the paged layer
    8 times over 45 MB, eight rings 21 MB, nine states read and written 5.9
    MB): 11.2 GB, 13.6 ms at the HBM peak."""
    work = ss.decode_step(cfg, rows, context)
    least = shapes.least_seconds(work, PEAK)
    assert least["bound"] == "memory"
    per_row = (8 * context * 5120 + 8 * (min(context, 512) + 1) * 5120
               + 9 * 2 * (16 * 5120 * 4 + 3 * 5120 * 2) + 5120)
    assert work["bytes"] == ss.step_weight_bytes(cfg) + rows * per_row
    if (rows, context) == (48, 1100):
        assert per_row == pytest.approx(72e6, rel=0.02)
        assert work["bytes"] == pytest.approx(11.2e9, rel=0.005)
        assert least["seconds"] == pytest.approx(13.66e-3, rel=0.005)
        # 32 paged layers would read more in keys alone.
        assert 48 * 1100 * 163_840 > 8.4e9 > rows * per_row


def test_the_shared_layer_is_read_once_a_reader(cfg):
    one = ss.shared_kv_attend(cfg, 1, 1000)
    assert one["bytes"] == 8 * 1000 * 5120
    # 40 packed heads of 128 lanes score and sum 2 d lanes each: 8 q d a
    # key and reader.
    assert one["flops"] == 8 * 8 * 2560 * 1000
    assert ss.shared_kv_attend(cfg, 96, 1000)["bytes"] == 96 * one["bytes"]
    assert shapes.least_seconds(one, PEAK)["bound"] == "memory"


def test_the_rings_hold_512_keys_whatever_the_context(cfg):
    short, long, longer = (ss.ring_attend(cfg, 10, c)
                           for c in (100, 512, 4000))
    assert short["bytes"] == 10 * 8 * 101 * 5120
    assert long["bytes"] == longer["bytes"] == 10 * 8 * 513 * 5120


def test_the_scan_is_counted_by_its_bytes(cfg):
    """The scan's 6 N D operations a token and layer are the vector
    unit's; against the matrix unit's peak they never bind, so the shares
    are the bytes' (PERF.md section 3)."""
    step = ss.s6_step(cfg, 48)
    per = 2 * (16 * 5120 * 4 + 3 * 5120 * 2) + (4 * 5120 + 32) * 4
    assert step["bytes"] == 48 * 9 * per
    assert step["flops"] == 48 * 9 * 6 * 16 * 5120
    assert shapes.least_seconds(step, PEAK)["bound"] == "memory"
    chunk = ss.s6_chunk(cfg, 2048)
    assert chunk["bytes"] == 2048 * 9 * (3 * 5120 + 32) * 4
    assert chunk["flops"] == 2048 * 9 * 6 * 16 * 5120
    assert shapes.least_seconds(chunk, PEAK)["bound"] == "memory"


def test_the_dense_arithmetic_would_not_read_this_cells_truth(cfg):
    """What ``lib/shapes.py``'s count WOULD read here (the cell is listed
    in none of its three metrics): every layer a dense layer with its own
    K/V of 20 heads: 32 paged layers' keys where one is read 8 times."""
    dense = shapes.decode_step(cfg, 48, 1100)
    true = ss.decode_step(cfg, 48, 1100)
    assert dense["bytes"] != pytest.approx(true["bytes"], rel=0.2)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = "phi-4-mini-flash.reasoning-saturated"
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in ("decode_roofline_pct", "prefill_mfu_pct",
                 "decode_step_ms"):
        assert cell not in by_name[name]["workloads"]
