"""Int8 quantized KV-cache path (--kv-cache-dtype int8, docs/PERF.md round 7).

Covers the acceptance bars of the quantization PR:
  * quantize/dequantize round-trip error bounded by the symmetric-int8 step
    (half a scale unit per element, scale stored in bf16 FIRST);
  * wire serde exactness — an int8 block (payload + per-slot scales)
    offloads and restores BIT-identically, PKV1 blobs from pre-quantization
    stores still decode, and the disagg handoff manifest carries the
    kv_cache_dtype tag end-to-end;
  * pool sizing — an int8 pool derives >= 1.8x the blocks of a bf16 pool at
    equal HBM budget (paged attention; measured 1.98x at Dh=128);
  * the bench roofline's KV term follows the KV-cache dtype (pure-function
    math pinned for bf16 vs int8);
  * kernel + engine parity — the quantized Pallas flash-decode kernel
    matches the XLA reference on a dequantized pool, the window and paged
    read paths produce IDENTICAL greedy tokens from the same int8 pool, and
    the greedy exact-match rate vs a bf16 pool is measured and
    floor-asserted (not silently pinned at 100% — random-weight tiny models
    flip near-tie argmaxes far more than trained checkpoints; the measured
    rates are recorded in docs/PERF.md round 7).
"""

import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models.config import resolve_model_config
from production_stack_tpu.ops.quantization import (
    SCALE_DTYPE,
    dequantize_kv,
    quantize_kv,
)

# ------------------------------------------------------------------ quantizer

def test_quantize_roundtrip_error_bound():
    """Per-element reconstruction error <= half a quantization step (the
    stored bf16 scale is what q is computed against, so there is no hidden
    extra error), and the scale equals bf16(max|x| / 127) per (slot, head)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 2, 32, 64)).astype(np.float32) * \
        rng.uniform(0.01, 30.0, size=(4, 2, 32, 1)).astype(np.float32)
    q, scale = quantize_kv(jnp.asarray(x))
    assert np.asarray(q).dtype == np.int8
    assert scale.dtype == SCALE_DTYPE
    amax = np.max(np.abs(x), axis=-1)
    np.testing.assert_array_equal(
        np.asarray(scale, np.float32),
        np.asarray(jnp.asarray(amax / 127.0).astype(SCALE_DTYPE), np.float32),
    )
    deq = np.asarray(dequantize_kv(q, scale, jnp.float32))
    sf = np.asarray(scale, np.float32)[..., None]
    # round() contributes s/2; clipping the amax element (when bf16 rounds
    # the scale DOWN) contributes at most one bf16 ulp of amax (2^-8).
    bound = 0.5 * sf + np.abs(x) * 2.0 ** -8 + 1e-7
    assert np.all(np.abs(deq - x) <= bound)


def test_quantize_edge_cases():
    # All-zero rows keep scale 0 / payload 0 and reconstruct exact zeros
    # (the reserved null block must never produce NaNs via 0/0).
    q, s = quantize_kv(jnp.zeros((2, 3, 8)))
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s, np.float32) == 0)
    assert np.all(np.asarray(dequantize_kv(q, s, jnp.float32)) == 0)
    # The max-magnitude element always lands on +-127.
    x = jnp.asarray([[0.5, -2.0, 1.0, 0.0]])
    q, s = quantize_kv(x)
    assert int(np.max(np.abs(np.asarray(q, np.int32)))) == 127


# ----------------------------------------------------------------- wire serde

def test_serde_pkv2_roundtrip_bit_exact():
    from production_stack_tpu.kv_offload.serde import pack_block, unpack_block
    import ml_dtypes

    rng = np.random.default_rng(1)
    k = rng.integers(-127, 128, size=(2, 2, 4, 8), dtype=np.int8)
    v = rng.integers(-127, 128, size=(2, 2, 4, 8), dtype=np.int8)
    ks = rng.random((2, 2, 4)).astype(ml_dtypes.bfloat16)
    vs = rng.random((2, 2, 4)).astype(ml_dtypes.bfloat16)
    k2, v2, ks2, vs2 = unpack_block(pack_block(k, v, ks, vs))
    for a, b in ((k, k2), (v, v2), (ks, ks2), (vs, vs2)):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


def test_serde_pkv1_backcompat():
    """Blobs written by a bf16 engine (pre-quantization stores) decode with
    None scales — the bf16 wire format is unchanged."""
    from production_stack_tpu.kv_offload.serde import pack_block, unpack_block
    import ml_dtypes

    k = np.arange(2 * 2 * 4 * 8, dtype=np.float32).reshape(2, 2, 4, 8)
    k = k.astype(ml_dtypes.bfloat16)
    v = (k * 2).astype(ml_dtypes.bfloat16)
    blob = pack_block(k, v)
    assert blob[:4] == b"PKV1"
    k2, v2, ks2, vs2 = unpack_block(blob)
    assert ks2 is None and vs2 is None
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))


def test_manifest_roundtrip_int8():
    from production_stack_tpu.disagg.transfer import (
        HandoffManifest,
        pack_manifest,
        unpack_manifest,
    )
    import ml_dtypes

    rng = np.random.default_rng(2)
    n, nl, hkv, bs, dh = 3, 2, 2, 4, 8
    mani = HandoffManifest(
        request_id="r1", prompt_token_ids=[1, 2, 3], output_token_ids=[7],
        num_computed_tokens=3, block_size=bs, model="m",
        kv_cache_dtype="int8",
        k=rng.integers(-127, 128, size=(n, nl, hkv, bs, dh), dtype=np.int8),
        v=rng.integers(-127, 128, size=(n, nl, hkv, bs, dh), dtype=np.int8),
        k_scale=rng.random((n, nl, hkv, bs)).astype(ml_dtypes.bfloat16),
        v_scale=rng.random((n, nl, hkv, bs)).astype(ml_dtypes.bfloat16),
    )
    out = unpack_manifest(pack_manifest(mani))
    assert out.kv_cache_dtype == "int8"
    np.testing.assert_array_equal(out.k, mani.k)
    np.testing.assert_array_equal(out.v, mani.v)
    np.testing.assert_array_equal(
        np.asarray(out.k_scale), np.asarray(mani.k_scale)
    )
    np.testing.assert_array_equal(
        np.asarray(out.v_scale), np.asarray(mani.v_scale)
    )


async def test_handoff_dtype_mismatch_rejected():
    """An int8 decode engine must refuse a bf16 prefill bundle (the
    reconstruction would differ from what the prefill engine computed);
    the router turns the raised error into a degrade-to-unified retry."""
    from production_stack_tpu.disagg.transfer import HandoffManifest
    from production_stack_tpu.engine.engine import ServingEngine

    eng = ServingEngine(EngineConfig(
        model="tiny-llama", max_model_len=128, block_size=4,
        num_kv_blocks=32, attn_impl="xla", kv_cache_dtype="int8",
    ))
    mani = HandoffManifest(
        request_id="r1", prompt_token_ids=[1, 2, 3], output_token_ids=[7],
        num_computed_tokens=3, block_size=4, model="m",
        kv_cache_dtype="bfloat16",
    )
    gen = eng._generate_from_handoff(
        mani, SamplingParams(max_tokens=4), "r1"
    )
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        await gen.__anext__()


# -------------------------------------------------------------- pool sizing

def test_kv_cache_bytes_per_token_formula():
    mc = resolve_model_config("tiny-llama")
    per_tok = {
        dt: EngineConfig(kv_cache_dtype=dt).kv_cache_bytes_per_token(mc)
        for dt in ("bfloat16", "int8")
    }
    nl, hkv, dh = mc.num_layers, mc.num_kv_heads, mc.head_dim_
    assert per_tok["bfloat16"] == 2 * nl * hkv * dh * 2
    assert per_tok["int8"] == 2 * nl * hkv * (dh + 2)  # + per-slot bf16 scale
    # The overhead-adjusted capacity win: 2*Dh/(Dh+2) — 1.94x at Dh=64.
    assert per_tok["bfloat16"] / per_tok["int8"] >= 1.8
    # Unquantized pools store the COMPUTE dtype: a float32 pool costs 4
    # B/element, not bf16's 2 (block derivation would otherwise allocate
    # 2x the HBM budget on --dtype float32 engines).
    f32 = EngineConfig(dtype="float32").kv_cache_bytes_per_token(mc)
    assert f32 == 2 * per_tok["bfloat16"]


def test_config_rejects_unknown_kv_cache_dtype():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        EngineConfig(kv_cache_dtype="fp8").kv_cache_quantized


def test_int8_pool_derives_more_blocks():
    """Acceptance bar: at equal HBM budget (CPU probe falls back to a
    deterministic 2 GiB) the derived int8 paged pool holds >= 1.8x the
    blocks of the bf16 pool, and engine.stats() exposes the derived pool
    bytes + dtype."""
    from production_stack_tpu.engine.engine import ServingEngine

    blocks, pool_bytes = {}, {}
    for dt in ("bfloat16", "int8"):
        eng = ServingEngine(EngineConfig(
            model="tiny-llama-128dh", max_model_len=512, block_size=16,
            num_kv_blocks=None, attn_impl="paged", dtype="float32",
            max_num_seqs=512, kv_cache_dtype=dt, hbm_utilization=0.002,
        ))
        blocks[dt] = eng.runner.num_kv_blocks
        pool_bytes[dt] = eng.runner.kv_pool_bytes
        s = eng.stats()
        assert s["kv_cache_dtype"] == dt
        assert s["kv_pool_bytes"] == pool_bytes[dt]
        assert s["kv_num_blocks"] == blocks[dt]
    assert blocks["int8"] >= 1.8 * blocks["bfloat16"]
    # Same budget: the int8 pool's DERIVED bytes stay within it.
    mc = resolve_model_config("tiny-llama-128dh")
    assert pool_bytes["int8"] == blocks["int8"] * EngineConfig(
        kv_cache_dtype="int8", block_size=16
    ).kv_cache_bytes_per_block(mc)


# ------------------------------------------------------------- roofline math

def test_roofline_components_pinned():
    """bench.roofline_components is a pure function: weight bytes follow the
    COMPUTE dtype, the KV term follows the KV-CACHE dtype; int8 roughly
    doubles the roofline once context depth dominates."""
    import bench

    mc = resolve_model_config("tiny-llama")
    d, f, v = mc.hidden_size, mc.intermediate_size, mc.vocab_size
    dh, h, hkv, nl = mc.head_dim_, mc.num_heads, mc.num_kv_heads, \
        mc.num_layers
    per_layer = d * (h * dh) + 2 * d * (hkv * dh) + (h * dh) * d + 3 * d * f
    embed = v * d * (1 if mc.tie_word_embeddings else 2)
    expected_params = (nl * per_layer + embed) * 2.0

    comp = bench.roofline_components(
        "tiny-llama", 2.0, "bfloat16", batch=8, avg_ctx=1024, peak_gbs=819.0
    )
    assert comp["param_bytes"] == expected_params
    assert comp["kv_bytes_per_token"] == 2 * nl * hkv * dh * 2
    expected = 819.0e9 / (
        expected_params / 8 + comp["kv_bytes_per_token"] * 1024
    )
    assert comp["roofline_tok_s"] == pytest.approx(expected)

    comp8 = bench.roofline_components(
        "tiny-llama", 2.0, "int8", batch=8, avg_ctx=1024, peak_gbs=819.0
    )
    assert comp8["kv_bytes_per_token"] == 2 * nl * hkv * (dh + 2)
    assert comp8["kv_cache_dtype"] == "int8"
    # Depth-dominant regime: the KV term is ~all the traffic, so the int8
    # roofline approaches the byte ratio (1.94x at Dh=64).
    deep_bf = bench.roofline_components(
        "tiny-llama", 2.0, "bfloat16", batch=256, avg_ctx=16384,
        peak_gbs=819.0,
    )
    deep_i8 = bench.roofline_components(
        "tiny-llama", 2.0, "int8", batch=256, avg_ctx=16384, peak_gbs=819.0,
    )
    assert deep_i8["roofline_tok_s"] / deep_bf["roofline_tok_s"] > 1.8


# ------------------------------------------------------------ kernel parity

def test_quantized_pallas_matches_dequantized_reference():
    """The Pallas flash-decode kernel's in-kernel rank-1 dequantization must
    match the XLA reference attention run over an explicitly dequantized
    pool (interpret mode on CPU). Includes a partially-filled superpage
    (80 < 512 tokens) so the scale-window padding path is exercised."""
    from production_stack_tpu.ops.attention import paged_attention_xla
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_stats,
    )

    rng = np.random.default_rng(0)
    L, Hkv, H, Dh, bs = 2, 2, 4, 64, 16
    B, Mb = 3, 5
    num_slots = 32 * bs
    kf = rng.standard_normal((L, Hkv, num_slots, Dh)).astype(np.float32)
    vf = rng.standard_normal((L, Hkv, num_slots, Dh)).astype(np.float32)
    kq, ks = quantize_kv(jnp.asarray(kf))
    vq, vs = quantize_kv(jnp.asarray(vf))
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    bt = jnp.asarray(
        rng.choice(np.arange(1, 32), size=(B, Mb), replace=False), jnp.int32
    )
    lens = jnp.asarray([80, 33, 1], jnp.int32)

    out, m, l = paged_flash_decode_stats(
        q, kq, vq, bt, lens, jnp.zeros((1,), jnp.int32),
        block_size=bs, interpret=True, k_scale=ks, v_scale=vs,
    )
    kd = dequantize_kv(kq, ks, jnp.float32)[0]
    vd = dequantize_kv(vq, vs, jnp.float32)[0]
    ref = paged_attention_xla(
        q[:, None], kd, vd, bt, lens, (lens - 1)[:, None], block_size=bs
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref[:, 0]), atol=1e-4
    )


def test_gather_window_dequantizes_exactly():
    """The window gather over an int8 pool reconstructs the same values as
    dequantizing the whole pool first — all read paths share one
    dequantization arithmetic (ops/quantization.py:dequantize_kv)."""
    from production_stack_tpu.ops.attention import gather_window

    rng = np.random.default_rng(3)
    L, Hkv, Dh, bs = 2, 2, 8, 4
    num_slots = 16 * bs
    x = rng.standard_normal((L, Hkv, num_slots, Dh)).astype(np.float32)
    y = rng.standard_normal((L, Hkv, num_slots, Dh)).astype(np.float32)
    kq, ks = quantize_kv(jnp.asarray(x))
    vq, vs = quantize_kv(jnp.asarray(y))
    bt = jnp.asarray([[1, 3, 5], [2, 4, 6]], jnp.int32)
    wk, wv = gather_window(kq, vq, bt, bs, ks, vs, out_dtype=jnp.float32)
    kd = dequantize_kv(kq, ks, jnp.float32)
    vd = dequantize_kv(vq, vs, jnp.float32)
    wk_ref, wv_ref = gather_window(kd, vd, bt, bs)
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(wk_ref))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(wv_ref))


# ----------------------------------------------------------- engine parity

PARITY_PROMPTS = [
    f"hello world this is request {i} " * (i + 1) for i in range(4)
]
# Greedy exact-match floor vs the bf16 pool, on random-weight tiny models
# (near-uniform logits flip argmax near-ties far more than trained
# checkpoints do). Measured on this prompt set: mean tokenwise match 0.70,
# 1/4 sequences exact at 24 tokens (docs/PERF.md round 7); floor set with
# margin. NOT asserted at 100% by design.
TOKENWISE_MATCH_FLOOR = 0.35


async def _generate_all(engine, prompts, max_tokens=24):
    outs = {}

    async def one(i, p):
        toks = []
        async for o in engine.generate(
            prompt=p,
            sampling=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
        ):
            toks = o.token_ids
        outs[i] = toks

    await asyncio.gather(*[one(i, p) for i, p in enumerate(prompts)])
    return outs


@pytest.mark.slow
async def test_engine_int8_parity_and_readpath_consistency():
    """The parity bar for the quantized path, on the existing parity prompt
    set: (1) window and paged read paths over the SAME int8 pool produce
    IDENTICAL greedy tokens (all readers reconstruct the same values —
    deterministic); (2) the greedy match rate vs a bf16 pool is measured
    and floor-asserted (TOKENWISE_MATCH_FLOOR above documents why it is
    not 100%)."""
    from production_stack_tpu.engine.engine import ServingEngine

    results = {}
    for impl, dt in (
        ("window", "bfloat16"), ("window", "int8"), ("paged", "int8"),
    ):
        eng = ServingEngine(EngineConfig(
            model="tiny-llama-128dh", max_model_len=256, num_kv_blocks=128,
            attn_impl=impl, num_decode_steps=8, dtype="float32",
            kv_cache_dtype=dt,
        ))
        await eng.start()
        try:
            results[(impl, dt)] = await _generate_all(eng, PARITY_PROMPTS)
        finally:
            await eng.stop()
        if dt == "int8":
            assert eng.stats()["kv_quant_bytes_saved_total"] > 0

    # (1) read-path consistency: same int8 pool contents -> same tokens.
    assert results[("window", "int8")] == results[("paged", "int8")]

    # (2) measured greedy match rate vs bf16 (reported, floor-asserted).
    bf, i8 = results[("window", "bfloat16")], results[("window", "int8")]
    rates = []
    for i in range(len(PARITY_PROMPTS)):
        a, b = bf[i], i8[i]
        rates.append(
            sum(x == y for x, y in zip(a, b)) / max(len(a), len(b))
        )
    exact = sum(bf[i] == i8[i] for i in range(len(PARITY_PROMPTS)))
    mean_rate = sum(rates) / len(rates)
    print(f"int8-vs-bf16 greedy: exact {exact}/{len(PARITY_PROMPTS)}, "
          f"tokenwise {mean_rate:.3f} {rates}")
    assert mean_rate >= TOKENWISE_MATCH_FLOOR
    # The FIRST token of every sequence comes from prefill logits computed
    # on unquantized in-chunk KV — it must always match bf16.
    for i in range(len(PARITY_PROMPTS)):
        assert bf[i][0] == i8[i][0]


@pytest.mark.slow
async def test_engine_int8_paged_tp2_matches_tp1():
    """tp=2 shards the int8 pools AND their scale sidecars over kv heads
    (parallel/sharding.py:kv_scale_sharding); the shard_mapped kernel must
    dequantize local heads with local scales — same greedy tokens as the
    single-device int8 paged engine."""
    from production_stack_tpu.engine.engine import ServingEngine

    prompts = PARITY_PROMPTS[:3]
    results = {}
    for tp in (1, 2):
        eng = ServingEngine(EngineConfig(
            model="tiny-llama-128dh", max_model_len=256, num_kv_blocks=128,
            attn_impl="paged", num_decode_steps=8, dtype="float32",
            kv_cache_dtype="int8", tensor_parallel_size=tp,
        ))
        await eng.start()
        try:
            results[tp] = await _generate_all(eng, prompts, max_tokens=16)
        finally:
            await eng.stop()
    assert results[1] == results[2]


async def _gen(engine, prompt, n=4):
    last = None
    async for out in engine.generate(
        prompt=prompt,
        sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                ignore_eos=True),
    ):
        last = out
    return last


async def test_engine_offload_spill_restore_int8_bit_exact():
    """kv_offload round-trip with an int8 pool: blocks spill int8 + scales
    over the wire (PKV2, ~half the bf16 bytes) and restore BIT-identically
    — the greedy continuation after a device-cache wipe equals the fully
    recomputed one."""
    import time

    from production_stack_tpu.engine.engine import ServingEngine

    cfg = EngineConfig(
        model="tiny-llama", max_model_len=256, block_size=4,
        num_kv_blocks=128, max_num_seqs=4, max_num_batched_tokens=64,
        attn_impl="xla", kv_offload_cpu=True, kv_offload_max_cpu_gb=0.5,
        kv_cache_dtype="int8",
    )
    engine = ServingEngine(cfg)
    engine.offload.flush_interval = 0.02
    await engine.start()
    try:
        shared = "offload shared prefix " * 4
        out_a = await _gen(engine, shared + "userA")
        deadline = time.time() + 10
        while time.time() < deadline and \
                engine.offload.spilled_blocks_total < 10:
            await asyncio.sleep(0.05)
        assert engine.offload.spilled_blocks_total >= 10
        # Offload store keys are namespaced by dtype: int8 blobs live under
        # q8| so a bf16 engine sharing the tier can never splice them.
        assert engine.offload._store_key(b"h") == b"q8|h"
        engine.block_manager.reset_prefix_cache()

        restored_before = engine.offload.restored_tokens_total
        out_b = await _gen(engine, shared + "userB")
        assert engine.offload.restored_tokens_total > restored_before
        assert out_b.num_cached_tokens > 0

        out_a2 = await _gen(engine, shared + "userA")
        assert out_a2.token_ids == out_a.token_ids
    finally:
        await engine.stop()
