"""Pallas flash-decode kernel numerics vs the XLA reference (interpret mode
runs the kernel's exact dataflow — DMAs, double buffering, online softmax —
on CPU)."""

import numpy as np
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.attention import paged_attention_xla
from production_stack_tpu.ops.pallas.paged_attention import (
    paged_attention_decode_pallas,
    paged_flash_decode_stats,
    supports_pallas_decode,
)
from production_stack_tpu.ops.quantization import dequantize_kv, quantize_kv


def test_supports_gate():
    assert supports_pallas_decode(128, 16)
    assert supports_pallas_decode(256, 32)
    assert supports_pallas_decode(64, 16)        # lane-packed (2 tok/row)
    assert supports_pallas_decode(32, 16)        # lane-packed (4 tok/row)
    assert not supports_pallas_decode(96, 16)    # 128 not divisible by dh
    assert not supports_pallas_decode(128, 48)   # bs doesn't divide superpage
    assert not supports_pallas_decode(32, 2)     # bs < pack factor


def test_decode_kernel_matches_xla_interpret():
    rng = np.random.default_rng(0)
    b, h, hkv, dh, bs, mb = 3, 8, 4, 128, 16, 40
    num_blocks = 64
    num_slots = num_blocks * bs
    q = jnp.asarray(rng.standard_normal((b, 1, h, dh)), jnp.float32)
    k_pool = jnp.asarray(
        rng.standard_normal((hkv, num_slots, dh)), jnp.float32
    )
    v_pool = jnp.asarray(
        rng.standard_normal((hkv, num_slots, dh)), jnp.float32
    )
    bt = np.zeros((b, mb), np.int32)
    for i in range(b):
        bt[i] = rng.choice(np.arange(1, num_blocks), mb, replace=False)
    block_tables = jnp.asarray(bt)
    # Lengths hit: tail partial page, single token, >1 superpage.
    kv_lens = jnp.asarray([37, 1, 520], jnp.int32)
    q_pos = (kv_lens - 1)[:, None]

    ref = paged_attention_xla(
        q, k_pool, v_pool, block_tables, kv_lens, q_pos, block_size=bs
    )
    out = paged_attention_decode_pallas(
        q, k_pool, v_pool, block_tables, kv_lens,
        block_size=bs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# Row-to-row hand-over of the superpage buffers. The TPU interpreter starts
# VMEM scratch as NaN (a real call finds whatever the last kernel left), and
# completes a DMA only at its wait, so a row that reads a buffer nobody
# fetched or cleared for it, or that waits on the wrong semaphore, shows.

BS = 16
MAX_BLOCKS = 82      # one table width for every case: fewer programs to trace
HEADS, KV_HEADS, LAYERS = 4, 2, 2
_RAGGED = np.random.default_rng(7).integers(1, 700, 20).tolist()

ROW_SEQUENCES = {
    "empty-first": [0, 37, 600],
    "empty-between": [300, 0, 0, 513],
    "empty-last": [520, 40, 0],
    "all-empty-but-one": [0, 0, 77, 0],
    "three-superpages-then-one-token": [1300, 1],
    "one-token-then-three-superpages": [1, 1300],
    "kv-len-1-16-511": [1, 16, 511],
    "kv-len-512-513-1024": [512, 513, 1024],
    "32-rows-12-empty": _RAGGED + [0] * 12,
}
POOLS = {"bf16": (jnp.bfloat16, 128), "int8": (jnp.int8, 128),
         "dh64": (jnp.bfloat16, 64)}


def _paged_case(lens, dh, seed):
    """Queries, float pools whose blocks are scattered (each row owns
    shuffled blocks, block 0 is the null block every padded table entry
    points at) and the mask of blocks some live row owns."""
    rng = np.random.default_rng(seed)
    b, mb = len(lens), MAX_BLOCKS
    num_blocks = 1 + b * mb
    order = 1 + rng.permutation(b * mb).reshape(b, mb)
    bt = np.zeros((b, mb), np.int32)
    owned = np.zeros(num_blocks, bool)
    for i, n in enumerate(lens):
        pages = -(-n // BS)
        bt[i, :pages] = order[i, :pages]
        owned[order[i, :pages]] = True
    shape = (LAYERS, KV_HEADS, num_blocks * BS, dh)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, HEADS, dh)), jnp.float32)
    return q, k, v, jnp.asarray(bt), jnp.asarray(lens, jnp.int32), owned


def _check_rows(got, ref, lens, atol):
    out, m, l = (np.asarray(x) for x in got)
    live = np.asarray(lens) > 0
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], atol=atol)
    assert np.all(np.isfinite(m[live])) and np.all(l[live] > 0)
    # An empty row is a no-op under the merge: (0, -inf, 0).
    assert np.all(out[~live] == 0) and np.all(l[~live] == 0)
    assert np.all(np.isneginf(m[~live]))


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("rows", ROW_SEQUENCES)
def test_row_handover_matches_xla(rows, pool):
    lens = ROW_SEQUENCES[rows]
    dtype, dh = POOLS[pool]
    q, k, v, bt, kv_lens, _ = _paged_case(lens, dh, seed=len(lens))
    layer = 1
    scales = {}
    if dtype == jnp.int8:
        kp, ks = quantize_kv(jnp.asarray(k))
        vp, vs = quantize_kv(jnp.asarray(v))
        scales = {"k_scale": ks, "v_scale": vs}
        k_ref = dequantize_kv(kp, ks, jnp.float32)
        v_ref = dequantize_kv(vp, vs, jnp.float32)
    else:
        kp, vp = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
        k_ref, v_ref = kp.astype(jnp.float32), vp.astype(jnp.float32)
    got = paged_flash_decode_stats(
        q, kp, vp, bt, kv_lens, jnp.int32(layer), block_size=BS,
        interpret=pltpu.InterpretParams(), **scales,
    )
    ref = paged_attention_xla(
        q[:, None], k_ref[layer], v_ref[layer], bt, kv_lens,
        jnp.full((len(lens), 1), 10**6, jnp.int32), block_size=BS,
    )[:, 0]
    _check_rows(got, ref, lens, atol=1e-4)


@pytest.mark.parametrize("dh", [128, 64])
@pytest.mark.parametrize(
    "rows",
    ["three-superpages-then-one-token", "empty-between", "kv-len-1-16-511"],
)
def test_poisoned_pool_never_reaches_a_result(rows, dh):
    """NaN and Inf in every block no live row owns — block 0, which padded
    table entries point at, among them — and in the scratch the call finds:
    a masked key's weight is 0, and 0 * NaN would still poison the row. A
    short row behind a long one computes over a buffer whose tail holds the
    long row's keys; it must not see them either."""
    lens = ROW_SEQUENCES[rows]
    q, k, v, bt, kv_lens, owned = _paged_case(lens, dh, seed=11)
    poison = np.where(np.arange(owned.size) % 2, np.nan, np.inf)
    poison = np.where(np.arange(owned.size) % 3, poison, -np.inf)
    dead = np.repeat(~owned, BS)
    kp, vp = k.copy(), v.copy()
    kp[:, :, dead] = np.repeat(poison[~owned], BS)[None, None, :, None]
    vp[:, :, dead] = np.repeat(poison[~owned], BS)[None, None, :, None]
    layer = 0
    got = paged_flash_decode_stats(
        q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
        bt, kv_lens, jnp.int32(layer), block_size=BS,
        interpret=pltpu.InterpretParams(detect_races=True),
    )
    clean = [jnp.asarray(x[layer], jnp.bfloat16).astype(jnp.float32)
             for x in (k, v)]
    ref = paged_attention_xla(
        q[:, None], *clean, bt, kv_lens,
        jnp.full((len(lens), 1), 10**6, jnp.int32), block_size=BS,
    )[:, 0]
    _check_rows(got, ref, lens, atol=1e-4)
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    assert not interpret_pallas_call.races.races_found
