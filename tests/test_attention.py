"""Paged attention vs. a dense reference implementation, and the seam the
models attend through (``attend`` over a ``KVView``) vs. the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import (
    KVView,
    attend,
    dense_decode_stats,
    gather_window,
    merge_attention_segments,
    paged_attention_xla,
    window_attention,
    write_kv_to_pool,
)

BLOCK = 4


def dense_attention(q, k, v, kv_len, q_positions):
    """q: [T,H,Dh]; k/v: [S,Hkv,Dh] already laid out in sequence order."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    k = np.repeat(k, g, axis=1)
    v = np.repeat(v, g, axis=1)
    scale = dh**-0.5
    scores = np.einsum("thd,shd->hts", q * scale, k).astype(np.float32)
    s = k.shape[0]
    mask = (np.arange(s)[None, :] <= q_positions[:, None]) & (
        np.arange(s)[None, :] < kv_len
    )
    scores = np.where(mask[None], scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", probs, v)


def test_paged_matches_dense_decode_and_prefill():
    rng = np.random.default_rng(0)
    hkv, h, dh = 2, 4, 8
    num_blocks = 10
    pool_shape = (hkv, num_blocks * BLOCK, dh)  # head-major
    k_pool = jnp.asarray(rng.normal(size=pool_shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=pool_shape), jnp.float32)

    # Sequence of 10 tokens in blocks [3, 7, 5] (page order = sequence order).
    blocks = [3, 7, 5]
    kv_len = 10
    block_tables = jnp.array([blocks + [0]], jnp.int32)  # padded width 4

    # Dense copies of the live KV, slot order -> sequence order.
    slots = [b * BLOCK + o for b in blocks for o in range(BLOCK)][:kv_len]
    k_seq = np.asarray(k_pool)[:, slots].transpose(1, 0, 2)  # [S, Hkv, Dh]
    v_seq = np.asarray(v_pool)[:, slots].transpose(1, 0, 2)

    # --- decode: 1 query at position kv_len-1
    q = jnp.asarray(rng.normal(size=(1, 1, h, dh)), jnp.float32)
    out = paged_attention_xla(
        q, k_pool, v_pool, block_tables,
        jnp.array([kv_len], jnp.int32),
        jnp.array([[kv_len - 1]], jnp.int32),
        block_size=BLOCK,
    )
    ref = dense_attention(
        np.asarray(q)[0], k_seq, v_seq, kv_len, np.array([kv_len - 1])
    )
    np.testing.assert_allclose(np.asarray(out)[0], ref, rtol=2e-4, atol=2e-4)

    # --- prefill chunk: queries at positions 6..9 (causal within chunk)
    q4 = jnp.asarray(rng.normal(size=(1, 4, h, dh)), jnp.float32)
    out4 = paged_attention_xla(
        q4, k_pool, v_pool, block_tables,
        jnp.array([kv_len], jnp.int32),
        jnp.array([[6, 7, 8, 9]], jnp.int32),
        block_size=BLOCK,
    )
    ref4 = dense_attention(
        np.asarray(q4)[0], k_seq, v_seq, kv_len, np.array([6, 7, 8, 9])
    )
    np.testing.assert_allclose(np.asarray(out4)[0], ref4, rtol=2e-4, atol=2e-4)


def test_write_kv_to_pool_scatter_and_null_block():
    hkv, dh = 2, 4
    k_pool = jnp.zeros((hkv, 8 * BLOCK, dh))
    v_pool = jnp.zeros((hkv, 8 * BLOCK, dh))
    k_new = jnp.ones((1, 3, hkv, dh))
    v_new = 2 * jnp.ones((1, 3, hkv, dh))
    # Two real tokens into block 2, one padding token to slot 0.
    slot_mapping = jnp.array([[2 * BLOCK, 2 * BLOCK + 1, 0]], jnp.int32)
    k_pool, v_pool = write_kv_to_pool(k_pool, v_pool, k_new, v_new, slot_mapping)
    assert np.asarray(k_pool)[:, 2 * BLOCK].sum() == hkv * dh
    assert np.asarray(v_pool)[:, 2 * BLOCK + 1].sum() == 2 * hkv * dh
    # Null block received the padding write (harmless by design).
    assert np.asarray(k_pool)[:, 0].sum() == hkv * dh
    # Nothing else touched.
    assert np.asarray(k_pool)[:, 3 * BLOCK:].sum() == 0


# ------------------------------------------------------- the attention seam
# Head dim 32 at block size 16: the smallest shape the Pallas decode kernel
# admits (supports_pallas_decode), run in interpret mode.
_L, _HKV, _H, _DH, _BS, _MB, _B, _R = 2, 2, 4, 32, 16, 4, 2, 4
_KV_LENS = np.array([37, 20], np.int32)


def _seam_inputs(t, seed=0):
    r = np.random.default_rng(seed)

    def f32(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)

    slots = (1 + _B * _MB) * _BS
    tables = jnp.asarray(
        1 + np.arange(_B * _MB, dtype=np.int32).reshape(_B, _MB))
    kv_lens = jnp.asarray(_KV_LENS)
    return dict(
        q=f32(_B, t, _H, _DH), k=f32(_B, t, _HKV, _DH),
        v=f32(_B, t, _HKV, _DH),
        positions=kv_lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None],
        chunk_lens=jnp.full((_B,), t, jnp.int32),
        pool_k=f32(_L, _HKV, slots, _DH), pool_v=f32(_L, _HKV, slots, _DH),
        tables=tables, kv_lens=kv_lens,
        ring_k=f32(_HKV, _B, _R, _DH), ring_v=f32(_HKV, _B, _R, _DH),
        # Two ring entries written (positions before the query), two not.
        ring_pos=jnp.asarray(
            np.stack([_KV_LENS - 2, _KV_LENS - 1,
                      np.full(_B, 2**30), np.full(_B, 2**30)], 1),
            jnp.int32),
    )


@pytest.mark.parametrize(
    "case", ["window", "window+ring", "pool+ring", "pool+ring-int8",
             "chunk_bias"])
def test_attend_equals_the_kernel_it_should_choose(case):
    """``attend`` picks from what the view holds: over every view the runner
    builds it returns, bit for bit, what the kernel for that view returns
    when called directly (float32, seeded)."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_stats,
    )
    from production_stack_tpu.ops.quantization import quantize_kv

    layer = jnp.int32(1)
    t = 3 if case == "chunk_bias" else 1
    x = _seam_inputs(t)
    q, k, v = x["q"], x["k"], x["v"]
    ring = dict(ring_k=x["ring_k"], ring_v=x["ring_v"],
                ring_pos=x["ring_pos"])
    if case.startswith("pool"):
        pool_k, pool_v, ks, vs = x["pool_k"], x["pool_v"], None, None
        if case.endswith("int8"):
            pool_k, ks = quantize_kv(pool_k)
            pool_v, vs = quantize_kv(pool_v)
        view = KVView(pool_k=pool_k, pool_v=pool_v, k_scale=ks, v_scale=vs,
                      block_tables=x["tables"], kv_lens=x["kv_lens"],
                      block_size=_BS, interpret=True, **ring)
        q2 = q.reshape(_B, _H, _DH)
        seg_p = paged_flash_decode_stats(
            q2, pool_k, pool_v, x["tables"], x["kv_lens"], layer,
            block_size=_BS, interpret=True, k_scale=ks, v_scale=vs)
        neg = jnp.finfo(jnp.float32).min
        bias = jnp.concatenate(
            [jnp.where(x["ring_pos"] < x["positions"], 0.0, neg),
             jnp.zeros((_B, 1), jnp.float32)], axis=1)
        seg_d = dense_decode_stats(
            q2,
            jnp.concatenate([x["ring_k"], k.transpose(2, 0, 1, 3)], axis=2),
            jnp.concatenate([x["ring_v"], v.transpose(2, 0, 1, 3)], axis=2),
            bias)
        want = merge_attention_segments(*seg_p, *seg_d).reshape(
            _B, 1, _H, _DH)
    else:
        win_k, win_v = gather_window(
            x["pool_k"], x["pool_v"], x["tables"], _BS)
        win = (win_k[1], win_v[1], x["kv_lens"])
        if case == "window":
            ring = {}
        bias = None
        if case == "chunk_bias":
            # Tokens 1 and 2 are siblings: neither attends the other.
            bias = jnp.zeros((t, t), jnp.float32).at[2, 1].set(
                jnp.finfo(jnp.float32).min)
        view = KVView(*win, chunk_bias=bias, **ring)
        want = window_attention(
            q, k, v, x["positions"], x["chunk_lens"], *win,
            ring.get("ring_k"), ring.get("ring_v"), ring.get("ring_pos"),
            chunk_bias=bias)
    got = attend(q, k, v, x["positions"], x["chunk_lens"], view, layer)
    assert got.dtype == jnp.float32 and got.shape == (_B, t, _H, _DH)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_opt_forward_over_a_pool_view_equals_its_window():
    """Any model runs whatever the view holds: tiny-opt (head dim 32) decodes
    one token against the pool through the Pallas kernel and against the
    gathered window of the same pool, and the two agree."""
    from production_stack_tpu.models import get_model
    from production_stack_tpu.models.config import TINY_OPT as cfg

    assert (cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_) == \
        (_L, _HKV, _DH)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = _seam_inputs(1, seed=1)
    tokens = jnp.asarray([[5], [9]], jnp.int32)
    ones = jnp.ones((_B,), jnp.int32)
    win_k, win_v = gather_window(x["pool_k"], x["pool_v"], x["tables"], _BS)
    views = {
        "pool": KVView(
            pool_k=x["pool_k"], pool_v=x["pool_v"], block_tables=x["tables"],
            kv_lens=x["kv_lens"], block_size=_BS, interpret=True),
        "window": KVView(win_k, win_v, x["kv_lens"]),
    }
    out = {
        name: model.forward(params, cfg, tokens, x["positions"], ones, view)
        for name, view in views.items()
    }
    for got, want in zip(out["pool"], out["window"]):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
