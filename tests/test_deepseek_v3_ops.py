"""The pieces the DeepSeek-V3 family brought (ops/moe.py, the grouped
matmul, the paged decode kernel over latent rows, the absorbed attention of
models/deepseek_v3.py), each against a form one can read off the equations:
hand-written routing cases, a dense einsum over every expert masked by the
routing, the expanded attention of the plain reference, ``attend``'s XLA
path. float32 on the CPU throughout, so the tolerances are those of a
reordered sum."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import deepseek_v3 as ds
from production_stack_tpu.models.config import TINY_DEEPSEEK_V3, LatentKVSpec
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import KVView, attend, gather_window
from production_stack_tpu.ops.pallas import grouped_matmul as gmm
from production_stack_tpu.ops.pallas.paged_attention import (
    paged_flash_decode_latent_stats,
    supports_latent_decode,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import deepseek_v3_ref as ref  # noqa: E402

SCALING = 2.448


# ---- the router, by hand ------------------------------------------------------
def _route(logits, bias, k=2, norm=True):
    """Route ONE token whose router logits are ``logits``: x = e_0, so that
    x W_r is W_r's first row."""
    e = len(logits)
    x = jnp.zeros((1, 4), jnp.float32).at[0, 0].set(1.0)
    w_r = jnp.zeros((4, e), jnp.float32).at[0].set(jnp.asarray(logits))
    idx, w = moe.route(x, w_r, jnp.asarray(bias, jnp.float32), k, SCALING,
                       norm)
    return [int(i) for i in idx[0]], np.asarray(w[0])


def test_router_weights_are_the_scores_normalised_and_scaled():
    logits = [2.0, -1.0, 0.5, 1.0]
    idx, w = _route(logits, [0.0] * 4)
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    assert idx == [0, 3]
    np.testing.assert_allclose(w, s[[0, 3]] / s[[0, 3]].sum() * SCALING,
                               rtol=1e-6)
    assert abs(w.sum() - SCALING) < 1e-5


def test_router_bias_changes_the_choice_and_not_the_weight():
    logits = [2.0, -1.0, 0.5, 1.0]
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    # Expert 2 (score 0.62) overtakes expert 3 (0.73) by its bias alone.
    idx, w = _route(logits, [0.0, 0.0, 0.2, 0.0])
    assert sorted(idx) == [0, 2]
    got = dict(zip(idx, w))
    # Its weight is its SCORE's share, not (score + bias)'s.
    np.testing.assert_allclose(
        [got[0], got[2]], s[[0, 2]] / s[[0, 2]].sum() * SCALING, rtol=1e-6)
    with_bias = (s[2] + 0.2) / (s[0] + s[2] + 0.2) * SCALING
    assert abs(got[2] - with_bias) > 0.05


def test_router_without_normalisation_scales_the_raw_scores():
    logits = [2.0, -1.0, 0.5, 1.0]
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    idx, w = _route(logits, [0.0] * 4, norm=False)
    np.testing.assert_allclose(w, s[idx] * SCALING, rtol=1e-6)


def test_router_computes_in_float32_whatever_it_is_given():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 16), jnp.bfloat16)
    w_r = jax.random.normal(jax.random.PRNGKey(1), (16, 8), jnp.float32)
    idx, w = moe.route(x, w_r, jnp.zeros((8,)), 3, SCALING)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(w.sum(-1)), SCALING, rtol=1e-5)


# ---- the experts: sorted runs against a dense einsum -----------------------------
def _dense_experts(x, idx, w, valid, w_gate_up, w_down):
    """Every expert for every token, weighted by the routing: zero where an
    expert was not chosen."""
    e, f = w_down.shape[0], w_down.shape[1]
    with jax.default_matmul_precision("highest"):
        h = jnp.einsum("nd,edf->enf", x, w_gate_up)
        out = jnp.einsum("enf,efd->end",
                         jax.nn.silu(h[..., :f]) * h[..., f:], w_down)
        dense = jnp.zeros((x.shape[0], e)).at[
            jnp.arange(x.shape[0])[:, None], idx].add(w)
        dense = jnp.where(valid[:, None], dense, 0.0)
        return jnp.einsum("ne,end->nd", dense, out)


def _experts_case(case, n=24, d=32, f=16, e=8, k=3):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (n, d), jnp.float32)
    w_gate_up = jax.random.normal(ks[1], (e, d, 2 * f), jnp.float32) * d ** -.5
    w_down = jax.random.normal(ks[2], (e, f, d), jnp.float32) * f ** -.5
    w = jax.random.uniform(ks[3], (n, k), jnp.float32, 0.1, 1.0)
    valid = jnp.ones((n,), bool)
    if case == "spread":
        idx = jnp.stack([jax.random.permutation(kk, e)[:k]
                         for kk in jax.random.split(ks[4], n)])
    elif case == "one-expert-starved-one-takes-all":
        # Expert 0 is every token's first choice; expert 5 nobody's.
        rest = jnp.asarray([[1, 2], [3, 4], [6, 7], [2, 6]])
        idx = jnp.concatenate([jnp.zeros((n, 1), jnp.int32),
                               rest[jnp.arange(n) % 4]], axis=1)
    else:                                   # padded rows reach no expert
        idx = jnp.stack([jax.random.permutation(kk, e)[:k]
                         for kk in jax.random.split(ks[4], n)])
        valid = jnp.arange(n) % 3 != 1
    return x, idx.astype(jnp.int32), w, valid, w_gate_up, w_down


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "pallas-interpreted"])
@pytest.mark.parametrize("case", [
    "spread", "one-expert-starved-one-takes-all", "some-tokens-not-valid"])
def test_expert_ffn_equals_every_expert_masked_by_the_routing(case,
                                                              interpret):
    x, idx, w, valid, w_gate_up, w_down = _experts_case(case)
    y, stats = moe.expert_ffn(x, idx, w, valid, w_gate_up, w_down,
                              interpret=interpret)
    want = _dense_experts(x, idx, w, valid, w_gate_up, w_down)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # The counters: pairs of the valid tokens, experts with a token, the
    # busiest expert's tokens, one call.
    live = np.asarray(idx)[np.asarray(valid)]
    counts = np.bincount(live.reshape(-1), minlength=w_down.shape[0])
    assert [int(s) for s in stats] == [
        live.size, int((counts > 0).sum()), int(counts.max()), 1]
    if case != "spread":
        assert bool(jnp.all(y[~valid] == 0.0))
    if case.startswith("one-expert"):
        assert counts[5] == 0 and counts[0] == x.shape[0]


def test_grouped_matmul_with_a_group_base_reads_the_right_layer():
    """A layer's groups sit at ``layer * E`` of the whole stack: the rows
    of layer 1 meet layer 1's matrices."""
    x, idx, w, valid, w_gate_up, w_down = _experts_case("spread")
    e = w_down.shape[0]
    stack_gu = jnp.concatenate([jnp.zeros_like(w_gate_up), w_gate_up])
    stack_dn = jnp.concatenate([jnp.zeros_like(w_down), w_down])
    y, stats = moe.expert_ffn(x, idx + e, w, valid, stack_gu, stack_dn)
    want, _ = moe.expert_ffn(x, idx, w, valid, w_gate_up, w_down)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6)
    assert int(stats[1]) <= e


def test_grouped_matmul_tiles():
    # Published widths: 2048 -> 2 x 768 and 768 -> 2048, bf16.
    assert gmm.tiling(144, 2048, 1536) == (128, 2048, 512)
    assert gmm.tiling(24, 768, 2048)[0] == 32
    tm, tk, tn = gmm.tiling(2048 * 6, 768, 2048)
    assert (tm, tk) == (128, 768) and 2048 % tn == 0
    assert tk * tn * 2 <= gmm.RHS_BLOCK_BYTES


# ---- absorbed attention is the expanded form -------------------------------------
def test_absorbed_attention_equals_the_expanded_form():
    """``models/deepseek_v3.py:_attention`` (queries carried into the latent
    space, scores and values over the cached ROW; it returns its BRANCH, the
    residual is its caller's) against the reference's expanded keys and
    values of every head, one layer, one sequence."""
    mc = TINY_DEEPSEEK_V3
    params = ds.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    lp = jax.tree.map(lambda x: x[0], params["layers"]["dense"])
    t = 19
    hidden = jax.random.normal(jax.random.PRNGKey(4), (1, t, mc.hidden_size))
    positions = jnp.arange(t, dtype=jnp.int32)[None]
    rope = ds._rope_cos_sin(positions, mc.qk_rope_head_dim, mc.rope_theta)
    with jax.default_matmul_precision("highest"):
        got, row = ds._attention(mc, rope, positions, jnp.asarray([t]),
                                 hidden, lp, KVView(), None)
        cfg = {"num_attention_heads": mc.num_heads,
               "qk_nope_head_dim": mc.qk_nope_head_dim,
               "qk_rope_head_dim": mc.qk_rope_head_dim,
               "kv_lora_rank": mc.kv_lora_rank, "rope_theta": mc.rope_theta,
               "rms_norm_eps": mc.rms_norm_eps}
        x = ref.rms_norm(hidden[0], lp["attn_norm"], mc.rms_norm_eps)
        want = ref.attention(cfg, lp, x)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # What goes to the cache: [c | k_r | zeros] of the pool's width.
    spec = LatentKVSpec(mc.kv_lora_rank, mc.qk_rope_head_dim)
    assert row.shape == (1, t, 1, spec.width)
    assert bool(jnp.all(row[..., mc.kv_lora_rank + mc.qk_rope_head_dim:] == 0))


# ---- the paged kernel over latent rows -------------------------------------------
def _latent_case(b=3, h=4, rank=128, rope=8, block_size=16, mb=6):
    width = LatentKVSpec(rank, rope).width
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    n_blocks = b * mb + 1
    pool = jax.random.normal(ks[0], (2, 1, n_blocks * block_size, width))
    pool = pool.at[..., rank + rope:].set(0.0)
    tables = (1 + jnp.arange(b * mb, dtype=jnp.int32)).reshape(b, mb)
    tables = jax.random.permutation(ks[1], tables.reshape(-1)).reshape(b, mb)
    kv_lens = jnp.asarray([37, 0, 96], jnp.int32)[:b]
    q = jax.random.normal(ks[2], (b, 1, h, width))
    q = q.at[..., rank + rope:].set(0.0)
    row = jax.random.normal(ks[3], (b, 1, 1, width))
    row = row.at[..., rank + rope:].set(0.0)
    return pool, tables, kv_lens, q, row, rank, block_size


def test_latent_kernel_interpreted_equals_attends_xla_path():
    """One decode step over a latent pool: ``attend`` with the pool (the
    Pallas kernel, interpreted, merged with the step's own row) against
    ``attend`` with the same rows gathered into a window (XLA)."""
    pool, tables, kv_lens, q, row, rank, bs = _latent_case()
    positions = kv_lens[:, None]
    ones = jnp.ones((q.shape[0],), jnp.int32)
    scale = 0.11
    empty_v = pool[..., :0]
    for layer in (0, 1):
        paged = attend(q, row, None, positions, ones, KVView(
            pool_k=pool, pool_v=empty_v, block_tables=tables,
            kv_lens=kv_lens, block_size=bs, interpret=True),
            jnp.int32(layer), scale=scale, value_dim=rank)
        win_k, _ = gather_window(pool, empty_v, tables, bs)
        window = attend(q, row, None, positions, ones,
                        KVView(win_k[layer], None, kv_lens),
                        scale=scale, value_dim=rank)
        assert paged.shape == (q.shape[0], 1, q.shape[2], rank)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(window),
                                   atol=2e-5, rtol=2e-5)


def test_latent_kernel_row_without_history_returns_nothing():
    pool, tables, kv_lens, q, _, rank, bs = _latent_case()
    out, m, l = paged_flash_decode_latent_stats(
        q[:, 0], pool, tables, kv_lens, jnp.int32(0), block_size=bs,
        value_dim=rank, scale=0.11, interpret=True)
    assert bool(jnp.all(out[1] == 0)) and bool(jnp.all(l[1] == 0))
    assert bool(jnp.all(jnp.isneginf(m[1])))
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.all(l[0] > 0))


def test_latent_kernel_supports_whole_lanes_only():
    assert supports_latent_decode(640, 512, 16)
    assert supports_latent_decode(256, 128, 16)
    assert not supports_latent_decode(576, 512, 16)     # a row of 4.5 tiles
    assert not supports_latent_decode(128, 32, 16)      # values cut a tile
