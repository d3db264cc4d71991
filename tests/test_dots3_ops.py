"""The operations the dots3-note family adds (ops/attention.py): the
indexer's scores and the radix select that finds a query's top-k without a
sort, the selected-row attention over every view a runner builds (nothing, a
gathered window, the pool for a chunk and for a decode step: one result),
the window ring over LATENT rows at a window that divides no chunk, and the
selected sets against the reference's. tests/test_dots3.py says what the
engine is held to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import dots3_note as m
from production_stack_tpu.models.config import TINY_DOTS3
from production_stack_tpu.ops import attention as A
from tests.dots3_helpers import hf_config, ref

CFG = TINY_DOTS3
RNG = np.random.default_rng(5)


def _normal(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


# ------------------------------------------------------------ the selection
def _top_k_sets(scores, visible, k):
    """``lax.top_k``'s sets (ties to the lower index), as a mask."""
    masked = jnp.where(visible, scores, -jnp.inf)
    _, which = jax.lax.top_k(masked, min(k, scores.shape[-1]))
    mask = jnp.zeros(scores.shape, bool)
    b, t = np.indices(scores.shape[:2])
    mask = mask.at[b[..., None], t[..., None], which].set(True)
    return mask & visible


@pytest.mark.parametrize("k", [1, 7, 48, 200])
@pytest.mark.parametrize("ties", [False, True])
def test_the_radix_select_is_top_k(k, ties):
    """Signed scores, keys no query sees, fewer visible keys than k, and
    (``ties``) scores of a few values only, so that every cut falls inside
    a run of equals: the lower position wins, as ``lax.top_k`` has it."""
    b, t, n = 2, 9, 160
    scores = _normal(b, t, n)
    if ties:
        scores = jnp.round(scores * 2) / 2
    pos_k = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    pos_q = jnp.asarray(RNG.integers(0, n, (b, t)), jnp.int32)
    visible = pos_k[:, None, :] <= pos_q[:, :, None]
    got = A.topk_mask(scores, pos_k, visible, k)
    np.testing.assert_array_equal(got, _top_k_sets(scores, visible, k))
    assert int(got.sum(-1).max()) <= k


def test_ties_go_to_the_lower_position_whatever_the_slot_order():
    """Keys laid out of position order (a chunk's behind its history's
    slots): the tie is cut by position, not by slot."""
    scores = jnp.zeros((1, 1, 8), jnp.float32)
    pos_k = jnp.asarray([[5, 6, 7, 0, 1, 2, 3, 4]], jnp.int32)
    got = A.topk_mask(scores, pos_k, jnp.ones((1, 1, 8), bool), 3)
    assert sorted(np.asarray(pos_k[0])[np.asarray(got[0, 0])]) == [0, 1, 2]


def test_index_scores_in_blocks_are_the_statement(monkeypatch):
    q, w, k = _normal(2, 24, 3, 16), _normal(2, 24, 3), _normal(2, 40, 16)
    whole = jnp.einsum("bth,bthk->btk", w, jax.nn.relu(
        jnp.einsum("bthd,bkd->bthk", q, k)))
    np.testing.assert_allclose(A.index_scores(q, w, k), whole, atol=1e-5)
    monkeypatch.setattr(A, "SCORE_BLOCK_BYTES", 2 * 3 * 40 * 4 * 4)
    assert A._query_block(24, 2 * 3 * 40 * 4) == 4
    np.testing.assert_allclose(A.index_scores(q, w, k), whole, atol=1e-5)


def test_the_selected_sets_are_the_references():
    """The full layers' selections of the module's whole forward
    (``routing=True`` returns them beside the chosen experts) against the
    reference's, whole rows of them, layer by layer: inputs drawn at random
    lie away from ties, and in float32 an earlier layer's equal sets leave
    the next layer's inputs equal."""
    from tests.dots3_helpers import prompt

    t = 150
    params = m.init_params(CFG, jax.random.PRNGKey(2), jnp.float32)
    tokens = prompt(t, 4)
    *_, masks = m.forward(
        params, CFG, jnp.asarray([tokens]),
        jnp.arange(t, dtype=jnp.int32)[None], jnp.asarray([t]),
        routing=True)
    theirs = []
    ref.forward(params, hf_config(CFG), tokens, selection=theirs)
    assert masks.shape == (3, 1, t, t) and len(theirs) == 3
    for ours, want in zip(masks[:, 0], theirs):
        np.testing.assert_array_equal(ours, want)
        assert int(ours[-1].sum()) == CFG.index_topk
    # Neither the newest keys nor anything a position alone decides.
    newest = int(masks[0, 0, -1, -CFG.index_topk:].sum())
    assert CFG.index_topk // 8 < newest < CFG.index_topk


# --------------------------------------------- one result over every view
H, W_ROW, RANK, DI, HI, BS = 4, 256, 128, 128, 2, 16


def _sequence(n):
    """A sequence's absorbed queries, rows, index keys and indexer
    operands."""
    return (_normal(1, n, H, W_ROW) / 8, _normal(1, n, 1, W_ROW),
            _normal(1, n, 1, DI), _normal(1, n, HI, DI), _normal(1, n, HI))


def _paged(rows, k_idx, held, mb):
    """``rows`` [n, W] and index keys [n, Di] of one sequence, its first
    ``held`` in the two pools of two layers (layer 1 is read) by a shuffled
    block table."""
    blocks = mb + 3
    table = jnp.asarray(RNG.permutation(np.arange(1, blocks))[:mb],
                        jnp.int32)[None]
    pools = [np.zeros((2, 1, blocks * BS, x.shape[-1]), np.float32)
             for x in (rows, k_idx)]
    for s in range(held):
        at = int(table[0, s // BS]) * BS + s % BS
        pools[0][1, 0, at], pools[1][1, 0, at] = rows[s], k_idx[s]
    return jnp.asarray(pools[0]), jnp.asarray(pools[1]), table


KW = dict(scale=0.1, value_dim=RANK, topk=24)


@pytest.mark.parametrize("cuts", [False, True])
def test_a_chunk_over_the_pool_or_a_window_is_the_whole_sequence(
        monkeypatch, cuts):
    """89 tokens at once against 57 in the cache and a chunk of 32 padded
    to 40: over the pool (the layer's pages gathered), over a gathered
    window, with and without the history's cut chosen at run time."""
    if cuts:
        monkeypatch.setattr(A, "HISTORY_CUT_FLOOR", 16)
    n, held, t = 89, 57, 40
    q, rows, k_idx, q_idx, w_idx = _sequence(n + 8)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    whole, seen = A.attend_selected_latent(
        q[:, :n], rows[:, :n], k_idx[:, :n], q_idx[:, :n], w_idx[:, :n],
        pos, jnp.asarray([n]), A.KVView(), **KW)
    assert int(seen[0]) == n * (n + 1) // 2
    assert int(seen[1]) == sum(min(p + 1, 24) for p in range(n))
    chunk = slice(held, held + t)
    args = (q[:, chunk], rows[:, chunk], k_idx[:, chunk], q_idx[:, chunk],
            w_idx[:, chunk], held + jnp.arange(t, dtype=jnp.int32)[None],
            jnp.asarray([n - held]))
    pool, pool_idx, table = _paged(np.asarray(rows[0, :, 0]),
                                   np.asarray(k_idx[0, :, 0]), held, 8)
    over_pool, seen_p = A.attend_selected_latent(
        *args, A.KVView(pool_k=pool, pool_v=pool_idx, block_tables=table,
                        kv_lens=jnp.asarray([held]), block_size=BS),
        jnp.int32(1), **KW)
    win = jnp.zeros((1, 1, 128, W_ROW)).at[0, 0, :held].set(
        rows[0, :held, 0])
    win_idx = jnp.zeros((1, 1, 128, DI)).at[0, 0, :held].set(
        k_idx[0, :held, 0])
    over_win, seen_w = A.attend_selected_latent(
        *args, A.KVView(win_k=win, win_v=win_idx,
                        win_len=jnp.asarray([held])), **KW)
    for got in (over_pool, over_win):
        np.testing.assert_allclose(got[0, :n - held], whole[0, held:n],
                                   atol=2e-5)
    np.testing.assert_array_equal(seen_p, seen_w)
    assert int(seen_p[0]) == sum(range(held + 1, n + 1))


@pytest.mark.parametrize("cuts", [False, True])
def test_a_decode_step_reads_what_it_selected(monkeypatch, cuts):
    """A step at position 70: 60 rows in the pool, 10 in the train's ring
    (6 written), the token itself; the same as the whole sequence's last
    query, a second row that is not live counts nothing."""
    if cuts:
        monkeypatch.setattr(A, "HISTORY_CUT_FLOOR", 16)
    held, ring_n, n = 60, 6, 67
    q, rows, k_idx, q_idx, w_idx = _sequence(n)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    whole, _ = A.attend_selected_latent(
        q, rows, k_idx, q_idx, w_idx, pos, jnp.asarray([n]), A.KVView(),
        **KW)
    pool, pool_idx, table = _paged(np.asarray(rows[0, :, 0]),
                                   np.asarray(k_idx[0, :, 0]), held, 8)
    ring = jnp.zeros((1, 2, 10, W_ROW)).at[0, 0, :ring_n].set(
        rows[0, held:held + ring_n, 0])
    ring_idx = jnp.zeros((1, 2, 10, DI)).at[0, 0, :ring_n].set(
        k_idx[0, held:held + ring_n, 0])
    ring_pos = jnp.full((2, 10), 1 << 30, jnp.int32).at[0, :ring_n].set(
        held + jnp.arange(ring_n))
    two = lambda x: jnp.concatenate([x[:, -1:], x[:, -1:]], axis=0)  # noqa
    got, seen = A.attend_selected_latent(
        two(q), two(rows), two(k_idx), two(q_idx), two(w_idx),
        jnp.asarray([[n - 1], [n - 1]], jnp.int32), jnp.asarray([1, 0]),
        A.KVView(pool_k=pool, pool_v=pool_idx,
                 block_tables=jnp.concatenate([table, table]),
                 kv_lens=jnp.asarray([held, held]), ring_k=ring,
                 ring_v=ring_idx, ring_pos=ring_pos, block_size=BS),
        jnp.int32(1), **KW)
    np.testing.assert_allclose(got[0, 0], whole[0, -1], atol=2e-5)
    np.testing.assert_array_equal(seen, [n, 24])


# ----------------------------------------------- the ring over latent rows
def _dense_window(q, rows, w, scale, value_dim):
    """Every query of one sequence against its W newest rows, densely."""
    t = q.shape[1]
    s = jnp.einsum("thd,jd->htj", q[0], rows[0, :, 0]) * scale
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((j <= i) & (i - j < w), s, -jnp.inf)
    return jnp.einsum("htj,jd->thd", jax.nn.softmax(s, -1),
                      rows[0, :, 0, :value_dim])


@pytest.mark.parametrize("t", [128, 64, 50])
def test_a_latent_ring_at_a_window_that_divides_no_chunk(t):
    """A window of 33 latent rows: a first chunk of 70 tokens into an empty
    ring (stored wider than the row), then ``t`` more: 128 and 64 in blocks
    of 32 queries, 50 as one; each the dense statement over the whole
    sequence, and the ring left is the 33 newest rows."""
    w, d, vd, lanes, first = 33, 80, 64, 128, 70
    n = first + t
    q, rows = _normal(1, n, 2, d) / 4, _normal(1, n, 1, d)
    want = _dense_window(q, rows, w, 0.3, vd)
    rings = (jnp.zeros((1, 3, 1, w, lanes)),)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    at = jnp.int32(2)
    for lo, hi in ((0, first), (first, n)):
        got = A.window_ring_attend(
            q[:, lo:hi], rows[:, lo:hi], None, pos[:, lo:hi],
            jnp.asarray([hi - lo]), rings[0][:, 2], scale=0.3, value_dim=vd)
        np.testing.assert_allclose(got[0], want[lo:hi], atol=2e-5)
        rings = A.window_ring_write(rings, at, (rows[:, lo:hi],),
                                    pos[:, lo:hi], jnp.asarray([hi - lo]))
    for p in range(n - w, n):
        np.testing.assert_array_equal(rings[0][0, 2, 0, p % w, :d],
                                      rows[0, p, 0])
    assert not np.any(np.asarray(rings[0][0, :2])) \
        and not np.any(np.asarray(rings[0][..., d:]))
    # One more token as a decode step: attend, then write, in one call.
    nxt_q, nxt = _normal(1, 1, 2, d) / 4, _normal(1, 1, 1, d)
    got, after = A.window_ring_step(
        rings, at, nxt_q, nxt, None, jnp.asarray([[n]]), jnp.asarray([1]),
        scale=0.3, value_dim=vd)
    full_q = jnp.concatenate([q, nxt_q], 1)
    full_rows = jnp.concatenate([rows, nxt], 1)
    np.testing.assert_allclose(
        got[0, 0], _dense_window(full_q, full_rows, w, 0.3, vd)[-1],
        atol=2e-5)
    np.testing.assert_array_equal(after[0][0, 2, 0, n % w, :d], nxt[0, 0, 0])


def test_the_older_rings_blocks_are_as_they_were():
    """A window that divides its chunk (MiMo's 128, Phi-4-mini-flash's 512)
    keeps the blocks it had: the new rule is asked only where none did."""
    q, k, v = _normal(1, 256, 4, 16), _normal(1, 256, 2, 16), \
        _normal(1, 256, 2, 8)
    ring = (jnp.zeros((1, 2, 128, 16)), jnp.zeros((1, 2, 128, 8)))
    out = A.window_ring_attend(
        q, k, v, jnp.arange(256, dtype=jnp.int32)[None], jnp.asarray([256]),
        *ring, scale=0.25)
    s = jnp.einsum("thd,jhd->htj", q[0], jnp.repeat(k[0], 2, 1)) * 0.25
    i, j = jnp.arange(256)[:, None], jnp.arange(256)[None, :]
    s = jnp.where((j <= i) & (i - j < 128), s, -jnp.inf)
    want = jnp.einsum("htj,jhd->thd", jax.nn.softmax(s, -1),
                      jnp.repeat(v[0], 2, 1))
    np.testing.assert_allclose(out[0], want, atol=2e-5)
