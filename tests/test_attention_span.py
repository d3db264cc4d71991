"""A layer's SPAN (``attend(..., span=)``: a model's sliding-window
attention, ops/attention.py's module docstring tells it from the engine's
gathered window): the three dense paged kernels under a span, in interpret
mode on the CPU, against their XLA statements (``window_attention`` over
gathered history for a chunk, ``paged_attention_xla`` for decode), which are
themselves held to a mask written out by hand.

A superpage is 512 keys at these shapes (2 KV heads of 128 float32 lanes),
so spans of 24, 512 and 700 lie below, at and above one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import (
    NO_SPAN,
    KVView,
    attend,
    gather_kv_pages,
    keys_in_span,
    paged_attention_xla,
    window_attention,
)
from production_stack_tpu.ops.pallas.paged_attention import (
    paged_flash_decode_stats,
    paged_flash_prefill,
    paged_flash_prefill_packed,
    super_tokens,
)
from test_paged_prefill import (
    ATOL,
    BS,
    DH,
    LAYER,
    _case,
    _pack,
    _window_reference,
)

SUP = super_tokens(2, DH, 4, BS)
SPANS = [24, SUP, 700]


def _spanned_reference(c, span):
    """``window_attention`` under ``span`` over this layer's gathered
    pages (junk made finite first)."""
    kp = jnp.nan_to_num(c["kp"][LAYER])
    vp = jnp.nan_to_num(c["vp"][LAYER])
    return np.asarray(window_attention(
        c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"],
        gather_kv_pages(kp, c["bt"], BS), gather_kv_pages(vp, c["bt"], BS),
        c["kv_lens"], span=jnp.int32(span)))


def _by_hand(c, span):
    """The chunk's attention with the mask written out: key j of the row's
    whole sequence (history then chunk) is seen by the query at position i
    iff j <= i and i - j < span. float64 numpy, a row and a head at a time."""
    bt, hists = np.asarray(c["bt"]), np.asarray(c["kv_lens"])
    clens = np.asarray(c["chunk_lens"])
    q, k, v = (np.asarray(c[x], np.float64) for x in "qkv")
    kp = np.nan_to_num(np.asarray(c["kp"][LAYER], np.float64))
    vp = np.nan_to_num(np.asarray(c["vp"][LAYER], np.float64))
    b, t, h, dh = q.shape
    g = h // k.shape[2]
    out = np.zeros_like(q)
    for r in range(b):
        slots = (bt[r][:, None] * BS + np.arange(BS)[None]).reshape(-1)
        slots = slots[:hists[r]]
        for hd in range(h):
            keys = np.concatenate([kp[hd // g, slots], k[r, :clens[r], hd // g]])
            vals = np.concatenate([vp[hd // g, slots], v[r, :clens[r], hd // g]])
            j = np.arange(len(keys))
            for i in range(clens[r]):
                pos = hists[r] + i
                seen = (j <= pos) & (pos - j < span)
                s = keys[seen] @ q[r, i, hd] * dh ** -0.5
                p = np.exp(s - s.max())
                out[r, i, hd] = p @ vals[seen] / p.sum()
    return out


# ---- the XLA statement is the mask written out by hand
@pytest.mark.parametrize("span", [1, 24, 130, NO_SPAN])
def test_window_attention_under_a_span_is_the_mask_by_hand(span):
    c = _case(128, 4, 2, hists=[0, 37, 300], clens=[100, 128, 60])
    ref, hand = _spanned_reference(c, span), _by_hand(c, span)
    for i, cl in enumerate(np.asarray(c["chunk_lens"])):
        np.testing.assert_allclose(ref[i, :cl], hand[i, :cl], atol=ATOL,
                                   rtol=0)


def test_no_span_is_the_unbounded_program():
    """``span`` None is static: the jaxpr of every execution is the one it
    was; a span no position reaches gives the unbounded numbers."""
    c = _case(128, 4, 2, hists=[0, 37, 300], clens=[100, 128, 60])
    np.testing.assert_array_equal(_spanned_reference(c, NO_SPAN),
                                  np.asarray(_window_reference(c)))
    args = (c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"], c["kp"],
            c["vp"], c["bt"], c["kv_lens"], jnp.int32(LAYER))
    unbounded = paged_flash_prefill(*args, block_size=BS, interpret=True)
    none = paged_flash_prefill(*args, block_size=BS, interpret=True,
                               span=None)
    assert np.array_equal(np.asarray(unbounded), np.asarray(none))
    assert str(jax.make_jaxpr(
        lambda *a: paged_flash_prefill(*a, block_size=BS, span=None))(*args)
    ) == str(jax.make_jaxpr(
        lambda *a: paged_flash_prefill(*a, block_size=BS))(*args))


# ---- the rectangle prefill kernel
@pytest.mark.parametrize("span", SPANS, ids=lambda s: f"span{s}")
@pytest.mark.parametrize("t,hists,clens", [
    # Histories before, at and behind the bound; a padded row.
    (128, [0, 10, 24, 600, 1300], [100, 128, 128, 90, 0]),
    # Several query blocks: later blocks start behind the first's bound.
    (1024, [0, 530], [1024, 700]),
], ids=["histories", "query-blocks"])
def test_rectangle_kernel_under_a_span_matches_window(span, t, hists, clens):
    c = _case(t, 4, 2, hists=hists, clens=clens)
    out = np.asarray(paged_flash_prefill(
        c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"], c["kp"],
        c["vp"], c["bt"], c["kv_lens"], jnp.int32(LAYER), block_size=BS,
        interpret=True, span=jnp.int32(span)))
    ref = _spanned_reference(c, span)
    assert np.all(np.isfinite(out))
    for i, cl in enumerate(clens):
        np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=ATOL,
                                   rtol=0)


# ---- the packed prefill kernel (the path a K/V-only model takes)
@pytest.mark.parametrize("span", SPANS + [NO_SPAN], ids=lambda s: f"span{s}")
@pytest.mark.parametrize("t,hists,clens,sub_block", [
    # Segments whose histories start before, at and behind their bound.
    (256, [0, 24, 700], [100, 60, 90], 64),
    # A segment over several query blocks between two short ones.
    (1024, [1100, 20, 0], [40, 800, 100], 64),
    # One segment that fills the row, behind two superpages of history.
    (512, [1030], [512], None),
], ids=["before-at-behind", "segment-over-blocks", "one-segment"])
def test_packed_kernel_under_a_span_matches_window_a_segment(
        span, t, hists, clens, sub_block):
    c = _case(t, 4, 2, hists=hists, clens=clens)
    q, k, v = _pack(c, clens, t)
    out = np.asarray(paged_flash_prefill_packed(
        q, k, v, jnp.asarray(clens, jnp.int32), c["kp"], c["vp"], c["bt"],
        c["kv_lens"], jnp.int32(LAYER), block_size=BS, interpret=True,
        sub_block=sub_block, span=jnp.int32(span)))
    ref = _spanned_reference(c, span)
    assert np.all(np.isfinite(out))
    at = 0
    for i, cl in enumerate(clens):
        np.testing.assert_allclose(out[0, at:at + cl], ref[i, :cl],
                                   atol=ATOL, rtol=0)
        at += cl


# ---- the decode kernel
def _decode_case(lens, seed=0):
    rng = np.random.default_rng(seed)
    b, hkv, h = len(lens), 2, 4
    mb = -(-max(lens) // BS) + 2
    nblocks = 2 + b * mb
    bt = np.asarray(rng.permutation(np.arange(2, nblocks)).reshape(b, mb),
                    np.int32)
    shape = (2, hkv, nblocks * BS, DH)
    kp = rng.normal(size=shape).astype(np.float32)
    vp = rng.normal(size=shape).astype(np.float32)
    q = rng.normal(size=(b, h, DH)).astype(np.float32)
    return q, kp, vp, bt, np.asarray(lens, np.int32)


def _decode_reference(q, kp, vp, bt, lens, pos, span):
    return np.asarray(paged_attention_xla(
        jnp.asarray(q)[:, None], jnp.asarray(kp[LAYER]),
        jnp.asarray(vp[LAYER]), jnp.asarray(bt), jnp.asarray(lens),
        jnp.asarray(pos)[:, None], block_size=BS,
        span=None if span is None else jnp.int32(span)))[:, 0]


@pytest.mark.parametrize("span", SPANS + [2000, NO_SPAN],
                         ids=lambda s: f"span{s}")
def test_decode_kernel_under_a_span_matches_xla(span):
    """Rows under, at and past the bound, one at a superpage's edge, an
    empty one between live ones; the query sits at position kv_len (no
    ring) or a few past it (a ring holds the steps between)."""
    lens = [10, 24, 25, 0, 512, 513, 1100, 1536]
    q, kp, vp, bt, lens = _decode_case(lens)
    for ahead in (0, 5):
        pos = lens + ahead
        out, m, l = paged_flash_decode_stats(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens), jnp.int32(LAYER),
            block_size=BS, interpret=True,
            kv_lo=jnp.asarray(pos - span + 1, jnp.int32))
        ref = _decode_reference(q, kp, vp, bt, lens, pos, span)
        seen = np.minimum(lens, np.maximum(lens - (pos - span + 1), 0))
        live = np.flatnonzero(seen > 0)
        np.testing.assert_allclose(np.asarray(out)[live], ref[live],
                                   atol=ATOL, rtol=0)
        # A row that sees nothing of the pool is a no-op under the merge.
        dead = np.flatnonzero(seen == 0)
        assert not np.asarray(l)[dead].any()
        assert np.all(np.isneginf(np.asarray(m)[dead]))


def test_decode_kernel_without_a_bound_is_the_program_it_was():
    q, kp, vp, bt, lens = _decode_case([10, 600, 0, 1100])
    args = tuple(jnp.asarray(x) for x in (q, kp, vp, bt, lens)) \
        + (jnp.int32(LAYER),)
    a = paged_flash_decode_stats(*args, block_size=BS, interpret=True)
    b = paged_flash_decode_stats(*args, block_size=BS, interpret=True,
                                 kv_lo=None)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ---- a bounded kernel does not FETCH what it does not score
def _poison_behind(pool, bt, row, upto):
    """NaN in every slot of ``row``'s pages that lie wholly below key
    ``upto`` (a multiple of the superpage)."""
    pool = np.array(pool)
    for blk in bt[row][:upto // BS]:
        pool[:, :, blk * BS:(blk + 1) * BS] = np.nan
    return pool


def test_decode_kernel_fetches_no_superpage_behind_the_bound():
    lens = [1100, 1536, 700]
    q, kp, vp, bt, lens = _decode_case(lens)
    span = 100
    clean = _decode_reference(q, kp, vp, bt, lens, lens, span)
    for row, n in enumerate(lens):
        behind = (n - span + 1) // SUP * SUP
        assert behind >= SUP
        kp, vp = (_poison_behind(x, bt, row, behind) for x in (kp, vp))
    out, _, _ = paged_flash_decode_stats(
        *(jnp.asarray(x) for x in (q, kp, vp, bt, lens)), jnp.int32(LAYER),
        block_size=BS, interpret=True,
        kv_lo=jnp.asarray(lens - span + 1, jnp.int32))
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), clean, atol=ATOL, rtol=0)
    # ... and the unbounded kernel does read them.
    out, _, _ = paged_flash_decode_stats(
        *(jnp.asarray(x) for x in (q, kp, vp, bt, lens)), jnp.int32(LAYER),
        block_size=BS, interpret=True)
    assert not np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("packed", [False, True], ids=["rectangle", "packed"])
def test_prefill_kernels_fetch_no_superpage_behind_the_bound(packed):
    hists, clens, span, t = [1100, 1700], [128, 100], 100, 256
    c = _case(t, 4, 2, hists=hists, clens=clens)
    clean = _spanned_reference(c, span)
    kp, vp, bt = np.asarray(c["kp"]), np.asarray(c["vp"]), np.asarray(c["bt"])
    for row, n in enumerate(hists):
        behind = (n - span + 1) // SUP * SUP
        kp, vp = (_poison_behind(x, bt, row, behind) for x in (kp, vp))
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    if packed:
        q, k, v = _pack(c, clens, t)
        out = np.asarray(paged_flash_prefill_packed(
            q, k, v, jnp.asarray(clens, jnp.int32), kp, vp, c["bt"],
            c["kv_lens"], jnp.int32(LAYER), block_size=BS, interpret=True,
            span=jnp.int32(span)))
        rows = [out[0, :clens[0]], out[0, clens[0]:sum(clens)]]
    else:
        out = np.asarray(paged_flash_prefill(
            c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"], kp, vp,
            c["bt"], c["kv_lens"], jnp.int32(LAYER), block_size=BS,
            interpret=True, span=jnp.int32(span)))
        rows = [out[i, :cl] for i, cl in enumerate(clens)]
    for i, (row, cl) in enumerate(zip(rows, clens)):
        assert np.all(np.isfinite(row))
        np.testing.assert_allclose(row, clean[i, :cl], atol=ATOL, rtol=0)


# ---- ``attend`` hands the span to whichever execution it picks
def test_attend_over_a_pool_view_under_a_span_agrees_in_both_executions():
    c = _case(256, 4, 2, hists=[0, 24, 700], clens=[100, 60, 90])
    clens = [100, 60, 90]
    q, k, v = _pack(c, clens, 256)
    view = KVView(pool_k=jnp.nan_to_num(c["kp"]),
                  pool_v=jnp.nan_to_num(c["vp"]), block_tables=c["bt"],
                  kv_lens=c["kv_lens"], seg_lens=jnp.asarray(clens, jnp.int32),
                  block_size=BS)
    args = (q, k, v, jnp.zeros((1, 256), jnp.int32),
            jnp.asarray([250], jnp.int32))
    outs = [np.asarray(attend(*args, view._replace(interpret=interp),
                              jnp.int32(LAYER), span=jnp.int32(24)))
            for interp in (False, True)]
    np.testing.assert_allclose(outs[0][0, :250], outs[1][0, :250], atol=ATOL,
                               rtol=0)
    ref = _spanned_reference(c, 24)
    np.testing.assert_allclose(outs[1][0, 100:160], ref[1, :60], atol=ATOL,
                               rtol=0)


def test_attend_decode_under_a_span_masks_the_ring_too():
    """T == 1 over a pool view with a ring: pool, ring and the token
    itself together are the ``span`` newest keys."""
    lens = [600, 30]
    q, kp, vp, bt, lens = _decode_case(lens)
    rng = np.random.default_rng(1)
    r = 6
    ring_k = rng.normal(size=(2, 2, r, DH)).astype(np.float32)
    ring_v = rng.normal(size=(2, 2, r, DH)).astype(np.float32)
    k = rng.normal(size=(2, 1, 2, DH)).astype(np.float32)
    v = rng.normal(size=(2, 1, 2, DH)).astype(np.float32)
    filled = 4                               # ring entries written so far
    ring_pos = np.where(np.arange(r)[None] < filled,
                        lens[:, None] + np.arange(r)[None], 2 ** 30)
    pos = (lens + filled)[:, None]
    view = KVView(pool_k=jnp.asarray(kp), pool_v=jnp.asarray(vp),
                  block_tables=jnp.asarray(bt), kv_lens=jnp.asarray(lens),
                  ring_k=jnp.asarray(ring_k), ring_v=jnp.asarray(ring_v),
                  ring_pos=jnp.asarray(ring_pos, jnp.int32), block_size=BS,
                  interpret=True)
    for span in (3, 8, 100):
        out = np.asarray(attend(
            jnp.asarray(q)[:, None], jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pos, jnp.int32), jnp.ones((2,), jnp.int32), view,
            jnp.int32(LAYER), span=jnp.int32(span)))[:, 0]
        for row in range(2):
            slots = (bt[row][:, None] * BS + np.arange(BS)).reshape(-1)
            keys = np.concatenate([kp[LAYER][:, slots[:lens[row]]],
                                   ring_k[:, row, :filled], k[row, 0][:, None]],
                                  axis=1)[:, -span:]
            vals = np.concatenate([vp[LAYER][:, slots[:lens[row]]],
                                   ring_v[:, row, :filled], v[row, 0][:, None]],
                                  axis=1)[:, -span:]
            for hd in range(4):
                s = keys[hd // 2] @ q[row, hd] * DH ** -0.5
                p = np.exp(s - s.max())
                np.testing.assert_allclose(
                    out[row, hd], p @ vals[hd // 2] / p.sum(), atol=ATOL,
                    rtol=0)


@pytest.mark.parametrize("what", ["latent", "int8", "tree"])
def test_attend_refuses_a_span_where_it_has_no_execution(what):
    q = jnp.zeros((1, 4, 2, DH))
    kv = jnp.zeros((1, 4, 1, DH))
    view = {"latent": KVView(),
            "int8": KVView(k_scale=jnp.zeros((1,))),
            "tree": KVView(chunk_bias=jnp.zeros((4, 4)))}[what]
    with pytest.raises(ValueError, match="a span over"):
        attend(q, kv, None if what == "latent" else kv,
               jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32), view,
               span=jnp.int32(8))


# ---- the counters' closed form
def test_keys_in_span_is_the_sum_over_positions():
    for start, length, span in [(0, 10, 4), (0, 3, 8), (5, 20, 8),
                                (100, 7, 8), (7, 1, 8), (0, 0, 8),
                                (3, 9, NO_SPAN)]:
        want = sum(min(p + 1, span) for p in range(start, start + length))
        assert keys_in_span(start, length, span) == want
    got = keys_in_span(np.array([0, 5]), np.array([10, 20]), 8)
    assert got.tolist() == [keys_in_span(0, 10, 8), keys_in_span(5, 20, 8)]
