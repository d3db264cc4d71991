"""The Pallas flash PREFILL kernel over a LATENT pool
(ops/pallas/paged_attention.py:paged_flash_prefill_latent), in interpret
mode on the CPU, against ``window_attention`` over the gathered rows (the
statement of the computation), ``attend``'s choice between the kernel and
the window path for latent rows, and the runner of a tiny ``deepseek_v3``
config through it. Beside tests/test_paged_prefill.py, which holds the K/V
kernel to the same.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import (
    KVView,
    attend,
    gather_kv_pages,
    prefill_kernel_covers,
    window_attention,
)
from production_stack_tpu.ops.pallas.paged_attention import (
    packed_latent_tile,
    packed_sub_block,
    paged_flash_prefill_latent,
    paged_flash_prefill_packed_latent,
    prefill_tiles,
    supports_latent_prefill,
)
from tests.test_paged_prefill import _greedy
from tests.test_paged_prefill import _pack as _pack_row

BS, LAYER, SCALE = 16, 1, 0.0721
ATOL = 2e-5          # what tests/test_paged_prefill.py holds the K/V kernel to
# (heads, row width, value lanes): the benchmark's two latent configurations
# (32 heads over a 576-value row padded to 640 lanes, values its first 512)
# and a small one.
SERVED, SMALL = (32, 640, 512), (8, 256, 128)


def _case(t, shape, hists, clens, *, extra_blocks=3, max_pos=None, seed=0,
          dtype=jnp.float32):
    """One dispatch over latent rows: rows of history ``hists`` and chunk
    lengths ``clens`` at chunk width ``t``. Live blocks are scattered over
    the pool; table entries past a row's live blocks point at a block of
    NaN, and so does every entry of a row that holds nothing. Queries and
    rows are zero past the key's lanes (the last 64), as the model's are."""
    h, w, dv = shape
    rng = np.random.default_rng(seed)
    b = len(hists)
    live = [-(-(hi + cl) // BS) if cl else 0 for hi, cl in zip(hists, clens)]
    mb = max(live) + extra_blocks
    nblocks = 2 + sum(live)                 # block 0 null, block 1 poison
    ids = list(rng.permutation(np.arange(2, nblocks)))
    bt = np.ones((b, mb), np.int32)
    for i in range(b):
        bt[i, :live[i]] = [ids.pop() for _ in range(live[i])]
    pool = rng.normal(size=(2, 1, nblocks * BS, w)).astype(np.float32)
    q = rng.normal(size=(b, t, h, w)).astype(np.float32)
    rows = rng.normal(size=(b, t, 1, w)).astype(np.float32)
    for x in (pool, q, rows):
        x[..., w - 64:] = 0.0
    pool[:, :, BS:2 * BS] = np.nan
    pos = np.asarray(hists)[:, None] + np.arange(t)[None, :]
    if max_pos is not None:
        pos = np.minimum(pos, max_pos - 1)
    arr = lambda x: jnp.asarray(x, dtype)   # noqa: E731
    return dict(
        q=arr(q), rows=arr(rows), positions=jnp.asarray(pos, jnp.int32),
        chunk_lens=jnp.asarray(clens, jnp.int32), pool=arr(pool),
        bt=jnp.asarray(bt), kv_lens=jnp.asarray(hists, jnp.int32), dv=dv)


def _kernel(c):
    return paged_flash_prefill_latent(
        c["q"], c["rows"], c["chunk_lens"], c["pool"], c["bt"],
        c["kv_lens"], jnp.int32(LAYER), block_size=BS, value_dim=c["dv"],
        scale=SCALE, interpret=True)


def _window_reference(c, dtype=jnp.float32):
    """``window_attention`` over this layer's gathered rows as keys AND
    values, the first ``value_dim`` lanes of the result: junk made finite
    first (the oracle multiplies masked weights into values)."""
    win = gather_kv_pages(
        jnp.nan_to_num(c["pool"][LAYER]).astype(dtype), c["bt"], BS)
    cast = lambda x: x.astype(dtype)        # noqa: E731
    return window_attention(
        cast(c["q"]), cast(c["rows"]), cast(c["rows"]), c["positions"],
        c["chunk_lens"], win, win, c["kv_lens"], scale=SCALE)[..., :c["dv"]]


def _check(c, atol=ATOL):
    out = np.asarray(_kernel(c).astype(jnp.float32))
    ref = np.asarray(_window_reference(c))
    b, t, h, w = c["q"].shape
    assert out.shape == (b, t, h, c["dv"])
    _, tq = prefill_tiles(t, h, 1, w, c["pool"].dtype.itemsize, BS)
    assert np.all(np.isfinite(out))
    for i, cl in enumerate(np.asarray(c["chunk_lens"])):
        # Valid queries agree with the oracle; so do a live block's padded
        # ones (they see the row's valid keys, as in the window path).
        live_to = -(-cl // tq) * tq
        np.testing.assert_allclose(out[i, :live_to], ref[i, :live_to],
                                   atol=atol, rtol=0)
        # A query block that is all padding is zeros.
        assert not out[i, live_to:].any()
    return out


# ---- rows of unequal history and chunk length, one dispatch; both shapes
@pytest.mark.parametrize("shape", [SERVED, SMALL], ids=["served", "small"])
def test_rows_of_unequal_history_and_chunk_match_window(shape):
    h, w, dv = shape
    assert supports_latent_prefill(128, h, w, dv, 4, BS)
    _check(_case(128, shape, hists=[0, 37, 300, 64], clens=[100, 128, 60, 7]))


def test_the_tile_rule_follows_from_width_heads_and_vmem():
    """32 queries of 32 heads are one matmul operand at 640 lanes (both of
    ``prefill_tiles``' limits bind: a program's rows over all heads, and
    one KV head's score rows); a narrow row of few heads takes the whole
    chunk."""
    assert prefill_tiles(128, 32, 1, 640, 2, BS) == (512, 32)
    assert prefill_tiles(1024, 32, 1, 640, 2, BS) == (512, 32)
    assert prefill_tiles(128, 8, 1, 256, 4, BS) == (512, 128)


# ---- where the history ends
@pytest.mark.parametrize("hist", [0, 15, 16, 17, 511, 512, 513, 1030],
                         ids=lambda x: f"hist{x}")
def test_history_edges_match_window(hist):
    """History 0 (no superpage fetched), one below, at and one above a
    block edge and a superpage edge, and past two superpages."""
    _check(_case(128, SMALL, hists=[hist, 3], clens=[128, 90]))


# ---- chunk widths: one query block and key tile, and more than one of each
@pytest.mark.parametrize("t,rows,shape", [
    (128, 3, SMALL), (256, 3, SMALL), (1024, 1, SMALL), (256, 2, SERVED)],
    ids=["8x128", "4x256", "1x1024-two-key-tiles", "served-256"])
def test_chunk_widths_match_window(t, rows, shape):
    hists = [40, 0, 600][:rows]
    clens = [t, t - 29, t // 2 + 3][:rows]
    _check(_case(t, shape, hists=hists, clens=clens))


def test_padded_row_is_zeros_and_fetches_nothing():
    """A row with ``chunk_len`` 0 — its table and its length pointing at
    NaN — returns zeros, and leaves no NaN behind in the buffer the next
    row's masked tail would meet (the buffer is keys AND values): it
    issued no fetch."""
    c = _case(128, SMALL, hists=[64, 64, 5], clens=[0, 0, 20])
    out = _check(c)
    assert not out[0].any() and not out[1].any()


def test_padded_rows_everywhere_are_zeros():
    c = _case(128, SMALL, hists=[0, 0], clens=[0, 0])
    assert not np.asarray(_kernel(c)).any()


def test_what_lies_between_segments_is_inert_and_comes_out_finite():
    """tests/test_paged_prefill.py's, over latent rows: rows of 1, 77 and 0
    tokens, the padding of q and of the rows zeros and then +-1e4."""
    from tests.test_paged_prefill import _padding_set_to

    clens = [1, 77, 0]
    c = _case(128, SMALL, hists=[40, 0, 64], clens=clens)
    ref = np.asarray(_window_reference(c))
    outs = [np.asarray(_kernel(_padding_set_to(c, clens, value,
                                               ("q", "rows"))))
            for value in (0.0, 1e4)]
    for out in outs:
        assert np.all(np.isfinite(out))
        for i, cl in enumerate(clens):
            np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=ATOL,
                                       rtol=0)
    for i, cl in enumerate(clens):
        assert np.array_equal(outs[0][i, :cl], outs[1][i, :cl])


def test_positions_clamped_at_max_model_len():
    """The runner clamps positions at ``max_model_len - 1``: a live block's
    padded queries then share the last position, and the valid ones are
    untouched."""
    c = _case(256, SMALL, hists=[900, 1000], clens=[124, 24], max_pos=1024)
    assert int(c["positions"].max()) == 1023
    _check(c)


@pytest.mark.parametrize("shape", [SERVED, SMALL], ids=["served", "small"])
def test_bfloat16_pool_matches_window_in_bfloat16(shape):
    """bf16 operands, float32 scores and accumulation: the precision of
    ``window_attention`` on the same operands."""
    c = _case(128, shape, hists=[70, 0, 520], clens=[128, 31, 100],
              dtype=jnp.bfloat16)
    out = _kernel(c)
    assert out.dtype == jnp.bfloat16
    out = np.asarray(out.astype(jnp.float32))
    ref = np.asarray(_window_reference(c, jnp.bfloat16).astype(jnp.float32))
    for i, cl in enumerate(np.asarray(c["chunk_lens"])):
        np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=2e-2,
                                   rtol=0)


# ---- the predicate, and attend: which execution a view of latent rows gets
def test_prefill_kernel_covers_latent_rows():
    """One pool, values the head of the row, both whole lanes, heads that
    fill whole sublane tiles; what the K/V kernel is refused, the latent
    one is too."""
    def covers(t=128, h=32, hkv=1, w=640, dv=512, bs=BS,
               dtypes=(jnp.bfloat16,), **kw):
        return prefill_kernel_covers(t, h, hkv, w, dv, bs, dtypes,
                                     latent=True, **kw)

    assert covers()
    assert all(covers(t=t) for t in (128, 256, 512, 1024))
    assert covers(h=8, w=256, dv=128, dtypes=(jnp.float32,))
    for refused in ({"scales": True}, {"kv_sharded": True}, {"ring": True},
                    {"chunk_bias": True}):
        assert not covers(**refused), refused
    assert not covers(dtypes=(jnp.bfloat16, jnp.float32))
    assert not covers(hkv=2)                # a latent row has no KV heads
    assert not covers(w=576)                # a row of broken lanes
    assert not covers(dv=448) and not covers(dv=768)
    assert not covers(h=8)                  # bf16: 16 heads a sublane tile
    assert not covers(h=4, dtypes=(jnp.float32,))
    assert not covers(t=16, bs=32)          # a chunk of half a block
    assert not covers(t=768)                # not whole key tiles of 512
    # A PACKED row (PR 48): the same, and query blocks of whole sublane
    # tiles; the rows a deployment's envelope dispatches are covered. Since
    # PR 56 a rectangle runs the same body, so ``packed`` changes nothing.
    assert all(covers(t=t, packed=True) for t in (128, 256, 512, 1024))
    assert covers(h=8, w=256, dv=128, dtypes=(jnp.float32,), packed=True)
    assert not covers(h=8, packed=True) and not covers(t=768, packed=True)
    assert not covers(scales=True, packed=True)
    # K/V rows are not read as latent ones, nor latent rows as K/V.
    assert not prefill_kernel_covers(128, 32, 1, 640, 512, BS,
                                     (jnp.bfloat16,))
    assert prefill_kernel_covers(128, 16, 2, 128, 128, BS, (jnp.bfloat16,))


def _view(c, **kw):
    return KVView(pool_k=c["pool"], pool_v=c["pool"][..., :0],
                  block_tables=c["bt"], kv_lens=c["kv_lens"], block_size=BS,
                  **kw)


def _attend(c, view):
    return attend(c["q"], c["rows"], None, c["positions"], c["chunk_lens"],
                  view, jnp.int32(LAYER), scale=SCALE, value_dim=c["dv"])


def _attend_jaxpr(c, view):
    return str(jax.make_jaxpr(lambda q, rows: attend(
        q, rows, None, c["positions"], c["chunk_lens"], view,
        jnp.int32(LAYER), scale=SCALE, value_dim=c["dv"]))(
            c["q"], c["rows"]))


def test_attend_takes_the_latent_kernel_over_a_pool_view():
    c = _case(128, SMALL, hists=[0, 37], clens=[100, 128])
    view = _view(c, interpret=True)
    jaxpr = _attend_jaxpr(c, view)
    assert "paged_flash_prefill_latent" in jaxpr
    assert "paged_flash_decode" not in jaxpr
    np.testing.assert_allclose(np.asarray(_attend(c, view)),
                               np.asarray(_kernel(c)), atol=0, rtol=0)


def test_attend_on_a_cpu_program_gathers_and_is_window_attention():
    """Without ``interpret`` the execution follows the platform the program
    is lowered for: on the CPU, the layer's rows gathered and
    ``window_attention``, equal to the kernel."""
    c = _case(128, SMALL, hists=[0, 37, 300], clens=[100, 128, 60])
    c["pool"] = jnp.nan_to_num(c["pool"])
    fn = jax.jit(lambda q, rows: attend(
        q, rows, None, c["positions"], c["chunk_lens"], _view(c),
        jnp.int32(LAYER), scale=SCALE, value_dim=c["dv"]))
    assert "paged_flash_prefill" not in fn.lower(
        c["q"], c["rows"]).compile().as_text()
    out = np.asarray(fn(c["q"], c["rows"]))
    ref = np.asarray(_kernel(c))
    for i, cl in enumerate(np.asarray(c["chunk_lens"])):
        np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=ATOL,
                                   rtol=0)


def _uncovered_pool_views():
    c = _case(16, SMALL, hists=[0, 37], clens=[16, 9])
    c["pool"] = jnp.nan_to_num(c["pool"])
    ring = jnp.zeros((1, 2, 4, SMALL[1]), jnp.float32)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    return c, {
        "tree-chunk-bias": _view(
            c, chunk_bias=jnp.zeros((16, 16), jnp.float32)),
        "ring": _view(c, ring_k=ring, ring_v=ring,
                      ring_pos=jnp.zeros((2, 4), jnp.int32)),
        "int8-pool": _view(c)._replace(
            pool_k=c["pool"].astype(jnp.int8),
            k_scale=jnp.ones(c["pool"].shape[:3], jnp.bfloat16),
            v_scale=jnp.ones(c["pool"].shape[:3], jnp.bfloat16)),
        "kv-head-sharded-pool": _view(c, tp_mesh=mesh),
        "pool-of-another-dtype": _view(c)._replace(
            pool_k=c["pool"].astype(jnp.bfloat16)),
        "chunk-of-half-a-block": _view(c)._replace(block_size=32),
    }


@pytest.mark.parametrize("case", [
    "tree-chunk-bias", "ring", "int8-pool", "kv-head-sharded-pool",
    "pool-of-another-dtype", "chunk-of-half-a-block"])
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["lowered-for-the-backend", "interpret"])
def test_attend_refuses_a_latent_pool_view_the_kernel_does_not_cover(
        case, interpret):
    """As for K/V rows, there is no third execution: a chunk over a pool
    view of latent rows that the kernel does not cover raises while the
    program is traced; whoever builds views asks ``prefill_kernel_covers``
    first and gathers a window."""
    c, views = _uncovered_pool_views()
    with pytest.raises(ValueError, match="prefill_kernel_covers"):
        _attend_jaxpr(c, views[case]._replace(interpret=interpret))


def test_attend_refuses_heads_that_do_not_fill_a_sublane_tile():
    c = _case(16, (4, 256, 128), hists=[0, 37], clens=[16, 9])
    with pytest.raises(ValueError, match="prefill_kernel_covers"):
        _attend_jaxpr(c, _view(c, interpret=True))


# ---------------------------------------------------------------- a packed row
# PR 48: the sequences' chunks end to end in ONE row over the latent pool
# (paged_flash_prefill_packed_latent: tests/test_paged_prefill.py's packed
# kernel body with one page stream). The oracle is unchanged: a segment's
# tokens are what ``window_attention`` gives the segment as a row of its own.
# (heads, row width, value lanes): 16 heads take a query block of 128 tokens,
# 32 heads one of 64, each under a row tile of 256 keys.
HEADS16, HEADS32 = (16, 256, 128), (32, 256, 128)


def _pack(c, clens, t):
    return _pack_row(c, clens, t, names=("q", "rows"))


def _packed_kernel(c, clens, t, seg_lens=None, **kw):
    q, rows = _pack(c, clens, t)
    return np.asarray(paged_flash_prefill_packed_latent(
        q, rows, jnp.asarray(seg_lens or clens, jnp.int32), c["pool"],
        c["bt"], c["kv_lens"], jnp.int32(LAYER), block_size=BS,
        value_dim=c["dv"], scale=SCALE, interpret=True, **kw))


@pytest.mark.parametrize("t,shape,hists,clens,kw", [
    # A boundary inside a query block, behind no history and behind some.
    (256, SMALL, [0, 37], [100, 120], {}),
    # The served widths (values 512 of 640 lanes, 32 heads: 32-token query
    # blocks under 256-key row tiles): the cached system prompt, none,
    # and several pages; segments that start inside a block and a tile.
    (256, SERVED, [64, 0, 300], [100, 30, 120], {}),
    # Histories one below, at and one above a page boundary.
    (256, HEADS16, [15, 16, 17, 64], [60, 70, 80, 30], {}),
    # None, one and several superpages of history beside each other.
    (512, HEADS32, [0, 500, 1100], [100, 200, 150], {}),
    # A segment over several row tiles, between two short ones.
    (512, HEADS32, [300, 20, 0], [40, 400, 60], {}),
    # Slots of length 0 behind the live ones; the row's tail is padding:
    # the end of a block, and whole blocks.
    (512, HEADS32, [64, 64, 0, 0], [90, 30, 0, 0], {}),
    # A tile that is a block (the K/V kernel's spelling of the diagonal),
    # and a narrower row tile and wider sub-block than the rule's own.
    (256, HEADS16, [5, 0, 16], [60, 70, 80], {"key_tile": 128}),
    (512, HEADS32, [5, 0, 16], [160, 170, 180],
     {"sub_block": 32, "key_tile": 128}),
    # One segment that fills the row.
    (256, HEADS16, [40], [256], {}),
    (256, HEADS32, [700], [256], {}),
], ids=["boundary-in-a-block", "served-widths", "page-boundaries",
        "history-superpages", "segment-over-tiles", "empty-slots-padded-tail",
        "tile-is-a-block", "narrow-tile-wide-sub-block",
        "one-segment-16-heads",
        "one-segment-32-heads"])
@pytest.mark.parametrize("starts", ["as-packed", "one-off"])
def test_packed_latent_segments_match_window_a_segment(t, shape, hists,
                                                       clens, kw, starts):
    """Each segment of a packed row equals ``_latent_window_attention``'s
    statement of it (``window_attention`` over its gathered rows, a row of
    its own); one segment that fills the row equals the rectangle latent
    kernel; and the kernel told a WRONG start (the first boundary one token
    late) is told apart: the neighbour's first token then attends the wrong
    sequence."""
    h, w, dv = shape
    assert supports_latent_prefill(t, h, w, dv, 4, BS)
    c = _case(t, shape, hists=hists, clens=clens)
    ref = np.asarray(_window_reference(c))
    live = [cl for cl in clens if cl]
    if starts == "one-off":
        if len(live) < 2:
            pytest.skip("one segment has no boundary to move")
        wrong = [clens[0] + 1, clens[1] - 1, *clens[2:]]
        out = _packed_kernel(c, clens, t, seg_lens=wrong, **kw)
        at = clens[0]
        assert np.abs(out[0, at] - ref[1, 0]).max() > 1e-2
        return
    out = _packed_kernel(c, clens, t, **kw)
    assert out.shape == (1, t, h, dv) and np.all(np.isfinite(out))
    at = 0
    for i, cl in enumerate(clens):
        np.testing.assert_allclose(out[0, at:at + cl], ref[i, :cl],
                                   atol=ATOL, rtol=0)
        assert cl == 0 or np.abs(out[0, at:at + cl]).max() > 1e-3
        at += cl
    # A query block no segment reaches is zeros.
    _, tq = prefill_tiles(t, h, 1, w, 4, BS)
    assert not out[0, -(-at // tq) * tq:].any()
    if live == [t]:
        np.testing.assert_allclose(
            out, np.asarray(_kernel(c)), atol=ATOL, rtol=0)


def test_the_packed_tiles_follow_from_heads_and_width():
    """At the served widths a query block is 32 tokens, a sub-block 16 (512
    score rows: every head shares the row) and a row tile 256 keys, the
    row whole where it is shorter; few narrow heads take one block and
    sub-block of 128 under the same tile."""
    assert prefill_tiles(1024, 32, 1, 640, 2, BS) == (512, 32)
    assert packed_sub_block(32, 32, 2) == 16
    assert packed_latent_tile(1024, 32) == packed_latent_tile(256, 32) == 256
    assert packed_latent_tile(128, 32) == 128
    assert prefill_tiles(256, 16, 1, 256, 4, BS) == (512, 128)
    assert packed_latent_tile(128, 128) == 128      # a tile that is a block
    assert packed_latent_tile(256, 128) == 256
    assert prefill_tiles(512, 32, 1, 256, 4, BS) == (512, 64)


def test_attend_over_a_packed_latent_view_agrees_in_both_executions():
    """``attend`` over a view of latent rows that says ``seg_lens``: the
    kernel (the view says ``interpret``) and the other backends' execution,
    which takes the row apart into a row a segment and runs
    ``_latent_window_attention``; a packed view of two rows, or of heads
    that do not fill a sublane tile, still raises while it is traced."""
    t = 256
    hists, clens = [0, 40, 300, 0], [70, 90, 50, 0]
    c = _case(t, HEADS16, hists=hists, clens=clens)
    c["pool"] = jnp.nan_to_num(c["pool"])
    q, rows = _pack(c, clens, t)
    view = _view(c, seg_lens=jnp.asarray(clens, jnp.int32))
    positions = jnp.zeros((1, t), jnp.int32)     # read by neither
    row_len = jnp.asarray([sum(clens)], jnp.int32)

    def run(q, rows, view):
        return attend(q, rows, None, positions, row_len, view,
                      jnp.int32(LAYER), scale=SCALE, value_dim=c["dv"])

    jaxpr = str(jax.make_jaxpr(lambda q, rows: run(
        q, rows, view._replace(interpret=True)))(q, rows))
    assert "paged_flash_prefill_packed_latent" in jaxpr
    outs = [np.asarray(run(q, rows, view._replace(interpret=interpret)))
            for interpret in (False, True)]
    live = sum(clens)
    np.testing.assert_allclose(outs[0][0, :live], outs[1][0, :live],
                               atol=ATOL, rtol=0)
    assert np.abs(outs[1][0, :live]).max() > 1e-3
    assert not outs[0][0, live:].any()
    np.testing.assert_allclose(outs[1], _packed_kernel(c, clens, t),
                               atol=0, rtol=0)
    with pytest.raises(ValueError, match="does not cover"):
        run(jnp.concatenate([q, q]), jnp.concatenate([rows, rows]), view)
    few = _case(t, (4, 256, 128), hists=hists, clens=clens)
    with pytest.raises(ValueError, match="does not cover"):
        run(*_pack(few, clens, t), _view(
            few, seg_lens=jnp.asarray(clens, jnp.int32), interpret=True))


# ---- the runner: a latent model's prefill dispatch through the kernel
# (``_greedy``: tests/test_paged_prefill.py's, a prompt alone, then the rest)
@pytest.mark.parametrize("model", ["tiny-deepseek-v3", "tiny-xing4"])
@pytest.mark.asyncio
async def test_latent_engine_prefill_through_the_kernel_matches_the_gathered(
        monkeypatch, model):
    """The engine's paged prefill of a model that caches latent rows (the
    tiny ``deepseek_v3`` configs with heads enough to fill a sublane tile;
    the second with four residual streams, a low-rank query and YaRN's
    softmax scale): ``prefill_reads_pool`` on one device, no windowed
    family among those warm-up and the AOT prepass enumerate, none
    dispatched, and through the kernel (every view made to say
    ``interpret``) it serves the tokens the CPU's own execution (rows
    gathered, ``window_attention``) serves: a prompt alone, a prefix hit
    on it, a prompt that crosses chunks, short ones beside them."""
    import functools

    from production_stack_tpu.engine import runner as runner_mod
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.models import config as models_config

    name = model + "-8-heads"
    monkeypatch.setitem(
        models_config.NAMED_CONFIGS, name, dataclasses.replace(
            models_config.NAMED_CONFIGS[model], num_heads=8, num_kv_heads=8,
            name=name))
    base = "the quick brown fox jumps over the lazy dog " * 3
    prompts = [base, base + "and again", "x" * 150, "hi", "hello there"]
    results, families, windows = {}, {}, {}
    for execution in ("gathered", "kernel"):
        if execution == "kernel":
            monkeypatch.setattr(
                runner_mod, "KVView",
                functools.partial(runner_mod.KVView, interpret=True))
        cfg = EngineConfig(
            model=name, max_model_len=256, num_kv_blocks=128,
            attn_impl="paged", num_decode_steps=4, dtype="float32",
            max_num_batched_tokens=64, max_num_seqs=4, block_size=16,
            enable_warmup=False,
        )
        eng = ServingEngine(cfg)
        await eng.start()
        try:
            runner = eng.runner
            assert runner.kv_pools == 1 and runner.prefill_reads_pool
            assert runner.prefill_window_blocks == 1 << 30
            # Since PR 48 its dispatches are packed rows: both executions
            # below are the packed pair's (the row taken apart, the kernel).
            assert runner.prefill_packs and eng.scheduler.prefill_packed
            families[execution] = runner.reachable_prefill_families()
            seen = windows[execution] = []
            prefill = runner._prefill

            def spy(*args, _prefill=prefill, _seen=seen, **kw):
                _seen.append(kw["has_window"])
                return _prefill(*args, **kw)

            runner._prefill = spy
            results[execution] = await _greedy(eng, prompts)
            runner._prefill = prefill
            # GET /debug/programs says which execution the program holds.
            programs = [p for p in runner.audit_pool_programs()
                        if p["program"] == "prefill"]
            assert [p["family"][3] for p in programs] == [False]
            assert {p["prefill_attn"] for p in programs} == {
                "pallas" if execution == "kernel" else "xla"}
            assert all(p["prefill_reads_pool"] for p in programs)
        finally:
            await eng.stop()
    for execution in ("gathered", "kernel"):
        assert {f[3] for f in families[execution]} == {False}
        # A prefix hit and second chunks were dispatched: no window.
        assert len(windows[execution]) >= 4
        assert not any(windows[execution])
    assert results["kernel"] == results["gathered"]
    assert all(len(v) == 6 for v in results["kernel"].values())
