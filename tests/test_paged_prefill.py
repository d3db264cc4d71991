"""The Pallas flash PREFILL kernel over the paged pool
(ops/pallas/paged_attention.py:paged_flash_prefill), in interpret mode on
the CPU, against ``window_attention`` over gathered history (the statement
of the computation) and ``paged_attention_xla`` (the pool-only reference),
and ``attend``'s choice between the kernel and the window path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import (
    KVView,
    attend,
    gather_kv_pages,
    paged_attention_xla,
    prefill_kernel_covers,
    window_attention,
)
from production_stack_tpu.ops.pallas.paged_attention import (
    packed_pairs,
    packed_sub_block,
    paged_flash_prefill,
    paged_flash_prefill_packed,
    prefill_tiles,
    rectangle_pairs,
    supports_packed_prefill,
    supports_pallas_prefill,
)

BS, DH, LAYER = 16, 128, 1
ATOL = 2e-5          # what tests/test_paged_decode.py holds the decode kernel to


def _case(t, h, hkv, hists, clens, *, extra_blocks=3, max_pos=None, seed=0,
          dtype=jnp.float32):
    """One dispatch: rows of history ``hists`` and chunk lengths ``clens``
    at chunk width ``t``. Live blocks are scattered over the pool; table
    entries past a row's live blocks point at a block of NaN, and so does
    every entry of a row that holds nothing."""
    rng = np.random.default_rng(seed)
    b = len(hists)
    live = [-(-(hi + cl) // BS) if cl else 0 for hi, cl in zip(hists, clens)]
    mb = max(live) + extra_blocks
    nblocks = 2 + sum(live)                 # block 0 null, block 1 poison
    ids = list(rng.permutation(np.arange(2, nblocks)))
    bt = np.ones((b, mb), np.int32)
    for i in range(b):
        bt[i, :live[i]] = [ids.pop() for _ in range(live[i])]
    shape = (2, hkv, nblocks * BS, DH)
    kp = rng.normal(size=shape).astype(np.float32)
    vp = rng.normal(size=shape).astype(np.float32)
    kp[:, :, BS:2 * BS] = np.nan
    vp[:, :, BS:2 * BS] = np.nan
    q = rng.normal(size=(b, t, h, DH)).astype(np.float32)
    k = rng.normal(size=(b, t, hkv, DH)).astype(np.float32)
    v = rng.normal(size=(b, t, hkv, DH)).astype(np.float32)
    pos = np.asarray(hists)[:, None] + np.arange(t)[None, :]
    if max_pos is not None:
        pos = np.minimum(pos, max_pos - 1)
    arr = lambda x: jnp.asarray(x, dtype)   # noqa: E731
    return dict(
        q=arr(q), k=arr(k), v=arr(v), positions=jnp.asarray(pos, jnp.int32),
        chunk_lens=jnp.asarray(clens, jnp.int32), kp=arr(kp), vp=arr(vp),
        bt=jnp.asarray(bt), kv_lens=jnp.asarray(hists, jnp.int32))


def _kernel(c, **kw):
    return paged_flash_prefill(
        c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"], c["kp"],
        c["vp"], c["bt"], c["kv_lens"], jnp.int32(LAYER), block_size=BS,
        interpret=True, **kw)


def _window_reference(c):
    """``window_attention`` over this layer's gathered pages, junk made
    finite first (the oracle multiplies masked weights into values)."""
    kp = jnp.nan_to_num(c["kp"][LAYER].astype(jnp.float32))
    vp = jnp.nan_to_num(c["vp"][LAYER].astype(jnp.float32))
    win_k = gather_kv_pages(kp, c["bt"], BS)
    win_v = gather_kv_pages(vp, c["bt"], BS)
    f32 = lambda x: x.astype(jnp.float32)   # noqa: E731
    return window_attention(
        f32(c["q"]), f32(c["k"]), f32(c["v"]), c["positions"],
        c["chunk_lens"], win_k, win_v, c["kv_lens"])


def _check(c, atol=ATOL):
    out = np.asarray(_kernel(c).astype(jnp.float32))
    ref = np.asarray(_window_reference(c))
    t = c["q"].shape[1]
    h, hkv = c["q"].shape[2], c["k"].shape[2]
    _, tq = prefill_tiles(t, h, hkv, DH, c["kp"].dtype.itemsize, BS)
    clens = np.asarray(c["chunk_lens"])
    assert np.all(np.isfinite(out))
    for i, cl in enumerate(clens):
        # Valid queries agree with the oracle; so do a live block's padded
        # ones (they see the row's valid keys, as in the window path).
        live_to = -(-cl // tq) * tq
        np.testing.assert_allclose(out[i, :live_to], ref[i, :live_to],
                                   atol=atol, rtol=0)
        # A query block that is all padding is zeros.
        assert not out[i, live_to:].any()
    return out


# ---- rows of unequal history and chunk length, one dispatch; head layouts
@pytest.mark.parametrize("h,hkv", [(16, 2), (32, 8), (30, 30)],
                         ids=["gqa8-16x2", "gqa4-32x8", "mha-30x30"])
def test_rows_of_unequal_history_and_chunk_match_window(h, hkv):
    assert supports_pallas_prefill(128, h, hkv, DH, 4, BS)
    _check(_case(128, h, hkv, hists=[0, 37, 300, 64], clens=[100, 128, 60, 7]))


# ---- where the history ends
@pytest.mark.parametrize("hist", [0, 5, 16, 250, 256, 511, 512, 513, 1030],
                         ids=lambda x: f"hist{x}")
def test_history_edges_match_window(hist):
    """History 0 (no tile fetched), inside a block, at a block edge, at a
    compute-block edge, at and across a superpage edge."""
    _check(_case(128, 4, 2, hists=[hist, 3], clens=[128, 90]))


# ---- chunk widths: one query block, and more than one
@pytest.mark.parametrize("t,rows", [(128, 3), (256, 3), (512, 2), (2048, 1)],
                         ids=lambda x: str(x))
def test_chunk_widths_match_window(t, rows):
    hists = [40, 0, 600][:rows]
    clens = [t, t - 29, t // 2 + 3][:rows]
    _check(_case(t, 4, 2, hists=hists, clens=clens))


def test_matches_paged_attention_xla_with_the_chunk_written():
    """The pool-only reference: the chunk's K/V written at its positions,
    ``paged_attention_xla`` over history + chunk."""
    c = _case(128, 4, 2, hists=[0, 37, 300], clens=[100, 128, 60])
    out = np.asarray(_kernel(c))
    kp = np.nan_to_num(np.asarray(c["kp"][LAYER]))
    vp = np.nan_to_num(np.asarray(c["vp"][LAYER]))
    bt = np.asarray(c["bt"])
    for i in range(3):
        hist, cl = int(c["kv_lens"][i]), int(c["chunk_lens"][i])
        for j in range(cl):
            slot = bt[i, (hist + j) // BS] * BS + (hist + j) % BS
            kp[:, slot] = np.asarray(c["k"][i, j])
            vp[:, slot] = np.asarray(c["v"][i, j])
    ref = np.asarray(paged_attention_xla(
        c["q"], jnp.asarray(kp), jnp.asarray(vp), c["bt"],
        c["kv_lens"] + c["chunk_lens"], c["positions"], block_size=BS))
    for i in range(3):
        cl = int(c["chunk_lens"][i])
        np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=ATOL,
                                   rtol=0)


def test_padded_row_is_zeros_and_fetches_nothing():
    """A row with ``chunk_len`` 0 — its table and its length pointing at
    NaN — returns zeros, and leaves no NaN behind in the buffers the next
    row's masked tail would meet: it issued no fetch."""
    c = _case(128, 4, 2, hists=[64, 64, 5], clens=[0, 0, 20])
    out = _check(c)
    assert not out[0].any() and not out[1].any()


def test_padded_rows_everywhere_are_zeros():
    c = _case(128, 4, 2, hists=[0, 0], clens=[0, 0])
    assert not np.asarray(_kernel(c)).any()


def test_positions_clamped_at_max_model_len():
    """The runner clamps positions at ``max_model_len - 1``: a live block's
    padded queries then share the last position, and the valid ones are
    untouched."""
    c = _case(256, 4, 2, hists=[900, 1000], clens=[124, 24], max_pos=1024)
    assert int(c["positions"].max()) == 1023
    _check(c)


def test_bfloat16_pool_matches_window_in_bfloat16():
    """bf16 operands, float32 scores and accumulation: the precision of
    ``window_attention`` on the same operands."""
    c = _case(256, 8, 2, hists=[70, 0, 520], clens=[256, 31, 200],
              dtype=jnp.bfloat16)
    out = np.asarray(_kernel(c).astype(jnp.float32))
    kp = jnp.nan_to_num(c["kp"][LAYER])
    vp = jnp.nan_to_num(c["vp"][LAYER])
    ref = np.asarray(window_attention(
        c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"],
        gather_kv_pages(kp, c["bt"], BS), gather_kv_pages(vp, c["bt"], BS),
        c["kv_lens"]).astype(jnp.float32))
    for i, cl in enumerate(np.asarray(c["chunk_lens"])):
        np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=2e-2,
                                   rtol=0)


# ---- attend: which execution a view gets
def _view(c, **kw):
    return KVView(pool_k=c["kp"], pool_v=c["vp"], block_tables=c["bt"],
                  kv_lens=c["kv_lens"], block_size=BS, **kw)


def _attend_jaxpr(c, view, **kw):
    def f(q, k, v):
        return attend(q, k, v, c["positions"], c["chunk_lens"], view,
                      jnp.int32(LAYER), **kw)
    return str(jax.make_jaxpr(f)(c["q"], c["k"], c["v"]))


def test_attend_takes_the_kernel_over_a_pool_view():
    c = _case(128, 4, 2, hists=[0, 37], clens=[100, 128])
    view = _view(c, interpret=True)
    assert "paged_flash_prefill" in _attend_jaxpr(c, view)
    out = attend(c["q"], c["k"], c["v"], c["positions"], c["chunk_lens"],
                 view, jnp.int32(LAYER))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_kernel(c)),
                               atol=0, rtol=0)


def test_attend_on_a_cpu_program_gathers_and_is_window_attention():
    """Without ``interpret`` the execution follows the platform the program
    is lowered for: on the CPU, the layer's pages gathered and
    ``window_attention``, equal to the kernel."""
    c = _case(128, 4, 2, hists=[0, 37, 300], clens=[100, 128, 60])
    c["kp"], c["vp"] = jnp.nan_to_num(c["kp"]), jnp.nan_to_num(c["vp"])
    fn = jax.jit(lambda q, k, v: attend(
        q, k, v, c["positions"], c["chunk_lens"], _view(c),
        jnp.int32(LAYER)))
    assert "paged_flash_prefill" not in fn.lower(
        c["q"], c["k"], c["v"]).compile().as_text()
    out = np.asarray(fn(c["q"], c["k"], c["v"]))
    ref = np.asarray(_kernel(c))
    for i, cl in enumerate(np.asarray(c["chunk_lens"])):
        np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=ATOL,
                                   rtol=0)


def _uncovered_pool_views():
    c = _case(16, 4, 2, hists=[0, 37], clens=[16, 9])
    c["kp"], c["vp"] = jnp.nan_to_num(c["kp"]), jnp.nan_to_num(c["vp"])
    bias = jnp.zeros((16, 16), jnp.float32)
    ring = jnp.zeros((2, 2, 4, DH), jnp.float32)
    int8 = dict(pool_k=c["kp"].astype(jnp.int8),
                pool_v=c["vp"].astype(jnp.int8),
                k_scale=jnp.ones(c["kp"].shape[:3], jnp.bfloat16),
                v_scale=jnp.ones(c["kp"].shape[:3], jnp.bfloat16))
    half = dict(pool_k=c["kp"].astype(jnp.bfloat16),
                pool_v=c["vp"].astype(jnp.bfloat16))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    return c, {
        "tree-chunk-bias": _view(c, chunk_bias=bias),
        "ring": _view(c, ring_k=ring, ring_v=ring,
                      ring_pos=jnp.zeros((2, 4), jnp.int32)),
        "int8-pool": _view(c)._replace(**int8),
        "kv-head-sharded-pool": _view(c, tp_mesh=mesh),
        "pool-of-another-dtype": _view(c)._replace(**half),
        "chunk-of-half-a-block": _view(c)._replace(block_size=32),
    }


@pytest.mark.parametrize("case", [
    "tree-chunk-bias", "ring", "int8-pool", "kv-head-sharded-pool",
    "pool-of-another-dtype", "chunk-of-half-a-block"])
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["lowered-for-the-backend", "interpret"])
def test_attend_refuses_a_pool_view_the_kernel_does_not_cover(case,
                                                              interpret):
    """There is no third execution: a chunk over a pool view that the
    kernel does not cover raises while the program is traced (whoever
    builds views asks ``prefill_kernel_covers`` first and gathers a
    window), so no program for a TPU gathers a window a layer unseen."""
    c, views = _uncovered_pool_views()
    with pytest.raises(ValueError, match="prefill_kernel_covers"):
        _attend_jaxpr(c, views[case]._replace(interpret=interpret))


@pytest.mark.parametrize("case", ["tree-chunk-bias", "ring", "plain"])
def test_attend_keeps_the_window_path(case):
    """What the kernel does not cover is handed a gathered window and
    stays on ``window_attention``, even where the view says
    ``interpret``."""
    c = _case(16, 4, 2, hists=[0, 37], clens=[16, 9])
    kp, vp = jnp.nan_to_num(c["kp"]), jnp.nan_to_num(c["vp"])
    ring = jnp.zeros((2, 2, 4, DH), jnp.float32)
    parts = {
        "tree-chunk-bias": dict(
            chunk_bias=jnp.zeros((16, 16), jnp.float32)),
        "ring": dict(ring_k=ring, ring_v=ring,
                     ring_pos=jnp.zeros((2, 4), jnp.int32)),
        "plain": {},
    }[case]
    view = KVView(win_k=gather_kv_pages(kp[LAYER], c["bt"], BS),
                  win_v=gather_kv_pages(vp[LAYER], c["bt"], BS),
                  win_len=c["kv_lens"], interpret=True, **parts)
    jaxpr = _attend_jaxpr(c, view)
    assert "pallas_call" not in jaxpr and "paged_flash_prefill" not in jaxpr


@pytest.mark.parametrize("block_size,budget,kv_quantized,devices,reads", [
    (16, 2048, False, 1, True),      # the benchmark's dense envelopes
    (16, 16, False, 1, True),        # one bucket: 16 tokens, whole blocks
    (32, 16, False, 1, False),       # its only bucket is half a block
    (32, 2048, False, 1, True),      # every bucket from 128 is whole blocks
    (16, 2048, True, 1, False),      # an int8 pool
    (16, 2048, False, 4, False),     # a sharded pool or chunk
])
def test_the_runner_asks_attends_predicate_of_every_chunk_bucket(
        block_size, budget, kv_quantized, devices, reads):
    """``runner.prefill_reads_pool`` is ``prefill_kernel_covers`` over
    every chunk-length bucket the config can dispatch: one bucket the
    kernel does not tile and every prefill view holds a window."""
    from types import SimpleNamespace

    from production_stack_tpu.engine.runner import ModelRunner

    r = SimpleNamespace(
        config=SimpleNamespace(max_num_batched_tokens=budget,
                               block_size=block_size, max_num_seqs=64,
                               max_prefill_seqs=None),
        model_config=SimpleNamespace(num_heads=16), attn_impl="paged",
        kv_pools=2, kv_value_dim=DH, kv_quantized=kv_quantized,
        dtype=jnp.bfloat16,
        mesh=SimpleNamespace(size=devices),
        kv_spec=SimpleNamespace(kv_heads=2, head_dim=DH))
    r._prefill_t_buckets = lambda: ModelRunner._prefill_t_buckets(r)
    assert ModelRunner.prefill_reads_pool.func(r) is reads
    assert all(prefill_kernel_covers(t, 16, 2, DH, DH, block_size,
                                     (jnp.bfloat16,))
               for t in r._prefill_t_buckets()) is (
        reads or kv_quantized or devices > 1)


def test_attend_keeps_head_dim_64_and_odd_chunks_on_the_window_path():
    assert not supports_pallas_prefill(128, 8, 2, 64, 2, BS)
    assert not supports_pallas_prefill(384, 8, 2, 128, 2, BS)   # 384 % 256
    assert supports_pallas_prefill(2048, 30, 30, 128, 2, BS)
    assert prefill_tiles(2048, 30, 30, 128, 2, BS) == (256, 256)
    assert prefill_tiles(256, 16, 2, 128, 2, BS) == (512, 256)
    assert prefill_tiles(512, 32, 8, 128, 2, BS) == (512, 256)


def test_attend_latent_rows_take_the_window_path():
    """Latent rows (``v`` None) never reach the K/V kernel."""
    rng = np.random.default_rng(3)
    b, t, h, w, dv = 2, 16, 4, 256, 128
    q = jnp.asarray(rng.normal(size=(b, t, h, w)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(b, t, 1, w)), jnp.float32)
    win = jnp.asarray(rng.normal(size=(1, b, 32, w)), jnp.float32)
    pos = jnp.asarray(np.arange(t)[None] + np.array([[0], [20]]), jnp.int32)
    view = KVView(win_k=win, win_v=win, win_len=jnp.asarray([0, 20]),
                  interpret=True)
    jaxpr = str(jax.make_jaxpr(lambda q, r: attend(
        q, r, None, pos, jnp.asarray([16, 9]), view, None, scale=0.1,
        value_dim=dv))(q, rows))
    assert "pallas_call" not in jaxpr


# ---- the runner: a prefill dispatch through the kernel
async def _greedy(engine, prompts, max_tokens=6):
    import asyncio

    from production_stack_tpu.engine.sampling import SamplingParams

    outs = {}

    async def one(i, p):
        toks = []
        async for o in engine.generate(
            prompt=p, sampling=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True)):
            toks = o.token_ids
        outs[i] = toks

    # The first prompt alone (so the second finds its prefix cached), then
    # the rest at once: rows of unequal history in one dispatch.
    await one(0, prompts[0])
    await asyncio.gather(*[one(i, p) for i, p in enumerate(prompts)
                           if i > 0])
    return outs


@pytest.mark.asyncio
async def test_engine_prefill_through_the_kernel_matches_the_gathered(
        monkeypatch):
    """The engine's paged prefill — the view ``_prefill_impl`` builds, the
    one family a (rows, t), positions, block tables pinned at the full
    width — through the kernel (every view made to say ``interpret``)
    serves the tokens the CPU's own execution (pages gathered,
    ``window_attention``) serves: a prompt alone, a prefix hit on it, a
    prompt that crosses chunks, short ones beside them (float32: greedy
    near-ties of random weights are no signal in bf16)."""
    import functools

    from production_stack_tpu.engine import runner as runner_mod
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine

    base = "the quick brown fox jumps over the lazy dog " * 3
    prompts = [base, base + "and again", "x" * 150, "hi", "hello there"]
    results, families = {}, {}
    for execution in ("gathered", "kernel"):
        if execution == "kernel":
            monkeypatch.setattr(
                runner_mod, "KVView",
                functools.partial(runner_mod.KVView, interpret=True))
        cfg = EngineConfig(
            model="tiny-llama-128dh", max_model_len=256, num_kv_blocks=128,
            attn_impl="paged", num_decode_steps=4, dtype="float32",
            max_num_batched_tokens=64, max_num_seqs=4, block_size=16,
            enable_warmup=False,
        )
        eng = ServingEngine(cfg)
        await eng.start()
        try:
            assert eng.runner.prefill_reads_pool
            families[execution] = eng.runner.reachable_prefill_families()
            results[execution] = await _greedy(eng, prompts)
            # GET /debug/programs says which execution the program holds.
            assert {p["prefill_attn"]
                    for p in eng.runner.audit_pool_programs()
                    if p["program"] == "prefill"} == {
                        "pallas" if execution == "kernel" else "xla"}
        finally:
            await eng.stop()
    assert {f[3] for f in families["kernel"]} == {False}
    assert results["kernel"] == results["gathered"]
    assert all(len(v) == 6 for v in results["kernel"].values())


# ---------------------------------------------------------------- a packed row
# PR 46: the sequences' chunks end to end in ONE row, the kernel told where
# each begins (paged_flash_prefill_packed). The oracle is unchanged: a
# segment's tokens are what ``window_attention`` gives the segment as a row
# of its own.
def _pack(c, clens, t, names=("q", "k", "v")):
    """The rows of ``_case`` ``c`` (its operands ``names``) laid end to end
    in one row of ``t`` tokens; what lies past the last is junk, but
    finite."""
    def pack(x):
        row = np.concatenate(
            [np.asarray(x)[i, :cl] for i, cl in enumerate(clens)], 0)
        junk = np.full((t - row.shape[0], *row.shape[1:]), 7.0, row.dtype)
        return jnp.asarray(np.concatenate([row, junk], 0)[None])
    return tuple(pack(c[name]) for name in names)


def _packed_kernel(c, clens, t, **kw):
    q, k, v = _pack(c, clens, t)
    return np.asarray(paged_flash_prefill_packed(
        q, k, v, jnp.asarray(clens, jnp.int32), c["kp"], c["vp"], c["bt"],
        c["kv_lens"], jnp.int32(LAYER), block_size=BS, interpret=True, **kw))


@pytest.mark.parametrize("t,hists,clens,sub_block", [
    # A boundary inside a query block (and inside a sub-block).
    (256, [0, 37], [100, 120], 64),
    # A segment over several query blocks, between two short ones.
    (768, [300, 20, 0], [40, 600, 100], 64),
    # Three segments in one block.
    (256, [5, 0, 16], [60, 70, 80], 32),
    # Histories of none, one and several superpages beside each other.
    (512, [0, 500, 1100], [100, 200, 150], 128),
    # A row whose tail is padding: the end of a block, and a whole block.
    (512, [64, 64], [90, 30], 64),
    # Sub-blocks as wide as the query block (what few heads a KV head get).
    (512, [100, 0, 513], [300, 10, 150], None),
    # Sixteen segments, the last slots empty.
    (256, [0, 3, 64, 17] + [0] * 12, [30, 50, 60, 40] + [0] * 12, 32),
], ids=["boundary-in-a-block", "segment-over-blocks", "three-in-a-block",
        "history-superpages", "padded-tail", "whole-block-sub-blocks",
        "sixteen-slots"])
def test_packed_segments_match_window_a_segment(t, hists, clens, sub_block):
    h, hkv = 4, 2
    assert supports_packed_prefill(t, h, hkv, DH, 4, BS)
    c = _case(t, h, hkv, hists=hists, clens=clens)
    out = _packed_kernel(c, clens, t, sub_block=sub_block)
    ref = np.asarray(_window_reference(c))
    assert np.all(np.isfinite(out))
    at = 0
    for i, cl in enumerate(clens):
        np.testing.assert_allclose(out[0, at:at + cl], ref[i, :cl],
                                   atol=ATOL, rtol=0)
        at += cl
    # A query block no segment reaches is zeros.
    _, tq = prefill_tiles(t, h, hkv, DH, 4, BS)
    assert not out[0, -(-at // tq) * tq:].any()


@pytest.mark.parametrize("hist,sub_block", [(0, 64), (700, 64), (40, None)])
def test_one_segment_that_fills_the_row_is_the_row_bit_for_bit(hist,
                                                               sub_block):
    """``[1, T]`` through the packed kernel equals ``[1, T]`` through the
    rectangle kernel exactly: the same tiles in the same order over the
    same rows, whatever the sub-blocks."""
    c = _case(512, 4, 2, hists=[hist], clens=[512])
    pad = jnp.ones_like(c["bt"])
    packed = paged_flash_prefill_packed(
        c["q"], c["k"], c["v"], jnp.asarray([512, 0, 0], jnp.int32),
        c["kp"], c["vp"], jnp.concatenate([c["bt"], pad, pad]),
        jnp.asarray([hist, 0, 0], jnp.int32), jnp.int32(LAYER),
        block_size=BS, interpret=True, sub_block=sub_block)
    assert np.array_equal(np.asarray(packed), np.asarray(_kernel(c)))


def _padding_set_to(c, clens, value, names):
    """``c`` with every token past a row's chunk set to +-``value`` in its
    operands ``names``."""
    c = dict(c)
    for name in names:
        x = np.array(c[name])
        for i, cl in enumerate(clens):
            x[i, cl:] = value
            x[i, cl::2] = -value
        c[name] = jnp.asarray(x)
    return c


def test_what_lies_between_segments_is_inert_and_comes_out_finite():
    """A rectangle's rows hold 1, 77 and 0 tokens: whatever FINITE values
    the padding of q, k and v holds (the engine's is the model's own
    projections of padding tokens), the live tokens come out the same bit
    for bit and equal the oracle, and every output at a padding token is
    finite (no block of the output is left unwritten; a recurrence
    downstream multiplies padding by a mask, and NaN times zero is NaN)."""
    clens = [1, 77, 0]
    c = _case(128, 4, 2, hists=[40, 0, 64], clens=clens)
    ref = np.asarray(_window_reference(c))
    outs = [np.asarray(_kernel(_padding_set_to(c, clens, value,
                                               ("q", "k", "v"))))
            for value in (0.0, 1e4)]
    for out in outs:
        assert np.all(np.isfinite(out))
        for i, cl in enumerate(clens):
            np.testing.assert_allclose(out[i, :cl], ref[i, :cl], atol=ATOL,
                                       rtol=0)
    for i, cl in enumerate(clens):
        assert np.array_equal(outs[0][i, :cl], outs[1][i, :cl])


@pytest.mark.parametrize("lens,nq,tq,want", [
    # (segment, block) pairs, block by block; a block nobody reaches has a
    # pair of no tokens, and so have the entries past the last block.
    ([100, 120, 0], 1, 256, [(0, 0, 100), (1, 0, 120), (0, 0, 0)]),
    ([300, 10, 150], 2, 256,
     [(0, 0, 256), (0, 1, 44), (1, 1, 10), (2, 1, 150)]),
    ([40, 600, 100], 3, 256,
     [(0, 0, 40), (1, 0, 216), (1, 1, 256), (1, 2, 128), (2, 2, 100)]),
    ([90, 30], 2, 256, [(0, 0, 90), (1, 0, 30), (0, 1, 0)]),
    ([256, 256], 2, 256, [(0, 0, 256), (1, 1, 256), (0, 1, 0)]),
    ([0, 0], 2, 256, [(0, 0, 0), (0, 1, 0), (0, 1, 0)]),
])
def test_the_pairs_of_a_packed_row(lens, nq, tq, want):
    seg, blk, start, end, tokens = (np.asarray(x) for x in packed_pairs(
        jnp.asarray(lens, jnp.int32), nq, tq))
    assert len(seg) == nq + len(lens) - 1
    assert [(int(s) if n else 0, int(b), int(n))
            for s, b, n in zip(seg, blk, tokens)] == want
    assert int(tokens.sum()) == sum(lens)
    offs = np.concatenate([[0], np.cumsum(lens)])
    for s, a, e, n in zip(seg, start, end, tokens):
        if n:
            assert (a, e) == (offs[s], offs[s + 1])
    assert np.all(np.diff(blk) >= 0)


@pytest.mark.parametrize("lens,t,tq", [
    ([128, 1] + [0] * 14, 128, 128),        # 16 x 128
    ([0, 200, 0, 5], 256, 128),             # rows that hold nothing, anywhere
    ([256, 100, 7], 256, 128),              # 3 x 256
    ([2000], 2048, 256),                    # 1 x 2048
], ids=["16x128", "4x256-gaps", "3x256", "1x2048"])
def test_the_pairs_of_a_rectangle_laid_as_a_row(lens, t, tq):
    """``rectangle_pairs`` against a Python loop: segment r begins at
    ``r * t``, a block of the row is one segment's and has one pair; a
    segment's queries end with its last live block, its keys with its
    chunk; a block behind its row's chunk holds no token."""
    seg, blk, start, end, tokens, key_end = (
        np.asarray(x) for x in rectangle_pairs(
            jnp.asarray(lens, jnp.int32), t, tq))
    want = []
    for b in range(len(lens) * t // tq):
        r, first = b * tq // t, b * tq % t
        live = lens[r] > first
        want.append((r, b, r * t, r * t + -(-lens[r] // tq) * tq,
                     tq if live else 0, r * t + lens[r]))
    got = list(zip(*(x.tolist() for x in (seg, blk, start, end, tokens,
                                          key_end))))
    assert got == want
    # Every valid token lies in a live block of its own segment.
    assert sum(-(-n // tq) for n in lens) == int((tokens > 0).sum())


@pytest.mark.parametrize("tq,g,itemsize,want", [
    (256, 8, 2, 32),      # qwen2.5-3b: 256 query rows a sub-block
    (256, 4, 2, 64),      # mistral-7b
    (128, 8, 2, 32),
    (256, 1, 2, 256),     # one query head a KV head: the block whole
    (256, 64, 2, 16),     # never under a sublane tile of the dtype
    (32, 2, 4, 32),
])
def test_sub_blocks_keep_256_query_rows(tq, g, itemsize, want):
    assert packed_sub_block(tq, g, itemsize) == want


def test_attend_over_a_packed_view_agrees_in_both_executions():
    """``attend`` over a view that says ``seg_lens``: the kernel (the view
    says ``interpret``) and the other backends' execution, which takes the
    row apart into a row a segment and runs ``window_attention``."""
    t, h, hkv = 256, 4, 2
    hists, clens = [0, 40, 300, 0], [70, 90, 50, 0]
    c = _case(t, h, hkv, hists=hists, clens=clens)
    q, k, v = _pack(c, clens, t)
    kp, vp = (jnp.nan_to_num(c[x]) for x in ("kp", "vp"))
    view = KVView(pool_k=kp, pool_v=vp, block_tables=c["bt"],
                  kv_lens=c["kv_lens"], block_size=BS,
                  seg_lens=jnp.asarray(clens, jnp.int32))
    positions = jnp.zeros((1, t), jnp.int32)     # read by neither
    row_len = jnp.asarray([sum(clens)], jnp.int32)
    outs = [np.asarray(attend(q, k, v, positions, row_len,
                              view._replace(interpret=interpret),
                              jnp.int32(LAYER)))
            for interpret in (False, True)]
    live = sum(clens)
    np.testing.assert_allclose(outs[0][0, :live], outs[1][0, :live],
                               atol=ATOL, rtol=0)
    assert not outs[0][0, live:].any()
    # Latent rows have their packed form since PR 48 (its twin:
    # tests/test_paged_prefill_latent.py), and a packed view is one row.
    assert prefill_kernel_covers(
        256, 32, 1, 640, 512, BS, (jnp.bfloat16,), latent=True, packed=True)
    with pytest.raises(ValueError, match="does not cover"):
        attend(jnp.concatenate([q, q]), jnp.concatenate([k, k]),
               jnp.concatenate([v, v]), positions, row_len, view,
               jnp.int32(LAYER))


@pytest.mark.asyncio
async def test_engine_sixteen_prompts_packed_serve_what_each_serves_alone():
    """Sixteen prompts of mixed lengths at once through an engine whose
    prefill dispatches are packed rows (``prefill_packs``: paged K/V rows
    read in place, no state): the same greedy tokens and log-probabilities
    as each prompt alone, one that crosses the token budget among them;
    the dispatches carried several segments each and padded only their
    rows' ends."""
    import asyncio

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import ServingEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    rng = np.random.default_rng(46)
    lens = [5, 130, 17, 260, 64, 33, 700, 90, 8, 200, 45, 128, 3, 77, 150,
            21]
    prompts = [[int(x) for x in rng.integers(3, 200, n)] for n in lens]
    cfg = EngineConfig(
        model="tiny-llama-128dh", max_model_len=1024, num_kv_blocks=1024,
        attn_impl="paged", num_decode_steps=4, dtype="float32",
        max_num_batched_tokens=512, max_num_seqs=16, max_prefill_seqs=16,
        block_size=16, enable_warmup=False, enable_prefix_caching=False,
    )
    eng = ServingEngine(cfg)
    await eng.start()

    async def one(i):
        out = None
        async for o in eng.generate(
            prompt_token_ids=prompts[i], sampling=SamplingParams(
                temperature=0.0, max_tokens=5, ignore_eos=True,
                logprobs=2)):
            out = o
        return out.token_ids, [lp[0] for lp in out.logprobs]

    try:
        assert eng.runner.prefill_packs and eng.scheduler.prefill_packed
        assert {f[0] for f in eng.runner.reachable_prefill_families()} == \
            {1}
        alone = [await one(i) for i in range(len(prompts))]
        before = eng.stats()
        together = await asyncio.gather(*map(one, range(len(prompts))))
        after = eng.stats()
    finally:
        await eng.stop()
    for (toks_a, lps_a), (toks_t, lps_t) in zip(alone, together):
        assert toks_a == toks_t and len(toks_t) == 5
        np.testing.assert_allclose(lps_a, lps_t, atol=1e-4, rtol=0)

    def delta(name):
        return after[name] - before[name]

    dispatches = delta("prefill_dispatches_total")
    assert delta("prefill_tokens_issued_total") == sum(lens)
    assert delta("prefill_segments_total") == \
        delta("prefill_rows_issued_total") > dispatches
    # Only the rows' ends are padding: under a power of two of the whole.
    assert delta("prefill_tokens_padded_total") < 2 * sum(lens)
