"""The pieces under the MiMo-V2 family against their ``jnp`` statements: the
window ring's attend and write, the ring's decode step as a Pallas kernel in
place (interpret mode), the sink merged by its statistics, the experts a
chip holds. tests/test_mimo_v2.py holds the whole forward to the reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import mimo_v2
from production_stack_tpu.models.config import TINY_MIMO_V2
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops import moe
from tests.mimo_v2_helpers import W, hf_config, ref


# ------------------------------------------------------------- the ring ops
def _dense_window_attention(q, k_all, v_all, pos_q, scale, sink, w):
    """Softmax with one more column: q [T, H, Dk] at positions pos_q over
    keys [S, Hkv, Dk] at positions 0..S-1."""
    h, hkv = q.shape[1], k_all.shape[1]
    k = jnp.repeat(k_all, h // hkv, axis=1)
    v = jnp.repeat(v_all, h // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    j = jnp.arange(k.shape[0])
    dist = pos_q[:, None] - j[None, :]
    s = jnp.where(((dist >= 0) & (dist < w))[None], s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate(
            [s, jnp.broadcast_to(sink[:, None, None], s.shape[:2] + (1,))],
            -1)
        v = jnp.concatenate([v, jnp.zeros_like(v[:1])], 0)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("start,t,live", [
    (0, 1, 1), (5, 1, 1), (127, 1, 1), (128, 1, 1), (300, 1, 1),
    (0, 16, 16), (0, 16, 9), (120, 16, 16), (130, 16, 3),
    (0, 32, 32), (17, 32, 32), (0, 64, 64), (64, 64, 50), (16, 48, 48),
])
def test_window_ring_attend_and_write_are_the_masked_softmax(start, t, live):
    """The ring's two statements at a window of 16 keys against a dense
    masked softmax with the sink as one more column: a decode step, chunks
    shorter than, equal to and several windows long (blocks), padded rows,
    starts before and behind the first window; then the ring's contents."""
    w, h, hkv, dk, dv = 16, 4, 2, 24, 8
    rng = np.random.default_rng(start * 131 + t)
    total = start + t
    k_all = jnp.asarray(rng.normal(size=(total, hkv, dk)), jnp.float32)
    v_all = jnp.asarray(rng.normal(size=(total, hkv, dv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(t, h, dk)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    # The ring before the chunk: position p in slot p mod w, junk in the
    # slots nothing was written to.
    ring_k = np.full((1, 1, hkv, w, dk), 1e3, np.float32)
    ring_v = np.full((1, 1, hkv, w, dv), 1e3, np.float32)
    for p in range(start):
        ring_k[0, 0, :, p % w] = k_all[p]
        ring_v[0, 0, :, p % w] = v_all[p]
    positions = (start + jnp.arange(t))[None]
    lens = jnp.array([live])
    got = att.window_ring_attend(
        q[None], k_all[None, start:], v_all[None, start:], positions, lens,
        jnp.asarray(ring_k[:, 0]), jnp.asarray(ring_v[:, 0]),
        scale=dk ** -0.5, sink=sink)
    want = _dense_window_attention(
        q[:live], k_all[:start + live], v_all[:start + live],
        positions[0, :live], dk ** -0.5, sink, w)
    np.testing.assert_allclose(got[0, :live], want, atol=2e-5)
    new_k, new_v = att.window_ring_write(
        (jnp.asarray(ring_k), jnp.asarray(ring_v)), jnp.int32(0),
        (k_all[None, start:], v_all[None, start:]), positions, lens)
    for p in range(start + live):
        if p >= start + live - w:
            np.testing.assert_array_equal(new_k[0, 0, :, p % w], k_all[p])
            np.testing.assert_array_equal(new_v[0, 0, :, p % w], v_all[p])
    if start + live < w:        # slots nothing reached keep what they held
        assert float(new_k[0, 0, 0, w - 1, 0]) == 1e3


# ---- a decode step in place in the carried rings (ops/pallas/window_ring.py)
RING_STEP_CASES = {
    # positions of the bucket's rows, which of them take a token
    # (six rows each: one program of the interpreted kernel a dtype)
    "below-the-window": ([0, 1, 5, 15, 16, 126], [1] * 6),
    "at-the-window": ([127, 128, 129, 143, 144, 255], [1] * 6),
    "several-wraps": ([256, 1000, 4095, 8191, 8192, 70001], [1] * 6),
    # A state slot's second sequence: its slots hold the first one's keys,
    # which no query of the new sequence may see.
    "a-slot-reused": ([0, 3, 40, 100, 17, 31], [1] * 6),
    "dead-rows-between": ([7, 300, 131, 64, 2000, 90], [1, 0, 1, 0, 0, 1]),
    "none-live": ([7, 300, 131, 64, 2, 1], [0] * 6),
    "one-live-last": ([7, 300, 131, 640, 3, 911], [0, 0, 0, 0, 0, 1]),
}


# (KV heads, queries a KV head, slots, key lanes, value lanes) of a ring:
RING_STEP_HEADS = {
    # MiMo-V2.5's: 8 queries a KV head, keys of 192 lanes in rows of 256
    "8-queries": (2, 8, 128, 192, 128),
    # Phi-4-mini-flash's packed differential rows: 4 queries a KV row (half
    # a sublane tile), an odd number of rows (3 for its 10), a window of
    # several tiles of slots, keys and values of 128 lanes
    "4-queries": (3, 4, 64, 128, 128),
}


def _ring_step_params():
    """Every case of the first head shape in both dtypes, and of the second
    a subset in bfloat16 (float32 adds nothing there: the layout of a
    head's queries is the dtype's only where it is 16 bits wide)."""
    second = ("below-the-window", "at-the-window", "several-wraps",
              "a-slot-reused", "dead-rows-between", "none-live")
    out = [(case, sink, dtype, "8-queries")
           for dtype in ("bfloat16", "float32")
           for sink in ("sink", "no-sink") for case in RING_STEP_CASES]
    out += [(case, sink, "bfloat16", "4-queries")
            for sink in ("sink", "no-sink") for case in second]
    return [pytest.param(*p, id="-".join(
        p if p[3] != "8-queries" else p[:3])) for p in out]


@pytest.mark.parametrize("case,sink,dtype,heads", _ring_step_params())
def test_ring_step_kernel_is_the_jnp_statement_in_place(case, sink, dtype,
                                                        heads):
    """``ring_step_in_place`` (interpreted) against ``window_ring_step_jnp``
    at the two published head shapes (``RING_STEP_HEADS``; few KV heads and
    three layers here): the attention of every live row within the dtype's
    rounding, the rings EQUAL bit for bit in every slot, every dead row and
    every other layer. Every slot holds finite junk before the step
    (another sequence's keys), so a slot the visibility should hide and
    does not shows. The second shape's window is half the first's, so its
    positions are taken at half theirs where the case means the window."""
    positions, live = RING_STEP_CASES[case]
    hkv, g, w, dk, dv = RING_STEP_HEADS[heads]
    if w < 128 and case in ("below-the-window", "at-the-window"):
        positions = [p // (128 // w) for p in positions]
    b, nl, at = len(positions), 3, 1
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(len(case) * 7 + sum(positions))

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    lanes = mimo_v2.ring_width(dk)
    ring_k = jnp.pad(draw(b, nl, hkv, w, dk, scale=3.0),
                     ((0, 0),) * 4 + ((0, lanes - dk),))
    ring_v = draw(b, nl, hkv, w, dv, scale=3.0)
    q, k, v = draw(b, 1, hkv * g, dk), draw(b, 1, hkv, dk), \
        draw(b, 1, hkv, dv)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    lens = jnp.asarray(live, jnp.int32)
    sinks = jnp.asarray(rng.standard_normal(hkv * g) * 2 + 2, jnp.float32) \
        if sink == "sink" else None
    args = ((ring_k, ring_v), jnp.int32(at), q, k, v, pos, lens)
    want_o, want_rings = att.window_ring_step_jnp(
        *args, scale=dk ** -0.5, sink=sinks)
    got_o, got_rings = att.window_ring_step(
        *args, scale=dk ** -0.5, sink=sinks, interpret=True)
    assert got_o.shape == want_o.shape == (b, 1, hkv * g, dv)
    assert got_o.dtype == dt
    alive = np.asarray(live, bool)
    if alive.any():
        np.testing.assert_allclose(
            np.asarray(got_o, np.float32)[alive],
            np.asarray(want_o, np.float32)[alive],
            atol=3e-2 if dtype == "bfloat16" else 2e-5)
    for got, want, old in zip(got_rings, want_rings, (ring_k, ring_v)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        # and what the statement says of them: one row a live row's head.
        changed = np.asarray(got != old).any(axis=(-1, -3))   # [B, NL, W]
        want_changed = np.zeros((b, nl, w), bool)
        for r in np.flatnonzero(alive):
            want_changed[r, at, positions[r] % w] = True
        np.testing.assert_array_equal(changed, want_changed)


def test_ring_step_is_the_kernel_only_where_the_rings_fit_it():
    """The choice of ``window_ring_step`` is by what it can see: rings of
    whole lane tiles whose KV heads have whole sublane tiles of queries or
    an even part of one, a row's heads ONE block of bounded bytes, and
    ``interpret`` hold the kernel (MiMo-V2.5's 8 queries over each of 8 KV
    heads, Phi-4-mini-flash's 4 over each of 10 rows of 512 slots, and both
    tiny presets as they fall: 2 over 2 of 128 slots, 4 over 2 of 64); rows
    of 192 lanes, a window that is no whole tile, 3 queries a KV head and a
    block past the byte bound the ``jnp`` form; and a program lowered for a
    CPU without the switch the ``jnp`` form too."""
    from production_stack_tpu.ops.pallas.window_ring import (
        BUFFER_BYTES,
        NUM_BUFS,
        supports_step_kernel,
        tile_rows,
    )

    def rings(hkv, w, dk, dv, dtype=jnp.bfloat16):
        return (jax.ShapeDtypeStruct((4, 2, hkv, w, dk), dtype),
                jax.ShapeDtypeStruct((4, 2, hkv, w, dv), dtype))

    assert tile_rows(jnp.bfloat16) == 16 and tile_rows(jnp.float32) == 8
    assert supports_step_kernel(*rings(8, 128, 256, 128), 64)
    assert supports_step_kernel(*rings(10, 512, 128, 128), 40)
    assert supports_step_kernel(*rings(2, 128, 128, 128), 4)
    assert supports_step_kernel(*rings(2, 64, 128, 128, jnp.float32), 8)
    assert not supports_step_kernel(*rings(8, 128, 192, 128), 64)
    assert not supports_step_kernel(*rings(8, 24, 256, 128), 64)
    assert not supports_step_kernel(*rings(2, 128, 128, 128), 6)
    # Twelve rows of 512 slots are 3 MiB a block, and NUM_BUFS of them past
    # the buffers' VMEM; in float32 ten are.
    assert NUM_BUFS * 10 * 512 * 256 * 2 <= BUFFER_BYTES \
        < NUM_BUFS * 12 * 512 * 256 * 2
    assert not supports_step_kernel(*rings(12, 512, 128, 128), 48)
    assert not supports_step_kernel(
        *rings(10, 512, 128, 128, jnp.float32), 40)

    def step(interpret):
        def fn(rk, rv, q, k, v, pos, lens):
            return att.window_ring_step(
                (rk, rv), 0, q, k, v, pos, lens, scale=1.0,
                interpret=interpret)
        b, (rk, rv) = 4, rings(2, 128, 256, 128)
        sds = jax.ShapeDtypeStruct
        return jax.jit(fn).lower(
            rk, rv, sds((b, 1, 16, 192), rk.dtype),
            sds((b, 1, 2, 192), rk.dtype), sds((b, 1, 2, 128), rk.dtype),
            sds((b, 1), jnp.int32), sds((b,), jnp.int32)).as_text(
                debug_info=True)

    assert att.ring_step_path(step(True)) == "pallas"
    assert att.ring_step_path(step(False)) == "xla"
    assert att.ring_step_path("HloModule jit__prefill_impl") is None


def test_forward_steps_through_the_kernel_as_through_the_jnp_statement():
    """A decode step of ``forward`` (T == 1) with the view's ``interpret``
    switch holds the ring's kernel where the rings fit it (here 8 queries a
    KV head, keys of 48 lanes in rows of 128) and comes out as the ``jnp``
    statement's: the hidden state of the live rows, every full layer's new
    K and V, and the rings bit for bit, a dead row's among them. The leading
    layer is a window layer here (traced outside the scan, no ``cond``) and
    the scan holds both kinds (two ``cond``s a layer, the step between
    them)."""
    mc = dataclasses.replace(
        TINY_MIMO_V2, num_heads=16, num_layers=4,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention", "sliding_attention"))
    params = mimo_v2.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    b, n = 3, 140
    rng = np.random.default_rng(5)
    prompt_ids = jnp.asarray(rng.integers(0, mc.vocab_size, (b, n)))
    lens = jnp.asarray([n, 37, n - 12], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    state = mimo_v2.forward(params, mc, prompt_ids, positions, lens)[3]
    assert [s.shape for s in state] == [(b, 3, 2, W, 128)] * 2
    toks = jnp.asarray(rng.integers(0, mc.vocab_size, (b, 1)))
    step_lens = jnp.asarray([1, 0, 1], jnp.int32)
    outs = {}
    for interpret in (False, True):
        view = att.KVView(interpret=interpret)
        fn = jax.jit(lambda st, view=view: mimo_v2.forward(
            params, mc, toks, lens[:, None], step_lens, view, state=st))
        outs[interpret] = fn(state)
        text = fn.lower(state).as_text(debug_info=True)
        assert att.ring_step_path(text) == ("pallas" if interpret else "xla")
    (h0, k0, v0, st0, _), (h1, k1, v1, st1, _) = outs[False], outs[True]
    live = np.asarray(step_lens, bool)
    np.testing.assert_allclose(h1[live], h0[live], atol=2e-5)
    np.testing.assert_allclose(k1[:, :, live], k0[:, :, live], atol=2e-5)
    np.testing.assert_allclose(v1[:, :, live], v0[:, :, live], atol=2e-5)
    # Layer 0's ring is written from the same inputs on both paths: bit for
    # bit. Deeper layers' keys come from hidden states that differ by the
    # order of a float32 sum; their untouched slots and the dead row do not.
    for got, want, old in zip(st1, st0, state):
        np.testing.assert_array_equal(got[:, 0], want[:, 0])
        np.testing.assert_array_equal(got[1], old[1])
        np.testing.assert_allclose(got, want, atol=2e-5)
        changed = np.asarray(got != old).any(axis=(-1, -3))    # [B, NL, W]
        want_changed = np.zeros(changed.shape, bool)
        for r in np.flatnonzero(live):
            want_changed[r, :, int(lens[r]) % W] = True
        np.testing.assert_array_equal(changed, want_changed)


def test_the_sink_merged_by_statistics_is_one_more_softmax_column():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(3, 5, 7)) * 4, jnp.float32)  # [B,H,S]
    v = jnp.asarray(rng.normal(size=(3, 7, 6)), jnp.float32)
    sink = jnp.asarray([-30.0, -1.0, 0.5, 4.0, 30.0], jnp.float32)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    out = jnp.einsum("bhs,bsd->bhd", p, v) / l[..., None]
    got = att.sink_merged(out, m, l, sink)
    full = jnp.concatenate(
        [s, jnp.broadcast_to(sink[None, :, None], (3, 5, 1))], -1)
    want = jnp.einsum("bhs,bsd->bhd", jax.nn.softmax(full, -1)[..., :-1], v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # A sink far below every score changes nothing, one far above takes all.
    np.testing.assert_allclose(got[:, 0], out[:, 0], rtol=1e-5)
    assert float(jnp.abs(got[:, 4]).max()) < 1e-6


# ---------------------------------------------------------- the expert share
def test_pairs_of_experts_held_elsewhere_are_neither_computed_nor_counted():
    rng = np.random.default_rng(1)
    n, d, f, e, k = 6, 8, 4, 3, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(e, d, 2 * f)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, e, (n, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 0], bool)
    here = jnp.asarray(rng.integers(0, 2, (n, k)), bool)
    y, stats = moe.expert_ffn(x, idx, w, valid, wgu, wd, here=here)
    want = np.zeros((n, d), np.float32)
    for i in range(n):
        for j in range(k):
            if valid[i] and here[i, j]:
                h = x[i] @ wgu[idx[i, j]]
                want[i] += w[i, j] * np.asarray(
                    (jax.nn.silu(h[:f]) * h[f:]) @ wd[idx[i, j]])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    counted = int((valid[:, None] & here).sum())
    assert stats.shape == (len(moe.STATS_EP),) and len(moe.STATS) == 4
    assert int(stats[0]) == counted
    assert int(stats[4]) == int(valid.sum()) * k - counted
    # Without ``here`` the four counters and every valid pair, as before.
    _, plain = moe.expert_ffn(x, idx, w, valid, wgu, wd)
    assert plain.shape == (4,) and int(plain[0]) == int(valid.sum()) * k


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One sparse layer of the tiny model (16 experts, top-4) cut into 16
    shares of one expert: what every share's module computes for its own
    expert, summed, is the uncut reference's layer (there is no shared
    expert to count once)."""
    mc = TINY_MIMO_V2
    params = mimo_v2.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    sparse = params["layers"]["sparse"]
    layer = 1                                    # of the sparse stack
    lp = {k: v[layer] for k, v in sparse.items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 37, 128)),
                    jnp.float32)
    valid = jnp.ones((1, 37), bool)
    # The uncut reference: its FFN of the normed stream.
    u = ref.rms_norm(x[0], lp["ffn_norm"], mc.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.sparse_ffn(hf_config(mc), lp, u)
    total = jnp.zeros_like(x)
    elsewhere = 0
    for rank in range(16):
        share = dataclasses.replace(mc, n_routed_experts=1, ep_size=16,
                                    ep_rank=rank)
        experts = tuple(lp[k][rank:rank + 1] for k in ("w_gate_up",
                                                       "we_down"))
        rest = {k: v for k, v in lp.items()
                if k not in ("w_gate_up", "we_down")}
        out, stats, idx = mimo_v2._sparse_ffn(
            share, x, rest, experts, 0, valid, False)
        np.testing.assert_array_equal(idx, chosen)   # one router, 16 wide
        total = total + (out - x)
        elsewhere += int(stats[4])
        assert int(stats[0]) == int((chosen == rank).sum())
    np.testing.assert_allclose(total[0], want, atol=2e-5)
    # Every pair is computed on exactly one of the sixteen chips.
    assert elsewhere == 15 * 37 * mc.num_experts_per_tok
