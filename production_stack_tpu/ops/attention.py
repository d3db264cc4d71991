"""Paged attention over a block-pooled KV cache.

This is the TPU-native replacement for the paged-attention CUDA kernels that
live inside the reference's external vLLM engine images (the reference repo
itself ships none; see SURVEY.md §2.2 "vLLM engine").

Design: the KV cache is a flat pool of slots ``[num_slots, kv_heads, head_dim]``
per layer (num_slots = num_blocks * block_size; block 0 is the reserved null
block). A sequence's blocks are listed in its ``block_table``; slot ``j`` in
page order holds the KV for absolute token position ``j``. Both prefill chunks
(T > 1) and decode (T = 1) use the same entry point, so chunked prefill and
decode batches share one compiled program shape family.

``paged_attention_xla`` (pure jnp gather + einsum) is the reference every
kernel is tested against. What serving runs: the Pallas kernels of
ops/pallas/paged_attention.py over the pool in place — flash decode (T ==
1) and, in a program lowered for a TPU, flash prefill (T > 1: a chunk over
its rows' history and itself) — and ``window_attention``, the statement of
the chunk's attention, the kernels' oracle, and the path of everything the
prefill kernels do not cover: a backend without them, the window decode
path, speculative verify (``chunk_bias``), the sequence-parallel ring, an
int8 or kv-head-sharded pool, K/V or latent rows alike.

The serving path's seam: the runner describes the KV a forward
may read as one ``KVView``, a model module hands it unopened to ``attend``
from inside its layer, and ``attend`` picks the kernel. ``scan_layers`` runs
a model's layer function over the stacked layers and slices the view per
layer. No model file names a kernel.

Two things are told apart by name. The engine's WINDOW (``KVView.win_k``,
``window_attention``, ``gather_window``) is a gathered buffer of a row's
history: an execution, which changes no result. A layer's SPAN (``attend``'s
``span``, a model's sliding-window attention) is part of the model's
equations: a query at position i sees the keys at ``i - span < j <= i`` and
no others, on every execution, and the paged kernels neither fetch nor score
the superpages that lie wholly behind it.
"""

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = float(jnp.finfo(jnp.float32).min)

# Query-block size for the chunked prefill path: bounds the materialized
# score tensor at [Hkv, B, G*QBLOCK, S_total] f32 regardless of chunk length.
QBLOCK = 256


def _seg_scores(qf, keys):
    """q [Hkv, B, M, Dh] x keys [Hkv, B, S, Dh] -> [Hkv, B, M, S] f32.

    Both operands share leading (Hkv, B) batch dims in the SAME order, so XLA
    lowers this to a batched matmul with no physical transpose of the keys —
    load-bearing: a relayout of the KV window would double its HBM traffic.
    """
    return jax.lax.dot_general(
        qf, keys,
        dimension_numbers=(((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )


def _seg_pv(p, values):
    """p [Hkv, B, M, S] x values [Hkv, B, S, Dh] -> [Hkv, B, M, Dh] f32."""
    return jax.lax.dot_general(
        p.astype(values.dtype), values,
        dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )


def window_attention(
    q: jax.Array,            # [B, T, H, Dh] chunk queries (post-rope)
    k_chunk: jax.Array,      # [B, T, Hkv, Dh] chunk keys (post-rope)
    v_chunk: jax.Array,      # [B, T, Hkv, Dh]
    positions: jax.Array,    # [B, T] absolute position per query token
    chunk_lens: jax.Array,   # [B] valid (non-pad) tokens per row
    win_k: Optional[jax.Array] = None,   # [Hkv, B, S, Dh] gathered history
    win_v: Optional[jax.Array] = None,
    win_len: Optional[jax.Array] = None,  # [B] valid history per row
    ring_k: Optional[jax.Array] = None,   # [Hkv, B, R, Dh] intra-dispatch KV
    ring_v: Optional[jax.Array] = None,
    ring_pos: Optional[jax.Array] = None,  # [B, R] position per entry
    *,
    scale: Optional[float] = None,
    chunk_bias: Optional[jax.Array] = None,  # [T, T] additive f32 {0, -inf}
    qblock: int = QBLOCK,
    span: Optional[jax.Array] = None,        # [] int32: the layer's span
) -> jax.Array:
    """Dense attention against up to three key segments, TPU-shaped.

    The statement of a chunk's attention, and what runs wherever the
    Pallas prefill kernel does not (see the module docstring): the caller
    gathers the paged KV pool ONCE per dispatch into a contiguous
    [Hkv, B, S, Dh] window (slot s holds the sequence's absolute position
    s), and attention is plain masked batched matmuls — no gather ops
    inside the step. Its price, which the kernel does not pay (PERF.md §6,
    PR 35): every row is scored against the whole window and the whole
    chunk, and the float32 scores [Hkv, B, G*TQ, S + T] cross HBM for the
    max, the exp, the sum and the value product.

    Segments:
      * window — history tokens already in the pool (valid where s < win_len);
      * ring   — tokens produced by earlier steps of the SAME fused decode
        dispatch, not yet scattered to the pool (valid where
        ring_pos < position; unwritten entries carry a sentinel position);
      * chunk  — the current tokens themselves, causal within the chunk
        (valid where position_key <= position_query and key_idx < chunk_len).

    ``chunk_bias``: optional [T, T] additive f32 bias ADDED to the in-chunk
    causal mask — the speculative token-tree segment (ops/tree_mask.py),
    where sibling draft branches share a position and must not attend each
    other. The bias is an exact AND with position-causality (tree ancestry
    implies smaller depth, hence smaller position), shared across rows.
    Only the single-Q-block path supports it (speculative verify chunks are
    N+W <= 24 tokens, far under QBLOCK).

    ``span`` (see the module docstring): in every segment a key at position
    j is valid for a query at position i only where ``i - j < span`` as
    well. None (static): the masks below, and the program, as they were.

    Returns [B, T, H, Dh] in q.dtype.
    """
    b, t, h, dh = q.shape
    hkv = k_chunk.shape[2]
    g = h // hkv
    if scale is None:
        scale = dh ** -0.5

    # [B, T, H, Dh] -> [Hkv, B, G*T, Dh]: (Hkv, B) leading to match segments.
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf = qf.reshape(b, t, hkv, g, dh).transpose(2, 0, 3, 1, 4)  # [Hkv,B,G,T,Dh]
    kc = k_chunk.transpose(2, 0, 1, 3)    # [Hkv, B, T, Dh]
    vc = v_chunk.transpose(2, 0, 1, 3)

    # Additive mask biases — f32 {0,-inf}. Per-(row,key) masks are small and
    # built once; the per-(query,key) causal masks are built INSIDE each
    # Q-block from the block's positions, so at most [B, QBLOCK, T] exists at
    # a time (a precomputed [B, T, T] bias scanned as an xs operand costs
    # 512 MiB of HBM at T=4096, B=8 — advisor r2 finding).
    neg = jnp.float32(_NEG_INF)
    t_idx = jnp.arange(t, dtype=jnp.int32)
    chunk_valid = t_idx[None, :] < chunk_lens[:, None]              # [B, T]
    win_bias = None
    if win_k is not None:
        s = win_k.shape[2]
        s_idx = jnp.arange(s, dtype=jnp.int32)
        win_bias = jnp.where(s_idx[None, :] < win_len[:, None], 0.0, neg)  # [B, S]

    def in_span(pos_q, pos_k):
        # [B, TQ] x [B, S] -> [B, TQ, S]: the key lies inside the span.
        return pos_q[:, :, None] - pos_k[:, None, :] < span

    def per_query(bias, tq):
        # [B, TQ, S] -> [1, B, G*TQ, S]: a score block's rows.
        return jnp.broadcast_to(
            bias[:, None, :, :], (b, g, tq, bias.shape[-1])
        ).reshape(1, b, g * tq, bias.shape[-1])

    def q_block(qb, pos_q):
        # qb: [Hkv, B, G, TQ, Dh]; pos_q: [B, TQ] query positions
        tq = qb.shape[3]
        m = g * tq
        qb = qb.reshape(hkv, b, m, dh)
        seen = chunk_valid[:, None, :] \
            & (positions[:, None, :] <= pos_q[:, :, None])
        if span is not None:
            seen = seen & in_span(pos_q, positions)
        cb = jnp.where(seen, 0.0, neg)                      # [B, TQ, T]
        if chunk_bias is not None:
            # Clamped add: both masks bottom out at _NEG_INF, and
            # (-inf) + (-inf) would overflow the finite sentinel.
            cb = jnp.maximum(cb + chunk_bias[None, :, :], neg)
        segs = []
        if win_k is not None:
            sw = _seg_scores(qb, win_k)
            if span is None:
                segs.append(sw + win_bias[None, :, None, :])
            else:
                # Slot s of the window holds position s.
                segs.append(sw + per_query(jnp.where(
                    in_span(pos_q, jnp.broadcast_to(s_idx, (b, s))),
                    win_bias[:, None, :], neg), tq))
        if ring_k is not None:
            seen = ring_pos[:, None, :] < pos_q[:, :, None]
            if span is not None:
                seen = seen & in_span(pos_q, ring_pos)
            rb = jnp.where(seen, 0.0, neg)                  # [B, TQ, R]
            sr = _seg_scores(qb, ring_k)
            rb4 = jnp.broadcast_to(
                rb[:, None, :, :], (b, g, tq, rb.shape[-1])
            ).reshape(1, b, m, rb.shape[-1])
            segs.append(sr + rb4)
        sc = _seg_scores(qb, kc)
        cb4 = jnp.broadcast_to(
            cb[:, None, :, :], (b, g, tq, t)
        ).reshape(1, b, m, t)
        segs.append(sc + cb4)

        mx = segs[0].max(-1, keepdims=True)
        for ss in segs[1:]:
            mx = jnp.maximum(mx, ss.max(-1, keepdims=True))
        ps = [jnp.exp(ss - mx) for ss in segs]
        denom = sum(p.sum(-1, keepdims=True) for p in ps)
        vals = ([win_v] if win_k is not None else []) + \
               ([ring_v] if ring_k is not None else []) + [vc]
        out = sum(_seg_pv(p, val) for p, val in zip(ps, vals))
        out = out / denom                                   # [Hkv, B, M, Dh]
        return out.reshape(hkv, b, g, tq, dh)

    if t <= qblock:
        out = q_block(qf, positions)
    else:
        assert chunk_bias is None, \
            "chunk_bias (tree speculation) requires t <= QBLOCK"
        assert t % qblock == 0, "token bucket must be a multiple of QBLOCK"
        nb = t // qblock
        qs = qf.reshape(hkv, b, g, nb, qblock, dh).transpose(3, 0, 1, 2, 4, 5)
        pos_qs = positions.reshape(b, nb, qblock).transpose(1, 0, 2)

        def body(_, xs):
            qb, pos_q = xs
            return (), q_block(qb, pos_q)

        _, outs = jax.lax.scan(body, (), (qs, pos_qs))     # [nb, Hkv,B,G,QB,Dh]
        out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(hkv, b, g, t, dh)

    # [Hkv, B, G, T, Dh] -> [B, T, H, Dh]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, t, h, dh).astype(q.dtype)


def dense_decode_stats(
    q: jax.Array,         # [B, H, Dh] decode queries (post-rope, UNscaled)
    keys: jax.Array,      # [Hkv, B, S, Dh]
    values: jax.Array,    # [Hkv, B, S, Dh]
    bias: jax.Array,      # [B, S] additive f32 {0, -inf} validity mask
    *,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash-style stats for a small dense key segment (decode T == 1).

    Used for the intra-dispatch ring + current-token segment when the pool
    segment runs in the Pallas kernel (paged_flash_decode_stats). Returns
    (out [B, H, Dh] normalized, m [B, H] f32, l [B, H] f32); a row whose bias
    masks ALL keys returns (0, -inf, 0) — a no-op under merge.
    """
    b, h, dh = q.shape
    hkv = keys.shape[0]
    g = h // hkv
    if scale is None:
        scale = dh ** -0.5
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf = qf.reshape(b, hkv, g, dh).transpose(1, 0, 2, 3)  # [Hkv, B, G, Dh]
    scores = _seg_scores(qf, keys) + bias[None, :, None, :]  # [Hkv, B, G, S]
    m = jnp.max(scores, axis=-1)                             # [Hkv, B, G]
    # In a fully-masked row every score equals the mask bias, so
    # exp(score - m) would be exp(0) = 1; mask p explicitly (real scores are
    # tiny against _NEG_INF, so the threshold is unambiguous).
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(scores > jnp.float32(_NEG_INF) / 2, p, 0.0)
    l = jnp.sum(p, axis=-1)                                  # [Hkv, B, G]
    out = _seg_pv(p, values)                                 # [Hkv, B, G, Dh]
    out = out / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(1, 0, 2, 3).reshape(b, h, dh).astype(q.dtype)
    mt = jnp.where(l > 0, m, -jnp.inf)
    return out, mt.transpose(1, 0, 2).reshape(b, h), \
        l.transpose(1, 0, 2).reshape(b, h)


def merge_attention_segments(
    out_a: jax.Array, m_a: jax.Array, l_a: jax.Array,   # [B,H,Dh],[B,H],[B,H]
    out_b: jax.Array, m_b: jax.Array, l_b: jax.Array,
) -> jax.Array:
    """Flash-merge two NORMALIZED attention segments with their softmax stats
    into the attention over the union of their keys. Safe when one segment is
    empty (m = -inf, l = 0); at least one segment must have a valid key."""
    m = jnp.maximum(m_a, m_b)
    m = jnp.maximum(m, jnp.float32(_NEG_INF))  # both-empty guard
    wa = l_a * jnp.exp(m_a - m)
    wb = l_b * jnp.exp(m_b - m)
    denom = jnp.maximum(wa + wb, 1e-30)
    out = (
        out_a.astype(jnp.float32) * (wa / denom)[..., None]
        + out_b.astype(jnp.float32) * (wb / denom)[..., None]
    )
    return out.astype(out_a.dtype)


def sink_merged(out: jax.Array, m: jax.Array, l: jax.Array,
                sink: jax.Array) -> jax.Array:
    """A normalized attention segment ``out`` [..., H, Dv] with its softmax
    statistics ``m``, ``l`` [..., H] (float32) after a learned SINK joins
    the softmax's denominator: ``sink`` [H], one logit a query head, a key
    every query sees whose value is zero. One more segment of
    ``merge_attention_segments``: ``(0, sink, 1)``."""
    with jax.named_scope("attn_sink"):
        sink = jnp.broadcast_to(sink.astype(jnp.float32), m.shape)
        return merge_attention_segments(
            out, m, l, jnp.zeros_like(out), sink, jnp.ones_like(l))


def window_ring_positions(start: jax.Array, window: int) -> jax.Array:
    """The position each slot of a sequence's WINDOW RING holds before the
    token at ``start`` [B] is written: slot s keeps the newest position
    below ``start`` that is s modulo ``window``; a slot nothing was written
    to yet reads far behind every span. [B, window] int32."""
    s = jnp.arange(window, dtype=jnp.int32)[None, :]
    last = start[:, None] - 1
    held = last - jnp.mod(last - s, window)
    return jnp.where(held >= 0, held, -NO_SPAN)


def window_ring_attend(
    q: jax.Array,            # [B, T, H, Dk] queries (post-rope)
    k: jax.Array,            # [B, T, Hkv, Dk] this chunk's keys
    v: Optional[jax.Array],  # [B, T, Hkv, Dv]; None: latent rows
    positions: jax.Array,    # [B, T] consecutive from a row's positions[:, 0]
    chunk_lens: jax.Array,   # [B] valid tokens per row
    ring_k: jax.Array,       # [B, Hkv, W, Dk] the rows' rings BEFORE the chunk
    ring_v: Optional[jax.Array] = None,  # [B, Hkv, W, Dv]
    *,
    scale: float,
    sink: Optional[jax.Array] = None,    # [H] float32
    value_dim: Optional[int] = None,     # latent rows (``v`` None) only
) -> jax.Array:
    """Attention of a layer whose queries see the W newest keys up to
    themselves and whose sequences keep exactly those: a per-sequence ring
    of W slots in a state slot (models/config.py:StateSpec), position p in
    slot p mod W, instead of paged rows. The one statement for a decode
    step (T == 1) and a prefill chunk: a query at position i sees the ring's
    keys and the chunk's at ``0 <= i - j < W``; the ring slot the chunk's
    first token will overwrite holds position ``start - W`` and is out of
    every query's span already. A chunk of whole windows is scored a window
    of queries at a time against the window of keys before it (the ring for
    the first) and its own: [.., W, 2 W] scores a block, never [T, T].
    ``sink``: see ``sink_merged``. A ring whose rows are wider than the
    chunk's (whole 128-lane tiles: models/mimo_v2.py:ring_width) is read up
    to the chunk's width. Returns [B, T, H, Dv] in q.dtype.

    ``v`` None: LATENT rows (models/dots3_note.py): one ring, ``k`` the
    chunk's rows [B, T, 1, Dk], the values a row's first ``value_dim``
    lanes: [B, T, H, value_dim]. A window that does not divide the chunk
    (513 in 2048) is scored in blocks of the power of two of queries at or
    above ``W - 1``: block n sees the ``W`` entries before its own and its
    own, of the ring and the chunk end to end."""
    b, t, h, dk = q.shape
    hkv, w = ring_k.shape[1], ring_k.shape[2]
    g = h // hkv
    with jax.named_scope("ring_attend"):
        if v is None:
            ring_k = ring_k[..., :dk]
        else:
            ring_k, ring_v = ring_k[..., :dk], ring_v[..., :v.shape[-1]]
        start = positions[:, 0]
        t_idx = jnp.arange(t, dtype=jnp.int32)
        # A key past its row's length lies ahead of every query.
        pos_c = jnp.where(t_idx[None, :] < chunk_lens[:, None], positions,
                          NO_SPAN)
        pos_k = jnp.concatenate(
            [window_ring_positions(start, w), pos_c], axis=1)  # [B, W + T]
        keys = jnp.concatenate(
            [ring_k.astype(k.dtype), k.transpose(0, 2, 1, 3)], axis=2)
        vals = keys[..., :value_dim] if v is None else jnp.concatenate(
            [ring_v.astype(v.dtype), v.transpose(0, 2, 1, 3)], axis=2)
        # Blocks of queries and the keys each can see: whole windows where
        # the chunk is, else the one block of everything.
        tq = w if t % w == 0 and t > w else t
        if tq == t and t > w:
            # A window that does not divide the chunk: the smallest power
            # of two of queries whose block needs no key behind the W
            # entries before it, where that divides the chunk.
            tq = 1 << max(w - 2, 0).bit_length()
            tq = tq if t % tq == 0 and t > tq else t
        nb = t // tq

        def blocks(x, axis):
            # [.., W + T, ..] -> [.., nb, W + tq, ..]: block n holds the tq
            # entries of its queries and the W before them.
            if nb == 1:
                return jnp.expand_dims(x, axis)
            if tq != w:
                return jnp.stack(
                    [jax.lax.slice_in_dim(x, n * tq, n * tq + w + tq,
                                          axis=axis) for n in range(nb)],
                    axis=axis)
            shape = x.shape[:axis] + (nb, tq) + x.shape[axis + 1:]
            before = jax.lax.slice_in_dim(x, 0, t, axis=axis).reshape(shape)
            own = jax.lax.slice_in_dim(x, w, w + t, axis=axis).reshape(shape)
            return jnp.concatenate([before, own], axis=axis + 1)

        qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
        qf = qf.reshape(b, nb, tq, hkv, g, dk).transpose(0, 3, 1, 4, 2, 5)
        scores = jnp.einsum(
            "bhngqd,bhnkd->bhngqk", qf, blocks(keys, 2),
            preferred_element_type=jnp.float32)
        dist = positions.reshape(b, nb, tq)[:, :, :, None] \
            - blocks(pos_k, 1)[:, :, None, :]                  # [B, nb, tq, K]
        seen = (dist >= 0) & (dist < w)
        scores = jnp.where(seen[:, None, :, None], scores,
                           jnp.float32(_NEG_INF))
        m = jnp.max(scores, axis=-1)
        p = jnp.exp(scores - m[..., None])
        l = jnp.sum(p, axis=-1)
        vb = blocks(vals, 2)
        out = jnp.einsum("bhngqk,bhnkd->bhngqd", p.astype(vb.dtype), vb,
                         preferred_element_type=jnp.float32)
        out = out / l[..., None]
        # [B, Hkv, nb, G, tq, ..] -> [B, T, H, ..]
        out = out.transpose(0, 2, 4, 1, 3, 5).reshape(b, t, h, -1)
        m = m.transpose(0, 2, 4, 1, 3).reshape(b, t, h)
        l = l.transpose(0, 2, 4, 1, 3).reshape(b, t, h)
    if sink is not None:
        out = sink_merged(out, m, l, sink)
    return out.astype(q.dtype)


def window_ring_write(
    rings: Tuple[jax.Array, ...],   # each [B, Lr, Hkv, W, D*]: the rows' rings
    at: jax.Array,                  # [] int32: the layer's index among Lr
    new: Tuple[jax.Array, ...],     # each [B, T, Hkv, D*]: the chunk's rows
    positions: jax.Array,           # [B, T]
    chunk_lens: jax.Array,          # [B] valid tokens (0: the ring stays)
) -> Tuple[jax.Array, ...]:
    """The rows' rings after their chunk: position p in slot p mod W of
    layer ``at``, every other layer and the rings of rows without a valid
    token as they were. A decode step (T == 1) writes its one row in place
    (a scatter a row: nothing else of the carry moves); a chunk gathers,
    for every slot, the chunk's newest token that lands there. Where a
    ring's rows are wider than the chunk's, zeros fill them."""
    w = rings[0].shape[3]
    b, t = positions.shape
    with jax.named_scope("ring_write"):
        s = jnp.arange(w, dtype=jnp.int32)[None, :]
        if t == 1:
            # The one row into its slot of the layer, every other slot as
            # it was: a select over the layer's slice, which keeps the
            # rings in the layout the attention reads them in (a scatter of
            # [Hkv, D] rows would have the heads on the rows' axis).
            lands = (chunk_lens[:, None] > 0) \
                & (s == jnp.mod(positions[:, :1], w))            # [B, W]

            def taken(x):
                return x.transpose(0, 2, 1, 3)                   # [B,Hkv,1,D]
        else:
            end = positions[:, :1] + chunk_lens[:, None] - 1   # last valid
            src = chunk_lens[:, None] - 1 - jnp.mod(end - s, w)  # [B, W]
            lands = src >= 0

            def taken(x):
                return jnp.take_along_axis(
                    x, jnp.maximum(src, 0)[:, :, None, None],
                    axis=1).transpose(0, 2, 1, 3)
        out = []
        for ring, x in zip(rings, new):
            x = jnp.pad(x, ((0, 0),) * 3
                        + ((0, ring.shape[-1] - x.shape[-1]),))
            old = jax.lax.dynamic_index_in_dim(ring, at, 1, False)
            layer = jnp.where(lands[:, None, :, None],
                              taken(x).astype(ring.dtype), old)
            out.append(jax.lax.dynamic_update_index_in_dim(
                ring, layer, at, 1))
        return tuple(out)


def window_ring_step_jnp(rings, at, q, k, v, positions, chunk_lens, *,
                         scale, sink=None, value_dim=None):
    """``window_ring_step`` as plain ``jnp``: the statement of a decode
    step (``window_ring_attend`` over the layer's rings sliced out of the
    carry, then ``window_ring_write``), the path of a backend without the
    kernel, and the tests' oracle."""
    ring = tuple(jax.lax.dynamic_index_in_dim(r, at, 1, False)
                 for r in rings)
    attn = window_ring_attend(q, k, v, positions, chunk_lens, *ring,
                              scale=scale, sink=sink, value_dim=value_dim)
    new = (k,) if v is None else (k, v)
    return attn, window_ring_write(rings, at, new, positions, chunk_lens)


def window_ring_step(
    rings: Tuple[jax.Array, jax.Array],  # [B, Lr, Hkv, W, Dk], [.., Dv]
    at: jax.Array,            # [] int32: the layer's index among Lr
    q: jax.Array,             # [B, 1, H, Dk] queries (post-rope)
    k: jax.Array,             # [B, 1, Hkv, Dk] the step's keys
    v: jax.Array,             # [B, 1, Hkv, Dv]
    positions: jax.Array,     # [B, 1]
    chunk_lens: jax.Array,    # [B] 1: the row takes the token; 0: it is inert
    *,
    scale: float,
    sink: Optional[jax.Array] = None,    # [H] float32
    interpret: bool = False,
    value_dim: Optional[int] = None,     # latent rows (``v`` None) only
):
    """One decode step (T == 1) of a window layer on layer ``at`` of the
    rows' carried rings: (the attention [B, 1, H, Dv] of the rows that take
    a token, the rings with those rows' key and value in slot ``position mod
    W``). A row with ``chunk_lens`` 0 keeps its rings (its attention is
    nothing anybody reads), and with none live no ring moves.

    One algorithm, two executions, chosen HERE by what can be seen (the
    rule of ops/gated_delta.py:gdn_step_at): where the rings' shape fits
    it (``supports_step_kernel``: rows of whole lane tiles, 8, 16, .. or 4,
    2, 1 queries a KV head, a row's KV heads one block of bounded bytes;
    mimo-v2.5's 8 x 128 slots at 8 queries a head and phi-4-mini-flash's 10
    packed rows x 512 slots at 4 both do, since PR 55),
    a program LOWERED for a TPU (``lax.platform_dependent``) holds the
    Pallas kernel (ops/pallas/window_ring.py: in place in the carried rings,
    a live row's slots read once and one row written, a row that is not
    live untouched), and so does any program with ``interpret`` set (the
    runner's Pallas interpret switch: a CPU's tests); every other holds the
    ``jnp`` form. The two round alike (scores and statistics in float32,
    ``p`` in the values' dtype); only the order of the float32 sums over
    the slots differs, and the rings come out bit for bit the same.

    ``v`` None: LATENT rows in ONE ring ``[B, Lr, 1, W, D]`` (``rings`` a
    1-tuple; ``window_ring_attend``): the ``jnp`` form on every backend
    (the kernel steps two rings of whole tiles of slots; a ring of 513
    latent rows read in place is a later kernel's)."""
    if v is None:
        with jax.named_scope("ring_step"):
            return window_ring_step_jnp(
                tuple(rings), jnp.asarray(at, jnp.int32), q, k, None,
                positions, chunk_lens, scale=scale, sink=sink,
                value_dim=value_dim)
    from production_stack_tpu.ops.pallas.window_ring import (
        ring_step_in_place,
        supports_step_kernel,
    )

    def as_jnp(*args):
        return window_ring_step_jnp(*args, scale=scale, sink=sink)

    def as_kernel(rings, at, q, k, v, positions, chunk_lens, interpret=False):
        with jax.named_scope("ring_attend"):
            none = jnp.full((q.shape[2],), -jnp.inf, jnp.float32)
            o, *rings = ring_step_in_place(
                *rings, at, q[:, 0], k[:, 0], v[:, 0], positions[:, 0],
                chunk_lens, none if sink is None else sink,
                scale=scale, interpret=interpret)
        return o[:, None], tuple(rings)

    args = (tuple(rings), jnp.asarray(at, jnp.int32), q, k, v, positions,
            chunk_lens)
    with jax.named_scope("ring_step"):
        if not supports_step_kernel(*rings, q.shape[2]):
            return as_jnp(*args)
        if interpret:
            return as_kernel(*args, interpret=True)
        return jax.lax.platform_dependent(
            *args, tpu=as_kernel, default=as_jnp)


def ring_step_path(hlo_text: str):
    """Which execution of ``window_ring_step`` a compiled program
    (``as_text()``) holds: ``"pallas"``, ``"xla"``, or None where it holds
    no decode step of a window ring."""
    if "ring_step_in_place" in hlo_text:
        return "pallas"
    return "xla" if "/ring_step/" in hlo_text else None


class KVView(NamedTuple):
    """The KV a forward may read, every part optional. The runner builds it;
    a model passes it to ``scan_layers`` and ``attend`` without opening it.

    Inside ``scan_layers`` the window and the ring lose their leading layer
    axis; the pool keeps it (the kernel indexes it by ``layer``, so no layer
    of the pool is ever sliced out and copied)."""

    # History gathered once per dispatch (gather_window): slot s of row b
    # holds absolute position s, valid where s < win_len[b].
    win_k: Optional[jax.Array] = None      # [L, Hkv, B, S, Dh]
    win_v: Optional[jax.Array] = None
    win_len: Optional[jax.Array] = None    # [B]
    # KV of earlier steps of the same dispatch, not yet in the pool; an entry
    # is valid for a query where ring_pos < its position.
    ring_k: Optional[jax.Array] = None     # [L, Hkv, B, R, Dh]
    ring_v: Optional[jax.Array] = None
    ring_pos: Optional[jax.Array] = None   # [B, R]
    # The paged pool itself, read in place by the Pallas kernels: decode
    # (T == 1) and a prefill chunk (T > 1: kv_lens is then each row's
    # history, chunk_start; only a view ``prefill_kernel_covers`` says yes
    # to may hold the pool for a chunk, any other raises in ``attend``).
    # Scales set: int8 pool, dequantized in the decode kernel. tp_mesh set:
    # the pool is kv-head-sharded and the decode kernel runs under shard_map.
    pool_k: Optional[jax.Array] = None     # [L, Hkv, num_slots, Dh]
    pool_v: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None    # [L, Hkv, num_slots]
    v_scale: Optional[jax.Array] = None
    block_tables: Optional[jax.Array] = None  # [B, Mb]
    kv_lens: Optional[jax.Array] = None    # [B] tokens of each row in the pool
    # Set: the chunk is ONE packed row ([1, T]) whose tokens are S
    # sequences' chunks end to end from token 0, ``seg_lens`` [S] tokens
    # each, live ones first; ``block_tables`` and ``kv_lens`` are then a
    # SEGMENT each ([S, Mb], [S]). Pool views only, of K/V rows or of
    # latent rows (``prefill_kernel_covers(..., packed=True)``).
    seg_lens: Optional[jax.Array] = None
    block_size: int = 0
    interpret: bool = False
    tp_mesh: Optional[jax.sharding.Mesh] = None
    # Set: a prefill chunk (T > 1) rings its KV over the mesh's sp axis.
    sp_mesh: Optional[jax.sharding.Mesh] = None
    # [T, T] additive in-chunk bias: the speculative token tree
    # (ops/tree_mask.py). Window path only.
    chunk_bias: Optional[jax.Array] = None

    def act_dtype(self, default):
        """Dtype of the activations: the window's where there is one."""
        return self.win_k.dtype if self.win_k is not None else default


def attend(
    q: jax.Array,            # [B, T, H, Dh] queries (post-rope)
    k: jax.Array,            # [B, T, Hkv, Dh] this chunk's keys
    v: jax.Array,            # [B, T, Hkv, Dh]
    positions: jax.Array,    # [B, T] absolute position per token
    chunk_lens: jax.Array,   # [B] valid tokens per row
    view: KVView,            # ONE layer's view (see scan_layers)
    layer: Optional[jax.Array] = None,  # scalar layer index; pool views only
    *,
    scale: Optional[float] = None,      # None: Dh ** -0.5
    value_dim: Optional[int] = None,    # latent rows (``v`` None) only
    span: Optional[jax.Array] = None,   # [] int32: this layer's span
) -> jax.Array:
    """Causal attention of a chunk over itself and whatever ``view`` holds,
    by the kernel that fits: [B, T, H, Dh] in q.dtype.

    ``span`` (see the module docstring): a traced scalar of the LAYER, so
    that one scan holds bounded and unbounded layers (``NO_SPAN`` for the
    latter); every query sees the ``span`` newest keys up to itself. None
    is static: the programs of a model without one. K/V rows on one chip
    only: latent rows, a sharded pool, an int8 pool, the sequence-parallel
    ring and the speculative tree raise here and are refused at start
    (engine/config.py:refuse_what_a_span_cannot_follow).

    ``v`` None: LATENT rows (models/config.py:LatentKVSpec). ``k`` is then
    the chunk's rows [B, T, 1, W], every view part holds such rows, ``q``
    [B, T, H, W] is zero past the key's lanes, and the values are the rows'
    first ``value_dim`` lanes: [B, T, H, value_dim]."""
    if span is not None and (
            v is None or view.tp_mesh is not None or view.sp_mesh is not None
            or view.k_scale is not None or view.chunk_bias is not None):
        raise ValueError(
            "attend: a span over latent rows, a sharded or int8 pool, the "
            "sequence-parallel ring or a speculative tree has no execution")
    if v is None:
        return _attend_latent(q, k, positions, chunk_lens, view, layer,
                              scale, value_dim)
    b, t, h, dh = q.shape
    if scale is not None:
        # Every K/V path below scales the scores by Dh ** -0.5; a model's
        # own scale rides on the queries (one more rounding of q in its
        # own dtype where the ratio is no power of two).
        q = (q.astype(jnp.float32) * (scale * dh ** 0.5)).astype(q.dtype)
    if view.sp_mesh is not None and t > 1 and view.ring_k is None:
        from production_stack_tpu.ops.ring_attention import (
            ring_attention,
            ring_attention_kv,
        )

        if view.win_k is None:
            # Sequence-parallel prefill, first chunk: pure causal
            # self-attention (no history window, no intra-dispatch ring
            # buffer), computed exactly by ring attention over the sp axis —
            # KV shards stream around the ICI ring while each chip holds
            # O(T/sp) tokens (ops/ring_attention.py). Padding rows/tokens
            # carry positions beyond every real token of their row, so causal
            # masking by absolute position excludes them as keys.
            return ring_attention(q, k, v, positions, view.sp_mesh)
        # Sequence-parallel CONTINUATION chunk: the combined sequence
        # (gathered history window ++ chunk) is the ring's KV, sharded over
        # sp — each chip holds O((S_hist + T)/sp) keys instead of the whole
        # window, and ring attention engages on every chunk of a long
        # prefill, not just the first (VERDICT r4 weak #5). Window slot s
        # holds absolute position s; slots at or beyond win_len take a
        # sentinel position beyond every query so position-causality masks
        # them exactly like window_attention's validity bias.
        s_hist = view.win_k.shape[2]
        kw = view.win_k.transpose(1, 2, 0, 3)        # [B, S, Hkv, Dh]
        vw = view.win_v.transpose(1, 2, 0, 3)
        s_idx = jnp.arange(s_hist, dtype=jnp.int32)
        pos_w = jnp.where(
            s_idx[None, :] < view.win_len[:, None], s_idx[None, :],
            jnp.int32(2**30),
        )                                            # [B, S]
        return ring_attention_kv(
            q, positions,
            jnp.concatenate([kw, k], axis=1),
            jnp.concatenate([vw, v], axis=1),
            jnp.concatenate([pos_w, positions], axis=1),
            view.sp_mesh,
        )
    if view.pool_k is not None and t > 1:
        return _attend_chunk_over_pool(q, k, v, positions, chunk_lens, view,
                                       layer, span)
    if view.pool_k is not None:
        # Paged decode (T == 1): the pool segment runs in the Pallas
        # flash-decode kernel directly against this layer of the stacked HBM
        # pool (no gathered window copy); the intra-dispatch ring + the
        # current token form a small dense segment; the two merge by their
        # softmax stats. See ops/pallas/paged_attention.py.
        from production_stack_tpu.ops.pallas.paged_attention import (
            paged_flash_decode_stats,
            paged_flash_decode_stats_tp,
        )

        q2 = q.reshape(b, h, dh)
        if view.tp_mesh is not None:
            # TP>1: the pool is kv-head-sharded; run the kernel per-shard
            # via shard_map (exact — heads are independent) instead of
            # letting GSPMD all-gather the pool (advisor r3 high finding).
            out_p, m_p, l_p = paged_flash_decode_stats_tp(
                q2, view.pool_k, view.pool_v, view.block_tables,
                view.kv_lens, layer, view.tp_mesh,
                block_size=view.block_size, interpret=view.interpret,
                k_scale=view.k_scale, v_scale=view.v_scale,
            )
        else:
            out_p, m_p, l_p = paged_flash_decode_stats(
                q2, view.pool_k, view.pool_v, view.block_tables,
                view.kv_lens, layer,
                block_size=view.block_size, interpret=view.interpret,
                k_scale=view.k_scale, v_scale=view.v_scale,
                # The first slot of the pool the row's query still sees.
                kv_lo=None if span is None else positions[:, 0] - span + 1,
            )
        kc = k.transpose(2, 0, 1, 3)          # [Hkv, B, 1, Dh] current token
        vc = v.transpose(2, 0, 1, 3)
        self_bias = jnp.zeros((b, 1), jnp.float32)
        if view.ring_k is not None:
            keys = jnp.concatenate([view.ring_k, kc], axis=2)
            vals = jnp.concatenate([view.ring_v, vc], axis=2)
            seen = view.ring_pos < positions
            if span is not None:
                seen = seen & (positions - view.ring_pos < span)
            ring_bias = jnp.where(seen, 0.0, jnp.float32(_NEG_INF))  # [B, R]
            bias = jnp.concatenate([ring_bias, self_bias], axis=1)
        else:
            keys, vals, bias = kc, vc, self_bias
        out_d, m_d, l_d = dense_decode_stats(q2, keys, vals, bias)
        attn = merge_attention_segments(out_p, m_p, l_p, out_d, m_d, l_d)
        return attn.reshape(b, t, h, dh)
    return window_attention(
        q, k, v, positions, chunk_lens,
        view.win_k, view.win_v, view.win_len,
        view.ring_k, view.ring_v, view.ring_pos,
        chunk_bias=view.chunk_bias, span=span,
    )


# What a layer with no bound hands ``attend`` where another layer of the same
# scan has one: no position reaches it.
NO_SPAN = 1 << 30


def keys_in_span(start, length, span):
    """Keys that ``length`` tokens at positions ``start`` on see under
    ``span`` (themselves included), summed: the closed form the engine's
    counters use (exact host integers or arrays). The token at position p
    sees min(p + 1, span)."""
    start, length, span = (np.asarray(x, np.int64)
                           for x in (start, length, span))
    end = start + length
    # Positions below span - 1 see p + 1 keys, the others span.
    ramp_end = np.clip(span - 1, start, end)
    ramp = (ramp_end * (ramp_end + 1) - start * (start + 1)) // 2
    return ramp + (end - ramp_end) * span


def prefill_kernel_covers(
    t: int, num_heads: int, num_kv_heads: int, head_dim: int,
    value_dim: int, block_size: int, dtypes, *, latent: bool = False,
    scales: bool = False, kv_sharded: bool = False, ring: bool = False,
    chunk_bias: bool = False, packed: bool = False,
) -> bool:
    """THE predicate: whether a Pallas flash prefill kernel covers a chunk
    of ``t`` tokens over a view that holds the pool. Asked in two places
    that therefore agree: the runner, of every chunk length it can
    dispatch, before it builds the view (``prefill_reads_pool``: only then
    does a view hold the pool, and the window reserve, the scheduler's
    window budget and the windowed families go), and ``attend``, of the
    operands it is handed. Covered: ONE dtype (``dtypes``: the chunk's and
    the pools'), no int8 scales, no kv-head sharding, no ring, no
    ``chunk_bias``, and either K and V rows of one width in two pools at a
    head width, block size and chunk length the kernel tiles
    (``supports_pallas_prefill``), or ``latent`` rows: one pool of one row
    a token, ``head_dim`` its width, the values its first ``value_dim``
    lanes (``supports_latent_prefill``). ``packed``: the chunk is one row
    of several sequences' segments (``KVView.seg_lens``), which over K/V
    rows is a kernel of its own (``supports_packed_prefill``); over latent
    rows ONE body runs both forms since PR 56 (a rectangle is laid as a row
    whose segments begin at multiples of ``t``), so one predicate answers
    for both. Not covered by choice, though the decode kernels cover them: an int8 pool (its
    scales would ride as the decode kernel's do) and a kv-head-sharded
    pool; no benchmark cell runs either."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        supports_latent_prefill,
        supports_packed_prefill,
        supports_pallas_prefill,
    )

    kinds = {jnp.dtype(d) for d in dtypes}
    if scales or kv_sharded or ring or chunk_bias or len(kinds) != 1:
        return False
    itemsize = kinds.pop().itemsize
    if latent:
        return num_kv_heads == 1 and supports_latent_prefill(
            t, num_heads, head_dim, value_dim, itemsize, block_size)
    supports = supports_packed_prefill if packed else supports_pallas_prefill
    return value_dim == head_dim and supports(
        t, num_heads, num_kv_heads, head_dim, itemsize, block_size)


def _attend_chunk_over_pool(q, k, v, positions, chunk_lens, view, layer,
                            span=None):
    """A prefill chunk (T > 1) over a view that holds the POOL: each row's
    history is the pool's slots below ``view.kv_lens`` by its block table.
    ``span`` rides both executions as their last operand where there is one.

    One algorithm, two executions, chosen HERE by the platform the program
    is LOWERED for (``lax.platform_dependent``: the program's, not the
    process's default backend; the rule of ops/gated_delta.py:gdn_step_at).
    A program for a TPU holds the Pallas flash kernel
    (ops/pallas/paged_attention.py:paged_flash_prefill): history read in
    place, nothing gathered, no score tensor in HBM; so does any program
    whose view says ``interpret`` (the kernel's own tests on a CPU). A
    program for any other backend gathers this layer's pages of its rows
    and is ``window_attention``: the statement of the computation and the
    tests' oracle. There is no third execution: a pool view the kernel
    does not cover (``prefill_kernel_covers``) is the CALLER's fault and
    raises while the program is traced (at warm-up, on every backend),
    so no program for a TPU gathers a window a layer unseen; whoever
    builds views asks the same predicate first and hands ``attend`` a
    gathered window instead."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_prefill,
        paged_flash_prefill_packed,
    )

    bs = view.block_size
    b, t, h, dh = q.shape
    packed = view.seg_lens is not None
    if not prefill_kernel_covers(
            t, h, k.shape[2], dh, v.shape[-1], bs,
            (k.dtype, v.dtype, view.pool_k.dtype, view.pool_v.dtype),
            scales=view.k_scale is not None,
            kv_sharded=view.tp_mesh is not None,
            ring=view.ring_k is not None,
            chunk_bias=view.chunk_bias is not None,
            packed=packed) or (packed and b != 1):
        raise ValueError(
            f"attend: a chunk of {t} tokens ({h}/{k.shape[2]} heads x {dh}"
            f", {k.dtype} over a {view.pool_k.dtype} pool, block {bs}) "
            "over a pool view the prefill kernel does not cover "
            "(ops/attention.py:prefill_kernel_covers): gather a window")

    bound = () if span is None else (jnp.asarray(span, jnp.int32),)

    def spanned(of):
        # The layer's span, where it rides as the last operand.
        return of[0] if of else None

    def gathered(q, k, v, positions, chunk_lens, pool_k, pool_v, tables,
                 kv_lens, layer, *of):
        def one(x):
            return jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=True)

        win_k, win_v = gather_window(one(pool_k), one(pool_v), tables, bs,
                                     out_dtype=q.dtype)
        return window_attention(q, k, v, positions, chunk_lens, win_k[0],
                                win_v[0], kv_lens, span=spanned(of))

    def kernel(q, k, v, positions, chunk_lens, pool_k, pool_v, tables,
               kv_lens, layer, *of, interpret=False):
        return paged_flash_prefill(
            q, k, v, positions, chunk_lens, pool_k, pool_v, tables, kv_lens,
            layer, block_size=bs, interpret=interpret, span=spanned(of))

    if packed:
        # The two executions of a PACKED row: the kernel's packed form, and
        # the same oracle over the row taken apart, a row a segment.
        def unpacked(q, k, v, seg_lens, pool_k, pool_v, tables, kv_lens,
                     layer, *of):
            rows, put_back = unpack_segments(seg_lens, t)
            positions = kv_lens[:, None] + jnp.arange(t, dtype=jnp.int32)
            return put_back(gathered(
                q[0][rows], k[0][rows], v[0][rows], positions, seg_lens,
                pool_k, pool_v, tables, kv_lens, layer, *of))[None]

        def packed_kernel(q, k, v, seg_lens, pool_k, pool_v, tables,
                          kv_lens, layer, *of, interpret=False):
            return paged_flash_prefill_packed(
                q, k, v, seg_lens, pool_k, pool_v, tables, kv_lens, layer,
                block_size=bs, interpret=interpret, span=spanned(of))

        return _kernel_or_gathered(
            view, packed_kernel, unpacked, q, k, v, view.seg_lens,
            view.pool_k, view.pool_v, view.block_tables, view.kv_lens,
            jnp.asarray(layer, jnp.int32), *bound)
    return _kernel_or_gathered(
        view, kernel, gathered, q, k, v, positions, chunk_lens, view.pool_k,
        view.pool_v, view.block_tables, view.kv_lens,
        jnp.asarray(layer, jnp.int32), *bound)


def segment_of_token(seg_lens: jax.Array, t: int):
    """Of a packed row of ``t`` tokens (segments of ``seg_lens`` tokens end
    to end from token 0), for every token: (its segment, its index within
    the segment), [t] int32 each; a token past the last segment counts on
    from the last slot's start."""
    ends = jnp.cumsum(seg_lens)
    iota = jnp.arange(t, dtype=jnp.int32)
    seg = jnp.minimum(jnp.sum(iota[:, None] >= ends[None, :], axis=1),
                      seg_lens.shape[0] - 1)
    return seg, iota - (ends - seg_lens)[seg]


def unpack_segments(seg_lens: jax.Array, t: int):
    """A packed row of ``t`` tokens taken apart: ``rows`` [S, t] int32, the
    row's token behind token j of segment i (clipped past the segment's
    end: whatever lies there is masked by the segment's length), and
    ``put_back``, which lays [S, t, ...] values of the segments' tokens
    end to end again as [t, ...], zeros past the last."""
    ends = jnp.cumsum(seg_lens)
    iota = jnp.arange(t, dtype=jnp.int32)
    rows = jnp.minimum((ends - seg_lens)[:, None] + iota[None, :], t - 1)
    seg, within = segment_of_token(seg_lens, t)

    def put_back(x):
        out = x[seg, within]
        live = (iota < ends[-1]).reshape((t,) + (1,) * (out.ndim - 1))
        return jnp.where(live, out, 0)

    return rows, put_back


def _kernel_or_gathered(view, kernel, gathered, *args):
    """One algorithm, two executions (``_attend_chunk_over_pool``): the
    kernel where the view says ``interpret`` or the program is lowered for
    a TPU, the gathered oracle on any other backend."""
    if view.interpret:
        return kernel(*args, interpret=True)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=gathered)


def prefill_attn_path(hlo_text: str):
    """Which execution of a prefill chunk's attention a compiled program
    (``as_text()``) holds: ``"pallas"`` (the flash kernel over the pool) or
    ``"xla"`` (``window_attention`` over gathered keys)."""
    return "pallas" if "paged_flash_prefill" in hlo_text else "xla"


def _latent_window_attention(q, rows, positions, chunk_lens, win, win_len,
                             scale, value_dim, ring=None, ring_pos=None,
                             chunk_bias=None):
    """``window_attention`` with latent rows as keys AND values: the whole
    row is contracted for the values too and the first ``value_dim`` lanes
    of the result kept (a slice of the OUTPUT: slicing the window would
    copy it, every layer)."""
    return window_attention(
        q, rows, rows, positions, chunk_lens, win, win, win_len,
        ring, ring, ring_pos, scale=scale, chunk_bias=chunk_bias,
        # Every head shares the one row, so a query block is H times its
        # tokens tall: as many score rows a block as 8 query heads a KV
        # head give at QBLOCK (the score tensor is the program's largest
        # temporary: 4 GB at 8 rows x 3072 keys otherwise).
        qblock=max(16, QBLOCK * 8 // q.shape[2]),
    )[..., :value_dim]


def _attend_latent(q, rows, positions, chunk_lens, view, layer, scale,
                   value_dim):
    """``attend`` over latent rows: the same paths with the rows as keys
    AND values. A view that holds the POOL goes to a kernel that reads it
    in place and slices the values out of its VMEM buffer, which is free:
    the latent prefill kernel for a chunk
    (``_attend_latent_chunk_over_pool``), the decode kernel at T == 1. A
    gathered WINDOW (what a view the prefill kernel does not cover is
    handed: an int8 or sharded pool, a ring, ``chunk_bias``; and every
    window-path decode) never reaches a kernel."""
    b, t, h, w = q.shape
    if view.pool_k is None:
        return _latent_window_attention(
            q, rows, positions, chunk_lens, view.win_k, view.win_len, scale,
            value_dim, view.ring_k, view.ring_pos, view.chunk_bias)
    if t > 1:
        return _attend_latent_chunk_over_pool(
            q, rows, positions, chunk_lens, view, layer, scale, value_dim)
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_latent_stats,
    )

    q2 = q.reshape(b, h, w)
    out_p, m_p, l_p = paged_flash_decode_latent_stats(
        q2, view.pool_k, view.block_tables, view.kv_lens, layer,
        block_size=view.block_size, value_dim=value_dim, scale=scale,
        interpret=view.interpret,
    )
    keys = rows.transpose(2, 0, 1, 3)                  # [1, B, 1, W]
    bias = jnp.zeros((b, 1), jnp.float32)
    if view.ring_k is not None:
        keys = jnp.concatenate([view.ring_k, keys], axis=2)
        bias = jnp.concatenate([
            jnp.where(view.ring_pos < positions, 0.0, jnp.float32(_NEG_INF)),
            bias], axis=1)
    out_d, m_d, l_d = dense_decode_stats(q2, keys, keys, bias, scale=scale)
    attn = merge_attention_segments(
        out_p, m_p, l_p, out_d[..., :value_dim], m_d, l_d)
    return attn.reshape(b, t, h, value_dim)


def _attend_latent_chunk_over_pool(q, rows, positions, chunk_lens, view,
                                   layer, scale, value_dim):
    """``_attend_chunk_over_pool`` over ONE pool of latent rows: the same
    algorithm and two executions (ops/pallas/paged_attention.py:
    paged_flash_prefill_latent, the rectangle laid as a row of
    ``paged_flash_prefill_packed_latent``; or this layer's rows gathered
    and ``window_attention``), the same pair again for a PACKED row (the
    kernel as it is, or the row taken apart), the same raise at trace time
    on a pool view the kernel does not cover."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_prefill_latent,
        paged_flash_prefill_packed_latent,
    )

    bs = view.block_size
    b, t, h, w = q.shape
    packed = view.seg_lens is not None
    if not prefill_kernel_covers(
            t, h, rows.shape[2], w, value_dim, bs,
            (rows.dtype, view.pool_k.dtype), latent=True,
            scales=view.k_scale is not None,
            kv_sharded=view.tp_mesh is not None,
            ring=view.ring_k is not None,
            chunk_bias=view.chunk_bias is not None,
            packed=packed) or (packed and b != 1):
        raise ValueError(
            f"attend: a chunk of {t} tokens ({h} heads over latent rows of "
            f"{w} lanes, values {value_dim}, {rows.dtype} over a "
            f"{view.pool_k.dtype} pool, block {bs}) over a pool view the "
            "prefill kernel does not cover "
            "(ops/attention.py:prefill_kernel_covers): gather a window")

    def gathered(q, rows, positions, chunk_lens, pool, tables, kv_lens,
                 layer):
        page = jax.lax.dynamic_index_in_dim(pool, layer, 0, False)
        win = gather_kv_pages(page, tables, bs).astype(q.dtype)
        return _latent_window_attention(
            q, rows, positions, chunk_lens, win, kv_lens, scale, value_dim)

    def kernel(q, rows, positions, *args, interpret=False):
        # Token i of a row sits at position kv_lens[row] + i: the kernel
        # takes no positions.
        return paged_flash_prefill_latent(
            q, rows, *args, block_size=bs, value_dim=value_dim, scale=scale,
            interpret=interpret)

    if packed:
        # As for K/V rows: the kernel's packed form, and the oracle over
        # the row taken apart, a row a segment.
        def unpacked(q, rows, seg_lens, pool, tables, kv_lens, layer):
            take, put_back = unpack_segments(seg_lens, t)
            positions = kv_lens[:, None] + jnp.arange(t, dtype=jnp.int32)
            return put_back(gathered(
                q[0][take], rows[0][take], positions, seg_lens, pool,
                tables, kv_lens, layer))[None]

        def packed_kernel(*args, interpret=False):
            return paged_flash_prefill_packed_latent(
                *args, block_size=bs, value_dim=value_dim, scale=scale,
                interpret=interpret)

        return _kernel_or_gathered(
            view, packed_kernel, unpacked, q, rows, view.seg_lens,
            view.pool_k, view.block_tables, view.kv_lens,
            jnp.asarray(layer, jnp.int32))
    return _kernel_or_gathered(
        view, kernel, gathered, q, rows, positions, chunk_lens, view.pool_k,
        view.block_tables, view.kv_lens, jnp.asarray(layer, jnp.int32))


def scan_layers(
    layer_fn: Callable,   # (hidden, lp, view_l, layer, lora_l) -> (hidden, k, v)
    hidden: jax.Array,    # [B, T, D]
    layers,               # params["layers"]: every leaf stacked on a leading L
    view: KVView,
    lora=None,            # (adapter_idx [B], {target: (A [L,...], B [L,...])})
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``lax.scan`` of one layer function over the stacked layers: each step
    gets its layer's params, the view with that layer's window and ring, the
    layer index (only when the view holds a pool: the kernel needs it, and
    no other program carries the operand) and its slice of the LoRA stacks.
    Returns (hidden, k_new [L, Hkv, B, T, Dh], v_new): each layer's new KV
    in pool layout, for the runner to write."""
    num_layers = jax.tree.leaves(layers)[0].shape[0]
    layer_ids = jnp.arange(num_layers, dtype=jnp.int32) \
        if view.pool_k is not None else None
    adapter_idx, stacks = lora if lora is not None else (None, None)

    def step(h, xs):
        lp, win_k, win_v, ring_k, ring_v, layer, lora_l = xs
        view_l = view._replace(
            win_k=win_k, win_v=win_v, ring_k=ring_k, ring_v=ring_v
        )
        h, k_l, v_l = layer_fn(
            h, lp, view_l, layer,
            None if lora is None else (adapter_idx, lora_l),
        )
        return h, (k_l, v_l)

    # None is an empty pytree: an absent part adds no operand to the scan.
    hidden, (k_new, v_new) = jax.lax.scan(step, hidden, (
        layers, view.win_k, view.win_v, view.ring_k, view.ring_v,
        layer_ids, stacks,
    ))
    return hidden, k_new, v_new


def gather_window(
    kv_k: jax.Array,          # [L, Hkv, num_slots, Dh]
    kv_v: jax.Array,
    block_tables: jax.Array,  # [B, Mb] int32
    block_size: int,
    k_scale: Optional[jax.Array] = None,  # [L, Hkv, num_slots] (int8 pools)
    v_scale: Optional[jax.Array] = None,
    out_dtype=None,
) -> Tuple[jax.Array, jax.Array]:
    """One gather per dispatch: paged pool -> contiguous per-sequence windows
    [L, Hkv, B, Mb*bs, Dh]. Amortized over every layer and every fused decode
    step of the dispatch (a per-layer gather is ~5 ms/step on a v5e at
    B=16/S=1024 — the profiled round-1 bottleneck).

    Indexes BLOCKS of a [.., num_blocks, bs, Dh] view rather than slots of
    the flat pool: each gathered element is then a contiguous bs*Dh run
    (16x fewer indices, 16x longer runs), which XLA lowers to block-sized
    copies instead of row-sized ones — the slot-indexed form measured only
    ~2 GB/s on a v5e (r3 profiling), making the gather the prefill
    bottleneck.

    Quantized pools (``k_scale``/``v_scale`` set): the gather reads int8
    payload + per-slot scales (half the pool-side traffic of bf16) and the
    window is dequantized to ``out_dtype`` on the way out, so attention math
    downstream is unchanged and every read path reconstructs the same
    values (ops/quantization.py:dequantize_kv)."""
    b, mb = block_tables.shape
    l, hkv, num_slots, dh = kv_k.shape
    dv = kv_v.shape[-1]     # the keys' width, or 0: latent rows (KVView)
    nb = num_slots // block_size
    kr = kv_k.reshape(l, hkv, nb, block_size, dh)
    vr = kv_v.reshape(l, hkv, nb, block_size, dv)
    win_k = kr[:, :, block_tables]  # [L, Hkv, B, Mb, bs, Dh]
    win_v = vr[:, :, block_tables]
    win_k = win_k.reshape(l, hkv, b, mb * block_size, dh)
    win_v = win_v.reshape(l, hkv, b, mb * block_size, dv)
    if k_scale is not None:
        from production_stack_tpu.ops.quantization import dequantize_kv

        out_dtype = out_dtype or jnp.bfloat16
        ks = k_scale.reshape(l, hkv, nb, block_size)[:, :, block_tables]
        vs = v_scale.reshape(l, hkv, nb, block_size)[:, :, block_tables]
        win_k = dequantize_kv(
            win_k, ks.reshape(l, hkv, b, mb * block_size), out_dtype
        )
        win_v = dequantize_kv(
            win_v, vs.reshape(l, hkv, b, mb * block_size), out_dtype
        )
    return win_k, win_v


def gather_kv_pages(pool: jax.Array, block_tables: jax.Array, block_size: int) -> jax.Array:
    """Gather per-sequence KV from the slot pool.

    pool: [Hkv, num_slots, Dh] (head-major so the Pallas kernel DMAs pages
    with no relayout); block_tables: [B, Mb] -> [Hkv, B, Mb*bs, Dh].
    Block-indexed for contiguous bs*Dh copy runs (see gather_window).
    """
    b, mb = block_tables.shape
    hkv, num_slots, dh = pool.shape
    nb = num_slots // block_size
    pr = pool.reshape(hkv, nb, block_size, dh)
    return pr[:, block_tables].reshape(hkv, b, mb * block_size, dh)


@functools.partial(jax.jit, static_argnames=("block_size",))
def paged_attention_xla(
    q: jax.Array,             # [B, T, H, Dh]
    k_pool: jax.Array,        # [Hkv, num_slots, Dh]
    v_pool: jax.Array,        # [Hkv, num_slots, Dh]
    block_tables: jax.Array,  # [B, Mb] int32
    kv_lens: jax.Array,       # [B] int32 — total KV length incl. current chunk
    q_positions: jax.Array,   # [B, T] int32 — absolute positions of queries
    *,
    block_size: int,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # [Hkv, num_slots] (int8 pools)
    v_scale: Optional[jax.Array] = None,
    span: Optional[jax.Array] = None,     # [] int32: the layer's span
) -> jax.Array:
    """Reference paged attention: gather pages, masked softmax attention.

    Causal semantics: query at position p attends to KV slots [0, p] of its own
    sequence; slots beyond kv_len are masked (they may alias the null block).
    Int8 pools pass per-slot scales (``k_scale``/``v_scale``); the gathered
    pages dequantize inline before the score/PV contractions — the quantized
    pool never materializes as a bf16 copy of itself.
    """
    b, t, h, dh = q.shape
    hkv = k_pool.shape[0]
    g = h // hkv
    if scale is None:
        scale = dh ** -0.5

    k = gather_kv_pages(k_pool, block_tables, block_size)  # [Hkv, B, S, Dh]
    v = gather_kv_pages(v_pool, block_tables, block_size)
    if k_scale is not None:
        from production_stack_tpu.ops.quantization import dequantize_kv

        ks = gather_kv_pages(
            k_scale[..., None], block_tables, block_size
        )[..., 0]                                           # [Hkv, B, S]
        vs = gather_kv_pages(
            v_scale[..., None], block_tables, block_size
        )[..., 0]
        k = dequantize_kv(k, ks, jnp.float32)
        v = dequantize_kv(v, vs, jnp.float32)
    s = k.shape[2]

    qg = q.reshape(b, t, hkv, g, dh).astype(jnp.float32) * scale
    # scores: [B, Hkv, G, T, S]
    scores = jnp.einsum("btkgd,kbsd->bkgts", qg, k.astype(jnp.float32))

    key_pos = jnp.arange(s, dtype=jnp.int32)[None, :]               # [1, S]
    valid = key_pos < kv_lens[:, None]                               # [B, S]
    causal = key_pos[:, None, :] <= q_positions[:, :, None]          # [B, T, S]
    if span is not None:
        causal &= q_positions[:, :, None] - key_pos[:, None, :] < span
    mask = (valid[:, None, :] & causal)[:, None, None, :, :]         # [B,1,1,T,S]
    scores = jnp.where(mask, scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,kbsd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, t, h, dh).astype(q.dtype)


def write_kv_to_pool(
    k_pool: jax.Array,      # [Hkv, num_slots, Dh]
    v_pool: jax.Array,
    k_new: jax.Array,       # [B, T, Hkv, Dh]
    v_new: jax.Array,
    slot_mapping: jax.Array,  # [B, T] int32 — flat slot per token; 0 = discard
) -> tuple:
    """Scatter freshly-computed KV for the current tokens into the pools.

    Padding tokens carry slot 0 (the reserved null block), so their writes land
    harmlessly in slots that are never unmasked by attention.
    """
    flat = slot_mapping.reshape(-1)
    # [B, T, Hkv, Dh] -> [Hkv, B*T, Dh] to match the head-major pool.
    kf = k_new.reshape(-1, *k_new.shape[2:]).transpose(1, 0, 2).astype(k_pool.dtype)
    vf = v_new.reshape(-1, *v_new.shape[2:]).transpose(1, 0, 2).astype(v_pool.dtype)
    return k_pool.at[:, flat].set(kf), v_pool.at[:, flat].set(vf)


# ---------------------------------------------------------------------------
# Learned sparse selection over latent rows (models/dots3_note.py; DeepSeek-
# V3.2's indexer). Beside its latent row a full layer's token caches the
# INDEX KEY of its indexer, in the SECOND pool (models/config.py:
# LatentKVSpec.index_dim): the same block table, the same write, and every
# part of a ``KVView`` that holds values elsewhere (``pool_v``, ``win_v``,
# ``ring_v``) holds index keys here. A query scores every visible key by
#
#     I[t, s] = sum_h w[t, h] * relu(q_idx[t, h] . k_idx[s])      (float32)
#
# and attends the ``topk`` keys of largest I (all of them while no more are
# visible; ties to the lower position, ``lax.top_k``'s rule). Selection is
# discontinuous like routing: scores and the choice are float32 whatever the
# activations. Two executions of the one statement:
#
#   * a CHUNK (a prefill chunk, a whole sequence, a window-path decode step)
#     scores its queries against history, ring and itself DENSELY under the
#     mask ``s in S_t`` (``_selected_chunk``): the same mathematics at more
#     work than O(T k); the k-th score is found by a radix select over the
#     float32 bit patterns (``topk_mask``: 32 counting passes, no sort);
#   * a DECODE STEP over a view that holds the pools reads what it selected
#     (``_selected_decode``): the rows' index keys (a block of the index
#     pool is a whole tile: 256 B a key, nothing of the latent rows),
#     ``lax.top_k``, then a gather of the min(L, topk) selected latent rows,
#     attended by ``dense_decode_stats``.
#
# Both count what they did (int32: keys visible, keys selected, over the live
# queries), which the model returns among its ``FORWARD_STATS``.

_HI = jax.lax.Precision.HIGHEST
# Bytes a block of float32 scores may take where a loop bounds them.
SCORE_BLOCK_BYTES = 128 << 20
# History lengths a chunk's (or a step's index scan's) work is cut to: the
# smallest of ``S >> 3, S >> 2, S >> 1, S`` that holds every row's history,
# chosen at run time (``_by_history``); below this many slots there is one.
HISTORY_CUT_FLOOR = 2048


def _query_block(t: int, bytes_per_query: int) -> int:
    """The largest divisor of ``t`` whose block of queries stays inside
    ``SCORE_BLOCK_BYTES`` (at least 1)."""
    cap = max(1, SCORE_BLOCK_BYTES // max(1, bytes_per_query))
    return max(d for d in range(1, min(t, cap) + 1) if t % d == 0)


def index_scores(q_idx: jax.Array, w_idx: jax.Array,
                 k_idx: jax.Array) -> jax.Array:
    """q_idx [B, T, Hi, Di], w_idx [B, T, Hi] float32, k_idx [B, K, Di] ->
    I [B, T, K] float32. The per-head scores [.., Hi, K] exist a block of
    queries at a time (64 heads x 2048 queries x 17 k keys would be 9 GB)."""
    b, t, hi, _ = q_idx.shape
    k = k_idx.shape[1]

    def block(qb, wb):
        s = jnp.einsum("bthd,bkd->bthk", qb, k_idx, precision=_HI,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * wb[..., None], axis=2)

    tq = _query_block(t, b * hi * k * 4)
    if tq == t:
        return block(q_idx, w_idx)
    nb = t // tq
    _, out = jax.lax.scan(
        lambda _, xs: ((), block(*xs)), (),
        (q_idx.reshape(b, nb, tq, *q_idx.shape[2:]).swapaxes(0, 1),
         w_idx.reshape(b, nb, tq, hi).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, t, k)


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores: jax.Array, pos_k: jax.Array, visible: jax.Array,
              k: int) -> jax.Array:
    """The ``k`` visible keys of largest score a query, as a mask: scores
    [B, T, K] float32, pos_k [B, K] int32 (the key's position: ties go to
    the lower), visible [B, T, K] bool -> [B, T, K] bool; every visible key
    where no more than ``k`` are. No sort: the k-th largest score is built
    bit by bit from counts (32 passes over the scores), and only where
    scores tie at it does a second search find the position that cuts the
    tie."""
    keys = jnp.where(visible, _sortable(scores), jnp.uint32(0))

    def count(mask):
        return jnp.sum(mask, axis=-1, dtype=jnp.int32)

    def bit(i, thr):
        cand = thr | jax.lax.shift_left(jnp.uint32(1),
                                        (31 - i).astype(jnp.uint32))
        return jnp.where(count(keys >= cand[..., None]) >= k, cand, thr)

    # The largest value that k keys reach (0: fewer than k are visible).
    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:2], jnp.uint32))
    above = keys > thr[..., None]
    tied = keys == thr[..., None]
    need = k - count(above)                                     # [B, T]

    def cut_by_position(_):
        # The largest x with count(tied & pos < x) <= need: positions of
        # visible keys differ, so exactly ``need`` lie below it.
        def pbit(i, x):
            cand = x | jax.lax.shift_left(jnp.int32(1), 30 - i)
            under = count(tied & (pos_k[:, None, :] < cand[..., None]))
            return jnp.where(under <= need, cand, x)

        x = jax.lax.fori_loop(0, 31, pbit, jnp.zeros(need.shape, jnp.int32))
        return tied & (pos_k[:, None, :] < x[..., None])

    tied = jax.lax.cond(jnp.any(count(tied & visible) > need),
                        cut_by_position, lambda _: tied, None)
    return visible & (above | tied)


def _by_history(longest: jax.Array, slots: int, unit: int, fn):
    """``fn(slots_read)`` with the smallest of the history cuts that holds
    ``longest`` slots (whole ``unit``s; every branch returns the same
    shapes): the work follows the history the rows have, not the table's
    width."""
    cuts = sorted({-(-(slots >> i) // unit) * unit for i in (3, 2, 1, 0)})
    if slots < HISTORY_CUT_FLOOR or len(cuts) == 1:
        return fn(slots)
    which = sum((longest > c).astype(jnp.int32) for c in cuts[:-1])
    return jax.lax.switch(which, [lambda c=c: fn(c) for c in cuts])


def _gather_blocks(pool, layer, tables, block_size):
    """The pages ``tables`` [B, n] of ONE layer of a pool [L, 1, slots, D],
    a block a slice (whole tiles), the layer an index of the same gather:
    [B, n * block_size, D]; no layer of the pool is sliced out."""
    l, _, slots, d = pool.shape
    b, n = tables.shape
    where = jnp.stack(
        [jnp.broadcast_to(jnp.asarray(layer, jnp.int32), tables.shape),
         tables], axis=-1)
    out = jax.lax.gather(
        pool.reshape(l, slots // block_size, block_size, d), where,
        jax.lax.GatherDimensionNumbers(
            offset_dims=(2, 3), collapsed_slice_dims=(0, 1),
            start_index_map=(0, 1)),
        slice_sizes=(1, 1, block_size, d),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return out.reshape(b, n * block_size, d)


def _selected_chunk(q, rows, k_idx, q_idx, w_idx, positions, chunk_lens,
                    hist, hist_idx, hist_pos, *, scale, value_dim, topk,
                    with_mask=False):
    """Chunk queries q [B, T, H, W] (absorbed) over ``hist`` [B, S, W] with
    its index keys ``hist_idx`` [B, S, Di] (``hist_pos`` [B, S]: a slot's
    position, or one no query reaches) and the chunk's own ``rows``
    [B, T, W] / ``k_idx`` [B, T, Di], densely under the selection's mask:
    ([B, T, H, value_dim], int32[2]) and, ``with_mask``, the mask
    [B, T, S + T]."""
    b, t, h, w = q.shape
    t_idx = jnp.arange(t, dtype=jnp.int32)
    live = t_idx[None, :] < chunk_lens[:, None]                      # [B, T]
    pos_c = jnp.where(live, positions, NO_SPAN)
    keys = jnp.concatenate([hist, rows], axis=1)                  # [B, K, W]
    pos_k = jnp.concatenate([hist_pos, pos_c], axis=1)               # [B, K]
    kk = keys.shape[1]
    visible = pos_k[:, None, :] <= positions[:, :, None]          # [B, T, K]
    with jax.named_scope("attn_index"):
        scores = index_scores(
            q_idx, w_idx, jnp.concatenate([hist_idx, k_idx], axis=1))
        chosen = topk_mask(scores, pos_k, visible, topk)
    with jax.named_scope("attn_select"):
        vals = keys[..., :value_dim]
        qf = (q.astype(jnp.float32) * scale).astype(q.dtype)

        def block(qb, mb):
            # qb [B, tq, H, W], mb [B, tq, K]
            tq = qb.shape[1]
            s = jax.lax.dot_general(
                qb.reshape(b, tq * h, w), keys,
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)          # [B, tq*H, K]
            s = jnp.where(mb[:, :, None, :], s.reshape(b, tq, h, kk),
                          jnp.float32(_NEG_INF))
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jax.lax.dot_general(
                p.astype(vals.dtype).reshape(b, tq * h, kk), vals,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return (o.reshape(b, tq, h, value_dim) / l).astype(q.dtype)

        tq = _query_block(t, b * h * kk * 4)
        if tq == t:
            out = block(qf, chosen)
        else:
            nb = t // tq
            _, out = jax.lax.scan(
                lambda _, xs: ((), block(*xs)), (),
                (qf.reshape(b, nb, tq, h, w).swapaxes(0, 1),
                 chosen.reshape(b, nb, tq, kk).swapaxes(0, 1)))
            out = out.swapaxes(0, 1).reshape(b, t, h, value_dim)
    stats = jnp.stack([jnp.sum(visible & live[:, :, None]),
                       jnp.sum(chosen & live[:, :, None])]).astype(jnp.int32)
    return (out, stats, chosen) if with_mask else (out, stats)


def _selected_decode(q, row, k_idx, q_idx, w_idx, positions, live, view,
                     layer, *, scale, value_dim, topk):
    """One decode step over a view that holds the POOLS: q [B, H, W], the
    step's row [B, W] and index key [B, Di], q_idx [B, Hi, Di], w_idx
    [B, Hi] float32, positions and live [B]. Reads the rows' index keys,
    ``lax.top_k``s, gathers the selected latent rows and attends them with
    the train's ring and the token itself: ([B, H, value_dim],
    int32[2])."""
    b, _, w = q.shape
    bs = view.block_size
    slots = view.block_tables.shape[1] * bs
    at = jnp.asarray(layer, jnp.int32).reshape(())
    s_idx = jnp.arange(slots, dtype=jnp.int32)
    with jax.named_scope("attn_index"):
        def pool_scores(read):
            # The index keys of the rows' first ``read`` slots.
            s = index_scores(
                q_idx[:, None], w_idx[:, None], _gather_blocks(
                    view.pool_v, at, view.block_tables[:, :read // bs],
                    bs))[:, 0]
            return jnp.pad(s, ((0, 0), (0, slots - read)))

        in_pool = s_idx[None, :] < view.kv_lens[:, None]          # [B, S]
        s_pool = jnp.where(
            in_pool, _by_history(jnp.max(view.kv_lens), slots, bs,
                                 pool_scores), -jnp.inf)
        # The train's earlier steps and the token itself, in position order
        # behind the pool's.
        late, late_idx = row[:, None], k_idx[:, None]
        seen = jnp.ones((b, 1), bool)
        if view.ring_k is not None:
            late = jnp.concatenate([view.ring_k[0], late], axis=1)
            late_idx = jnp.concatenate([view.ring_v[0], late_idx], axis=1)
            seen = jnp.concatenate(
                [view.ring_pos < positions[:, None], seen], axis=1)
        s_late = jnp.where(seen, index_scores(
            q_idx[:, None], w_idx[:, None], late_idx)[:, 0], -jnp.inf)
        scores = jnp.concatenate([s_pool, s_late], axis=1)
        k = min(topk, scores.shape[1])
        top, which = jax.lax.top_k(scores, k)                     # [B, k]
        picked = top > -jnp.inf
    with jax.named_scope("attn_select"):
        from_pool = picked & (which < slots)
        slot = jnp.where(from_pool, which, 0)
        block = jnp.take_along_axis(view.block_tables, slot // bs, axis=1)
        where = jnp.stack([jnp.broadcast_to(at, slot.shape),
                           block * bs + slot % bs], axis=-1)
        chosen = jax.lax.gather(
            view.pool_k[:, 0], where, jax.lax.GatherDimensionNumbers(
                offset_dims=(2,), collapsed_slice_dims=(0, 1),
                start_index_map=(0, 1)),
            slice_sizes=(1, 1, w),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)  # [B, k, W]
        # A late entry is attended where the top-k named it.
        late_at = slots + jnp.arange(late.shape[1], dtype=jnp.int32)
        late_in = jnp.any(picked[:, :, None]
                          & (which[:, :, None] == late_at), axis=1)
        keys = jnp.concatenate([chosen, late.astype(chosen.dtype)],
                               axis=1)[None]
        bias = jnp.where(jnp.concatenate([from_pool, late_in], axis=1),
                         0.0, jnp.float32(_NEG_INF))
        out, _, _ = dense_decode_stats(q, keys, keys, bias, scale=scale)
    live = live > 0
    stats = jnp.stack([
        jnp.sum(jnp.where(live, view.kv_lens + jnp.sum(seen, axis=1), 0)),
        jnp.sum(picked & live[:, None])]).astype(jnp.int32)
    return out[..., :value_dim], stats


def attend_selected_latent(
    q: jax.Array,            # [B, T, H, W] absorbed queries, zeros past the key
    rows: jax.Array,         # [B, T, 1, W] the chunk's latent rows
    k_idx: jax.Array,        # [B, T, 1, Di] the chunk's index keys
    q_idx: jax.Array,        # [B, T, Hi, Di] the indexer's queries
    w_idx: jax.Array,        # [B, T, Hi] float32: its heads' weights
    positions: jax.Array,    # [B, T]
    chunk_lens: jax.Array,   # [B] valid tokens per row
    view: KVView,            # ONE layer's view: index keys where values are
    layer: Optional[jax.Array] = None,   # pool views only
    *,
    scale: float,
    value_dim: int,
    topk: int,
    with_mask: bool = False,
):
    """``attend`` over latent rows of which every query reads the ``topk``
    its indexer picks (see above): ([B, T, H, value_dim] in q.dtype, int32
    [keys visible, keys selected] over the valid queries). A view that
    holds the pools is read in place at T == 1 (index keys, then the
    selected rows) and gathered a layer at T > 1; a gathered window is used
    as it is; sharded and int8 pools, the sequence-parallel ring and a
    speculative tree have no execution and raise. ``with_mask`` (a view
    that holds nothing: a whole sequence): also the selection [B, T, T],
    for the tests and the on-chip comparison."""
    if view.tp_mesh is not None or view.sp_mesh is not None \
            or view.k_scale is not None or view.chunk_bias is not None:
        raise ValueError(
            "attend_selected_latent: a sharded or int8 pool, the sequence-"
            "parallel ring or a speculative tree has no execution")
    b, t = positions.shape
    kw = dict(scale=scale, value_dim=value_dim, topk=topk)
    rows, k_idx = rows[:, :, 0], k_idx[:, :, 0]
    if view.pool_k is not None and t == 1:
        out, stats = _selected_decode(
            q[:, 0], rows[:, 0], k_idx[:, 0], q_idx[:, 0], w_idx[:, 0],
            positions[:, 0], chunk_lens, view, layer, **kw)
        return out[:, None], stats
    bs = view.block_size

    def over(read):
        # History of ``read`` slots: the layer's pages gathered, or the
        # window's head; slot s holds position s.
        if view.pool_k is not None:
            tables = view.block_tables[:, :read // bs]
            hist = _gather_blocks(view.pool_k, layer, tables, bs)
            hist_idx = _gather_blocks(view.pool_v, layer, tables, bs)
            held = view.kv_lens
        else:
            hist, hist_idx = view.win_k[0, :, :read], view.win_v[0, :, :read]
            held = view.win_len
        s_idx = jnp.arange(read, dtype=jnp.int32)[None, :]
        hist_pos = jnp.where(s_idx < held[:, None], s_idx, NO_SPAN)
        if view.ring_k is not None:
            hist = jnp.concatenate([hist, view.ring_k[0]], axis=1)
            hist_idx = jnp.concatenate([hist_idx, view.ring_v[0]], axis=1)
            hist_pos = jnp.concatenate([hist_pos, view.ring_pos], axis=1)
        return _selected_chunk(
            q, rows, k_idx, q_idx, w_idx, positions, chunk_lens,
            hist.astype(q.dtype), hist_idx.astype(q.dtype), hist_pos, **kw)

    if view.pool_k is not None:
        return _by_history(jnp.max(view.kv_lens),
                           view.block_tables.shape[1] * bs, bs, over)
    if view.win_k is not None:
        return _by_history(jnp.max(view.win_len), view.win_k.shape[2], 1,
                           over)
    hist = jnp.zeros((b, 0, q.shape[-1]), q.dtype)
    hist_idx = jnp.zeros((b, 0, k_idx.shape[-1]), q.dtype)
    hist_pos = jnp.zeros((b, 0), jnp.int32)
    if view.ring_k is not None:
        hist, hist_idx = view.ring_k[0].astype(q.dtype), \
            view.ring_v[0].astype(q.dtype)
        hist_pos = view.ring_pos
    return _selected_chunk(q, rows, k_idx, q_idx, w_idx, positions,
                           chunk_lens, hist, hist_idx, hist_pos,
                           with_mask=with_mask, **kw)
