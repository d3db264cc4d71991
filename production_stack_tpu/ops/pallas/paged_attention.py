"""Pallas TPU flash kernels over the paged KV pool: decode (below) and
prefill (the sections behind it: a rectangle of K/V rows, and ONE row of
several sequences' segments over K/V rows and over latent rows).

The TPU-native replacement for the paged-attention CUDA kernels inside the
reference's external vLLM images (SURVEY.md §2.2 "vLLM engine"). Design:

  * Grid over sequences. Each program computes the [H, Dh] attention output
    for one decode query against that sequence's KV pages of ONE layer.
  * The LAYER-STACKED pools ``[L, Hkv, num_slots, Dh]`` stay in HBM
    (`pltpu.HBM`); the kernel DMAs pages of the prefetched layer index into
    VMEM itself, so the serving path attends directly against the pool with
    NO gathered per-dispatch window copy (the round-2 window design
    materialized the batch's whole live KV per dispatch — ~64 GiB at the
    reference flagship config, VERDICT r2 weak #2 — and its XLA gather runs
    at ~2-3 GB/s on a v5e, a ~100 ms fixed tax per dispatch).
  * Pages are grouped into SUPERPAGES of 512 tokens: one compute iteration
    covers 512 keys (an MXU-friendly tile), while the underlying DMAs stay
    page-granular (pages are scattered in the pool). Two superpage buffers
    double-buffer fetch against compute ACROSS the whole call: its
    superpages form one sequence, row after row, and while superpage n is
    computed, superpage n + 1 is in flight into the other buffer, be it the
    same row's next one or the NEXT ROW's first. So a short row (one
    superpage: most chat contexts) does not start by waiting for a fetch it
    has just issued; only the call's first live row, and a row behind an
    empty one, do. Buffers, semaphores and the count of superpages fetched
    so far are scratch, which outlives a program; the grid axis is
    sequential ("arbitrary"), since the hand-over depends on program order.
  * What a short row costs is scalar work, not bytes (a DMA start is ~23 ns
    on a v5e, a branch about as much; PERF.md §6, PR 30): the fetch loops run
    over the row's own pages, a buffer's page copies share one byte-counting
    semaphore that is waited on in a few power-of-two runs, q and the
    outputs are resident blocks (one copy a call, none a program), and a
    row with ``kv_len == 0`` (bucket padding) issues, waits for and computes
    nothing.
  * Never-fetched tails are NOT zero-filled per row. What must hold is that
    a masked key's softmax weight (0) never meets a non-finite VALUE
    (0 * NaN = NaN in the PV contraction): V's buffers are cleared once a
    call, and later tails hold an earlier superpage's KV, finite like the
    pool (tests/test_pallas_kernel.py poisons the pool and the scratch).
  * Small head dims pack PACK = 128 // Dh consecutive tokens into one
    128-lane row (the pool is viewed as [L, Hkv, num_slots/PACK, 128], which
    keeps every DMA slice 128-lane aligned), and the compute splits each row
    back into PACK lane-halves — so Llama-1B-class models (Dh = 64) get the
    same windowless decode as Dh = 128 models.
  * Block tables + kv lengths + layer index ride scalar prefetch (SMEM) so
    DMA source addresses are computable before the body runs.
  * Online softmax (flash) accumulation in fp32 across superpages. The
    kernel RETURNS its softmax stats (running max ``m`` and sum ``l``) so
    the caller can flash-merge the pool segment with the intra-dispatch
    ring/self segment computed densely in XLA (ops/attention.py:
    merge_attention_segments).

The decode kernels take T == 1: queries sit at position >= kv_len, so
causality over the pool is exactly "attend to slots < kv_len" and no
per-token causal mask is needed. A prefill chunk (T > 1) has kernels of its
own on the same machinery: the history from the pool up to a sequence's
length, then its chunk causally. ``paged_flash_prefill`` runs a RECTANGLE of
K/V rows; ``paged_flash_prefill_packed`` and
``paged_flash_prefill_packed_latent`` (one kernel body: the last two
sections) a row in which several sequences' chunks lie. A rectangle of
LATENT rows is such a row, its chunks beginning at multiples of T
(``paged_flash_prefill_latent``); why the K/V rectangle kernel stays:
ROADMAP D21.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUPER_TOKENS = 512   # keys per compute iteration (amortizes the per-iteration
                     # flash-state relayout overhead; VMEM cost is
                     # 2 bufs * 2 pools * Hkv * 512/PACK * 128 * 2B)
BUFFER_BYTES = 8 << 20   # the superpage buffers' share of the 16 MiB of
                         # scoped VMEM a v5e gives a kernel
NUM_BUFS = 2         # superpage double buffering
ISSUE_UNROLL = 2     # pages a fetch-loop iteration issues (of 1, 2, 4 and 8
                     # the fastest on a v5e at both benchmark shapes, PERF.md)
LANES = 128          # minor-dim tiling the DMA slices must respect


def _pack(head_dim: int) -> int:
    return max(1, LANES // head_dim)


def super_tokens(num_kv_heads: int, head_dim: int, itemsize: int,
                 block_size: int) -> int:
    """Keys per compute iteration: ``SUPER_TOKENS``, halved while the four
    superpage buffers (every KV head each) are over ``BUFFER_BYTES`` — 30
    bf16 KV heads of 128 take 256, up to 16 heads the full 512."""
    tokens = SUPER_TOKENS
    while tokens > block_size and NUM_BUFS * 2 * num_kv_heads * tokens \
            * head_dim * itemsize > BUFFER_BYTES:
        tokens //= 2
    return tokens


class _PageFetch:
    """How pages of a row reach VMEM, stated once for the kernels
    below (trace time: built inside a kernel from its refs).

    A page is one copy a STREAM: ``streams`` lists ``(pool ref [L, heads,
    rows, lanes], buffer ref [NUM_BUFS, heads, a superpage's rows, lanes],
    DMA semaphores (NUM_BUFS,))``, K and V for the K/V kernels, the one
    latent pool for the others, and ``rows_per_page`` is what a page takes
    of a pool's row axis (``block_size``, over PACK where tokens are packed
    into whole lanes). Which buffer a superpage goes to, and who issues a
    program's first, is the hand-off's (``_superpage_sequence`` for the
    decode kernels, ``_tile_sequence`` for the prefill kernels)."""

    def __init__(self, streams, rows_per_page, *, block_size, super_tokens,
                 layer, block_tables_ref, kv_lens_ref):
        self.streams, self.rpp = streams, rows_per_page
        self.bs, self.sup = block_size, super_tokens
        self.spp = super_tokens // block_size   # pages per superpage
        self.layer = layer
        self.block_tables_ref, self.kv_lens_ref = block_tables_ref, kv_lens_ref

    def pages_of(self, row, s):
        # Pages of ``row`` that superpage s holds.
        return jnp.clip(pl.cdiv(self.kv_lens_ref[row], self.bs)
                        - s * self.spp, 0, self.spp)

    def start(self, row, s, slot):
        # Issue page-granular DMAs for superpage s of ``row`` into buffer
        # ``slot`` (pages are scattered in the pool; each is contiguous),
        # all in flight at once. The loops run over the row's own pages and
        # no further (``gp`` an iteration, then the odd ones): a short row
        # pays for what it holds, and the kernel's code stays a few pages
        # long where an unrolled superpage was 32 branches.
        rpp, spp = self.rpp, self.spp
        gp = min(ISSUE_UNROLL, spp)     # pages per fetch-loop iteration
        pages = self.pages_of(row, s)

        def issue(i):
            src = pl.ds(self.block_tables_ref[row, s * spp + i] * rpp, rpp)
            dst = pl.ds(pl.multiple_of(i * rpp, rpp), rpp)
            for pool, buf, sem in self.streams:
                pltpu.make_async_copy(
                    pool.at[self.layer, :, src], buf.at[slot, :, dst],
                    sem.at[slot],
                ).start()

        def issue_group(gi, carry):
            for j in range(gp):
                issue(gi * gp + j)
            return carry

        def issue_page(i, carry):
            issue(i)
            return carry

        jax.lax.fori_loop(0, pages // gp, issue_group, 0)
        jax.lax.fori_loop(pages // gp * gp, pages, issue_page, 0)

    def start_run(self, sources, row, at, rows, slot):
        # ``rows`` contiguous rows from ``at`` of ``row`` in ``sources``
        # ([heads, B, T, lanes], one a stream: a prefill chunk's own keys)
        # to the head of buffer ``slot``: one copy a stream.
        src = pl.ds(pl.multiple_of(at, rows), rows)
        for source, (_, buf, sem) in zip(sources, self.streams):
            pltpu.make_async_copy(
                source.at[:, row, src], buf.at[slot, :, pl.ds(0, rows)],
                sem.at[slot],
            ).start()

    def wait(self, pages, slot):
        # A DMA semaphore counts bytes, and a buffer's page copies all signal
        # the one semaphore of that buffer. So the wait is for the BYTES of
        # ``pages`` pages, taken in power-of-two runs of pages (the run
        # lengths present in the page count's binary form): at most two
        # waits a run, whatever order the pages land in.
        run = self.spp
        while run:
            @pl.when(pages & run != 0)
            def _():
                span = pl.ds(0, run * self.rpp)
                for pool, buf, sem in self.streams:
                    pltpu.make_async_copy(
                        pool.at[0, :, span], buf.at[slot, :, span],
                        sem.at[slot],
                    ).wait()
            run //= 2


def _superpage_sequence(fetch, cleared, b, kv_len, n_super, first,
                        first_of=None):
    """The decode kernels' hand-off of the superpage buffers from row to
    row: the call's superpages form ONE sequence across rows, superpage n of
    it in buffer n % NUM_BUFS, and row ``b`` (``n_super`` superpages of
    ``kv_len`` keys) starts at ``first``, in the buffer its predecessors
    left free, whatever their lengths were. Clears ``cleared`` once a call,
    issues the row's first superpage where no row before it did, and
    returns ``advance(s)``: puts what computes after superpage s in flight,
    waits for s and returns its buffer. ``first_of(row)``: the superpage of
    its pages a row begins at (a layer's span: the ones wholly behind it
    are neither fetched nor waited for); absent, 0."""
    kv_lens_ref = fetch.kv_lens_ref
    num_rows = kv_lens_ref.shape[0]
    if first_of is None:
        def at(row, s):
            return s
    else:
        def at(row, s):
            return first_of(row) + s

    # What a row does not fetch it still computes over: whole superpages,
    # and a masked key's softmax weight (0) must not meet a non-finite value
    # there: 0 * NaN = NaN inside the PV contraction would poison the row.
    # (A masked SCORE is replaced, not multiplied, so K may hold anything.)
    # Only what the call finds in its value buffers can be non-finite; what
    # its rows leave behind is KV, finite like the pool. So they are cleared
    # once a call, before the first DMA is in flight, and stale keys stay
    # where they are.
    @pl.when(b == 0)
    def _():
        cleared[...] = jnp.zeros(cleared.shape, cleared.dtype)

    # A live row's last iteration issues the next row's first superpage, so
    # only the call's first row, and a row behind an empty one, starts by
    # issuing its own (and then waits for it at once).
    prev_len = kv_lens_ref[jnp.maximum(b - 1, 0)]

    @pl.when((kv_len > 0) & ((b == 0) | (prev_len == 0)))
    def _():
        fetch.start(b, at(b, 0), jax.lax.rem(first, NUM_BUFS))

    def advance(s):
        n = first + s
        slot = jax.lax.rem(n, NUM_BUFS)

        # What computes next goes in flight now, into the other buffer:
        # this row's superpage s + 1 or, behind its last, superpage 0 of
        # the next row (an empty next row has no pages, so nothing issues).
        last = s + 1 == n_super

        @pl.when(jnp.logical_not(last) | (b + 1 < num_rows))
        def _():
            row = jnp.where(last, jnp.minimum(b + 1, num_rows - 1), b)
            fetch.start(row, at(row, jnp.where(last, 0, s + 1)),
                        jax.lax.rem(n + 1, NUM_BUFS))

        fetch.wait(fetch.pages_of(b, at(b, s)), slot)
        return slot

    return advance


def _prefetched_behind(kernel, prefetched: int, **scalars):
    """(``kernel`` taking every one of ``scalars`` that is given as one more
    scalar-prefetch ref behind the ``prefetched`` it has, by its keyword;
    the operands to pass there, in that order): the kernel as it is and
    nothing where all are None."""
    given = {name: x for name, x in scalars.items() if x is not None}
    if not given:
        return kernel, ()
    upto = prefetched + len(given)

    def more(*refs):
        kernel(*refs[:prefetched], *refs[upto:],
               **dict(zip(given, refs[prefetched:upto])))

    return more, tuple(given.values())


def _decode_kernel(
    # scalar prefetch
    layer_ref,          # SMEM [1] int32 — which layer of the stacked pool
    block_tables_ref,   # SMEM [B, Mb] int32
    kv_lens_ref,        # SMEM [B] int32
    # inputs
    q_ref,              # VMEM [B, H, Dh] (resident: fetched once a call)
    k_hbm,              # HBM  [L, Hkv, num_slots/PACK, Dh*PACK]
    v_hbm,              # HBM  [L, Hkv, num_slots/PACK, Dh*PACK]
    # quantized==True only (int8 pools): this dispatch's pre-gathered
    # per-slot dequant scales, lane-half-major (see
    # paged_flash_decode_stats) — k_sc_ref/v_sc_ref VMEM
    # [1, PACK, Hkv, Mb*bs/PACK] f32, then the outputs/scratch below.
    *rest,
    block_size: int,
    num_kv_heads: int,
    q_per_kv: int,
    scale: float,
    quantized: bool,
    super_tokens: int,
    lo_ref=None,        # SMEM [B] int32: a row's first visible key (a span)
):
    if quantized:
        (k_sc_ref, v_sc_ref, o_ref, m_ref, l_ref,
         k_buf, v_buf, sem_k, sem_v, fetched_ref) = rest
    else:
        k_sc_ref = v_sc_ref = None
        o_ref, m_ref, l_ref, k_buf, v_buf, sem_k, sem_v, fetched_ref = rest
    # o_ref: VMEM [B, H, Dh]; m_ref/l_ref: VMEM [B, 1, H] f32 (running max
    # pre-normalization / softmax denominator), resident like q and written
    # back once a call: a program moves no block of its own, which is most
    # of what an empty row used to cost; k_buf/v_buf: VMEM
    # [NUM_BUFS, Hkv, super_tokens/PACK, Dh*PACK] pool-dtype scratch;
    # sem_k/sem_v: DMA sems (NUM_BUFS,), one a buffer; fetched_ref: SMEM
    # [1] int32, superpages the rows before this one fetched. Scratch
    # outlives a program, which is what hands buffers from row to row.
    b = pl.program_id(0)
    layer = layer_ref[0]
    bs = block_size
    hkv, g = num_kv_heads, q_per_kv
    dh = q_ref.shape[-1]
    pack = _pack(dh)
    stp = super_tokens // pack          # packed rows per superpage
    kv_len = kv_lens_ref[b]
    n_super = pl.cdiv(kv_len, super_tokens)
    first_of = None
    if lo_ref is not None:
        # A row under a span begins at the superpage that holds its first
        # visible key (``lo < kv_len`` in every live row: the wrapper's).
        def first_of(row):
            return lo_ref[row] // super_tokens

        n_super = n_super - first_of(b)
    # The call's superpages before this row's (``_superpage_sequence``).
    first = jnp.where(b == 0, 0, fetched_ref[0])
    fetched_ref[0] = first + n_super

    # q: [H, Dh] -> [Hkv, G, Dh] fp32, pre-scaled
    q = q_ref[b].astype(jnp.float32).reshape(hkv, g, dh) * scale

    fetch = _PageFetch(
        [(k_hbm, k_buf, sem_k), (v_hbm, v_buf, sem_v)], bs // pack,
        block_size=bs, super_tokens=super_tokens, layer=layer,
        block_tables_ref=block_tables_ref, kv_lens_ref=kv_lens_ref)

    advance = _superpage_sequence(fetch, v_buf, b, kv_len, n_super, first,
                                  first_of)

    def body(s, carry):
        m, l, acc = carry
        slot = advance(s)
        if lo_ref is not None:
            s = s + first_of(b)          # the superpage among the row's own
        k_sup = k_buf[slot]   # [Hkv, S/PACK, Dh*PACK] — head-major: batch
        v_sup = v_buf[slot]   # dim leads, so NO per-superpage relayout.

        # Each lane-half f holds tokens pack*j + f. Static unroll over the
        # PACK halves; flash state update folds all halves of the superpage.
        m_parts = [m]
        s_parts = []
        for f in range(pack):
            kf = k_sup[:, :, f * dh:(f + 1) * dh]          # [Hkv, S/P, Dh]
            if quantized:
                # int8 payload: the raw dot is exact in f32 (|q| <= 127);
                # the per-slot dequant scale is a rank-1 factor on the KEY
                # axis, so it multiplies the scores instead of the payload
                # — K never materializes dequantized.
                kf = kf.astype(jnp.float32)
            scores = jax.lax.dot_general(
                q, kf,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )                                               # [Hkv, G, S/P]
            if quantized:
                ksc = k_sc_ref[0, f, :, pl.ds(s * stp, stp)]  # [Hkv, S/P]
                scores = scores * ksc[:, None, :]
            pos = s * super_tokens + pack * jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, stp), 2
            ) + f
            live = pos < kv_len
            if lo_ref is not None:
                # The first superpage's keys before the span's bound (it
                # holds at least one visible key, so the max stays finite).
                live = live & (pos >= lo_ref[b])
            scores = jnp.where(live, scores, -jnp.inf)
            s_parts.append(scores)
            m_parts.append(jnp.max(scores, axis=-1, keepdims=True))

        m_new = functools.reduce(jnp.maximum, m_parts)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha
        acc_new = acc * alpha
        for f in range(pack):
            p_ = jnp.exp(s_parts[f] - m_new)               # [Hkv, G, S/P]
            l_new = l_new + jnp.sum(p_, axis=-1, keepdims=True)
            vf = v_sup[:, :, f * dh:(f + 1) * dh]
            if quantized:
                # Same rank-1 trick on the VALUE side: fold each slot's
                # scale into its softmax weight before the PV contraction.
                vf = vf.astype(jnp.float32)
                vsc = v_sc_ref[0, f, :, pl.ds(s * stp, stp)]  # [Hkv, S/P]
                p_ = p_ * vsc[:, None, :]
            acc_new = acc_new + jax.lax.dot_general(
                p_, vf,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
        return m_new, l_new, acc_new

    m0 = jnp.full((hkv, g, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((hkv, g, 1), jnp.float32)
    acc0 = jnp.zeros((hkv, g, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_super, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)
    o_ref[b] = out.reshape(hkv * g, dh).astype(o_ref.dtype)
    m_ref[b, 0] = m.reshape(hkv * g)
    l_ref[b, 0] = l.reshape(hkv * g)


def supports_pallas_decode(head_dim: int, block_size: int) -> bool:
    pack = _pack(head_dim)
    return (
        (head_dim % LANES == 0 or LANES % head_dim == 0)
        and SUPER_TOKENS % block_size == 0
        and block_size % pack == 0
    )


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret")
)
def paged_flash_decode_stats(
    q: jax.Array,             # [B, H, Dh] decode queries (post-rope)
    k_pool: jax.Array,        # [L, Hkv, num_slots, Dh] (head-major per layer)
    v_pool: jax.Array,        # [L, Hkv, num_slots, Dh]
    block_tables: jax.Array,  # [B, Mb] int32
    kv_lens: jax.Array,       # [B] int32 — tokens resident in the pool
    layer_idx: jax.Array,     # [] or [1] int32 — layer of the stacked pool
    *,
    block_size: int,
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [L, Hkv, num_slots] — int8 pools
    v_scale: Optional[jax.Array] = None,
    kv_lo: Optional[jax.Array] = None,    # [B] int32: first visible key
) -> tuple:
    """Pool-segment flash decode for one layer of the stacked pool.

    ``kv_lo`` (a layer's span, ops/attention.py): row b attends the pool's
    slots ``kv_lo[b] <= j < kv_lens[b]`` and no others. It starts at the
    superpage that holds slot ``kv_lo[b]``: the superpages wholly behind it
    are neither fetched nor scored, and the slots of that first superpage
    that lie before ``kv_lo[b]`` are masked. A row with ``kv_lo >= kv_lens``
    is an empty row. Absent (static), the program is the unbounded one.

    Returns (out [B, H, Dh] normalized, m [B, H] f32, l [B, H] f32) so the
    caller can merge with other attention segments (see
    ops/attention.py:merge_attention_segments). Rows with kv_len == 0 return
    (0, -inf, 0) — a no-op under the merge — and cost a grid step: they
    fetch and compute nothing.

    The pool must be finite wherever a live row's pages reach; blocks no
    live row owns (block 0 behind padded table entries among them) may hold
    anything, and so may the VMEM the call finds: never-fetched tails of a
    superpage are not zero-filled per row, V's buffers are cleared once a
    call and stale finite KV stays (see the module docstring).

    Quantized pools (``k_scale``/``v_scale`` set, int8 payload): the page
    DMAs move int8 — half the bf16 HBM traffic — and dequantization happens
    INSIDE the kernel as rank-1 score/weight scaling; a bf16 copy of the
    pool never exists. The per-slot scales the dispatch can touch are
    gathered OUTSIDE the kernel ([B, Mb*bs] per head — a few hundred KB
    against the pool's GBs) because page-granular scale rows are far below
    the 128-lane DMA grain; they ride in as a lane-half-major VMEM input
    ``[B, PACK, Hkv, Mb*bs/PACK]`` so lane-half f of superpage s slices
    contiguously in-kernel.
    """
    b, h, dh = q.shape
    l_, hkv, num_slots, _ = k_pool.shape
    g = h // hkv
    if scale is None:
        scale = dh ** -0.5
    pack = _pack(dh)
    quantized = k_scale is not None
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    sup = super_tokens(hkv, dh, k_pool.dtype.itemsize, block_size)
    if kv_lo is not None:
        assert not quantized, "a span over an int8 pool is refused at start"
        kv_lo = jnp.maximum(kv_lo.astype(jnp.int32), 0)
        # No visible key in the pool: an empty row (fetches nothing).
        kv_lens = jnp.where(kv_lo < kv_lens, kv_lens, 0)

    # Lane-pack the pool view: [L, Hkv, NS/PACK, Dh*PACK] (free reshape).
    kp = k_pool.reshape(l_, hkv, num_slots // pack, dh * pack)
    vp = v_pool.reshape(l_, hkv, num_slots // pack, dh * pack)

    sc_inputs = []
    sc_specs = []
    if quantized:
        mb = block_tables.shape[1]
        nb = num_slots // block_size
        # Pad the window to whole SUPERPAGES: the kernel slices
        # sup/PACK scale rows per compute iteration even when the
        # block table covers less (tail scores there are masked by
        # pos >= kv_len, so the zero padding is never read into a result).
        total = mb * block_size
        padded = pl.cdiv(total, sup) * sup

        def sc_window(sc_pool):
            # This layer's per-slot scales at the dispatch's pages:
            # [Hkv, NS] -> gather blocks -> [Hkv, B, Mb*bs] -> lane-half
            # major [B, PACK, Hkv, padded/PACK] f32 (token t of a row's
            # window = half t%PACK, packed row t//PACK).
            sc_l = jnp.take(sc_pool, layer[0], axis=0)      # [Hkv, NS]
            scw = sc_l.reshape(hkv, nb, block_size)[:, block_tables]
            scw = scw.reshape(hkv, b, total)
            if padded != total:
                scw = jnp.pad(scw, ((0, 0), (0, 0), (0, padded - total)))
            scw = scw.reshape(hkv, b, padded // pack, pack)
            return scw.transpose(1, 3, 0, 2).astype(jnp.float32)

        sc_inputs = [sc_window(k_scale), sc_window(v_scale)]
        sc_block = (1, pack, hkv, padded // pack)
        sc_specs = [
            pl.BlockSpec(sc_block, lambda i, *_: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(sc_block, lambda i, *_: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ]

    kernel = functools.partial(
        _decode_kernel,
        block_size=block_size, num_kv_heads=hkv, q_per_kv=g,
        scale=float(scale), quantized=quantized, super_tokens=sup,
    )
    kernel, bound = _prefetched_behind(kernel, 3, lo_ref=kv_lo)

    def resident(*shape):
        # The whole array is one block that every program sees: copied in
        # (or out) once a call, indexed by the program's row in the kernel.
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(bound),
        grid=(b,),
        in_specs=[
            resident(b, h, dh),
            pl.BlockSpec(memory_space=pl.ANY),  # pool stays off-chip;
            pl.BlockSpec(memory_space=pl.ANY),  # kernel DMAs pages itself
            *sc_specs,
        ],
        out_specs=[
            resident(b, h, dh),
            # [B, 1, H]: a program writes row b as a whole [1, H] tile.
            resident(b, 1, h),
            resident(b, 1, h),
        ],
        scratch_shapes=[
            pltpu.VMEM(
                (NUM_BUFS, hkv, sup // pack, dh * pack),
                k_pool.dtype,
            ),
            pltpu.VMEM(
                (NUM_BUFS, hkv, sup // pack, dh * pack),
                v_pool.dtype,
            ),
            pltpu.SemaphoreType.DMA((NUM_BUFS,)),
            pltpu.SemaphoreType.DMA((NUM_BUFS,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dh), q.dtype),
            jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
        ],
        grid_spec=grid_spec,
        # Rows run in program order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        layer,
        block_tables, kv_lens, *bound, q, kp, vp, *sc_inputs,
    )
    return out, m.reshape(b, h), l.reshape(b, h)


def paged_flash_decode_stats_tp(
    q: jax.Array,             # [B, H, Dh] decode queries (post-rope)
    k_pool: jax.Array,        # [L, Hkv, num_slots, Dh] — Hkv sharded over tp
    v_pool: jax.Array,
    block_tables: jax.Array,  # [B, Mb] int32 (replicated)
    kv_lens: jax.Array,       # [B] int32 (replicated)
    layer_idx: jax.Array,
    mesh,                     # jax.sharding.Mesh with a "tp" axis > 1
    *,
    block_size: int,
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [L, Hkv, num_slots] — kv-head
    v_scale: Optional[jax.Array] = None,  # sharded like the pools
) -> tuple:
    """TP-sharded pool-segment flash decode via shard_map over kv heads.

    The KV pool is head-sharded over the tp mesh axis
    (parallel/sharding.py:kv_pool_sharding) and pallas_call carries no GSPMD
    partitioning rule, so calling the kernel directly under jit would force
    an all-gather of the entire pool (advisor r3 high finding). Each kv
    head's attention is independent, so running the kernel per-shard over
    its local heads — queries head-sharded to match (GQA groups stay with
    their kv head) — is exact and needs no collectives; the row-parallel
    o-projection's psum downstream is unchanged.

    Requires num_heads % tp == 0 and num_kv_heads % tp == 0 (enforced by
    EngineConfig.resolved_attn_impl).
    """
    from jax.sharding import PartitionSpec as P

    from production_stack_tpu.parallel.mesh import AXIS_TP

    quantized = k_scale is not None

    def fn(q_, kp_, vp_, bt_, lens_, li_, *sc_):
        ks_, vs_ = sc_ if quantized else (None, None)
        return paged_flash_decode_stats(
            q_, kp_, vp_, bt_, lens_, li_,
            block_size=block_size, scale=scale, interpret=interpret,
            k_scale=ks_, v_scale=vs_,
        )

    in_specs = (
        P(None, AXIS_TP, None),        # q: heads sharded
        P(None, AXIS_TP, None, None),  # pools: kv heads sharded
        P(None, AXIS_TP, None, None),
        P(None, None),                 # block tables replicated
        P(None,),                      # kv lens replicated
        P(None,),                      # layer index replicated
    )
    args = (q, k_pool, v_pool, block_tables, kv_lens,
            jnp.asarray(layer_idx, jnp.int32).reshape(1))
    if quantized:
        # Scale pools share the pools' kv-head sharding, so each shard
        # dequantizes its local heads with local scales — still collective-
        # free.
        in_specs += (P(None, AXIS_TP, None), P(None, AXIS_TP, None))
        args += (k_scale, v_scale)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(
            P(None, AXIS_TP, None),        # out [B, H, Dh]
            P(None, AXIS_TP),              # m [B, H]
            P(None, AXIS_TP),              # l [B, H]
        ),
        check_vma=False,
    )(*args)


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret")
)
def paged_attention_decode_pallas(
    q: jax.Array,             # [B, 1, H, Dh]
    k_pool: jax.Array,        # [Hkv, num_slots, Dh] (head-major)
    v_pool: jax.Array,        # [Hkv, num_slots, Dh]
    block_tables: jax.Array,  # [B, Mb] int32
    kv_lens: jax.Array,       # [B] int32
    *,
    block_size: int,
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [Hkv, num_slots] (int8 pools)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-layer convenience wrapper (normalized output only)."""
    b, t, h, dh = q.shape
    assert t == 1, "the decode kernel; a chunk takes paged_flash_prefill"
    out, _, _ = paged_flash_decode_stats(
        q.reshape(b, h, dh), k_pool[None], v_pool[None], block_tables,
        kv_lens, jnp.zeros((1,), jnp.int32),
        block_size=block_size, scale=scale, interpret=interpret,
        k_scale=None if k_scale is None else k_scale[None],
        v_scale=None if v_scale is None else v_scale[None],
    )
    return out.reshape(b, 1, h, dh)


# --------------------------------------------------------------- latent rows
# A latent pool (models/config.py:LatentKVSpec) keeps ONE row a token and
# layer, [L, 1, num_slots, W]: the compressed KV, the shared rotary key and
# zeros to whole lanes. Every query head attends the same rows (absorbed
# multi-head latent attention is multi-query attention whose keys are the
# whole row and whose values are its first ``value_dim`` lanes), so a page is
# fetched ONCE and serves the scores and the values. The kernel is the one
# above with one pool, one buffer a superpage and no packing or
# quantization: the same sequence of superpages across rows, the same
# next-row prefetch, the same byte-counting waits.


def _latent_decode_kernel(
    layer_ref,          # SMEM [1] int32
    block_tables_ref,   # SMEM [B, Mb] int32
    kv_lens_ref,        # SMEM [B] int32
    q_ref,              # VMEM [B, H, W] (resident; zeros past the key's lanes)
    kv_hbm,             # HBM  [L, 1, num_slots, W]
    o_ref,              # VMEM [B, H, Dv]
    m_ref,              # VMEM [B, 1, H] f32
    l_ref,              # VMEM [B, 1, H] f32
    kv_buf,             # VMEM [NUM_BUFS, 1, super_tokens, W]
    sem,                # DMA sems (NUM_BUFS,)
    fetched_ref,        # SMEM [1] int32
    *,
    block_size: int,
    value_dim: int,
    scale: float,
    super_tokens: int,
):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bs = block_size
    h = q_ref.shape[1]
    kv_len = kv_lens_ref[b]
    n_super = pl.cdiv(kv_len, super_tokens)
    first = jnp.where(b == 0, 0, fetched_ref[0])
    fetched_ref[0] = first + n_super

    q = q_ref[b].astype(jnp.float32)[None] * scale          # [1, H, W]

    fetch = _PageFetch(
        [(kv_hbm, kv_buf, sem)], bs, block_size=bs,
        super_tokens=super_tokens, layer=layer,
        block_tables_ref=block_tables_ref, kv_lens_ref=kv_lens_ref)

    # The buffers are keys AND values: cleared whole.
    advance = _superpage_sequence(fetch, kv_buf, b, kv_len, n_super, first)

    def body(s, carry):
        m, l, acc = carry
        slot = advance(s)
        rows = kv_buf[slot]                                  # [1, S, W]
        scores = jax.lax.dot_general(
            q, rows,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                    # [1, H, S]
        pos = s * super_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, super_tokens), 2)
        scores = jnp.where(pos < kv_len, scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(scores - m_new)
        l_new = l * alpha + jnp.sum(p_, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p_, rows[:, :, :value_dim],
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                    # [1, H, Dv]
        return m_new, l_new, acc_new

    m0 = jnp.full((1, h, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, h, 1), jnp.float32)
    acc0 = jnp.zeros((1, h, value_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_super, body, (m0, l0, acc0))

    o_ref[b] = (acc / jnp.maximum(l, 1e-30))[0].astype(o_ref.dtype)
    m_ref[b, 0] = m.reshape(h)
    l_ref[b, 0] = l.reshape(h)


def supports_latent_decode(width: int, value_dim: int,
                           block_size: int) -> bool:
    return (width % LANES == 0 and value_dim % LANES == 0
            and value_dim <= width and SUPER_TOKENS % block_size == 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "value_dim", "scale", "interpret"),
)
def paged_flash_decode_latent_stats(
    q: jax.Array,             # [B, H, W] absorbed queries, zeros past the key
    kv_pool: jax.Array,       # [L, 1, num_slots, W] latent rows
    block_tables: jax.Array,  # [B, Mb] int32
    kv_lens: jax.Array,       # [B] int32 — tokens resident in the pool
    layer_idx: jax.Array,     # [] or [1] int32
    *,
    block_size: int,
    value_dim: int,
    scale: float,
    interpret: bool = False,
) -> tuple:
    """``paged_flash_decode_stats`` over a latent pool: every head of a row
    attends the row's pages, keys the whole pool row, values its first
    ``value_dim`` lanes. Returns (out [B, H, value_dim] normalized, m [B, H]
    f32, l [B, H] f32); a row with ``kv_len == 0`` returns (0, -inf, 0) and
    fetches nothing. The pool must be finite wherever a live row's pages
    reach, its padding lanes included (the engine writes zeros there)."""
    b, h, w = q.shape
    sup = super_tokens(1, w, kv_pool.dtype.itemsize, block_size)
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    kernel = functools.partial(
        _latent_decode_kernel, block_size=block_size, value_dim=value_dim,
        scale=float(scale), super_tokens=sup,
    )

    def resident(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    out, m, l = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
            jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[resident(b, h, w),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[resident(b, h, value_dim), resident(b, 1, h),
                       resident(b, 1, h)],
            scratch_shapes=[
                pltpu.VMEM((NUM_BUFS, 1, sup, w), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(layer, block_tables, kv_lens, q, kv_pool)
    return out, m.reshape(b, h), l.reshape(b, h)


# ------------------------------------------------------------------ prefill
# A prefill chunk (T > 1) attends through the same machinery: the row's
# HISTORY is read in place from the paged pool by its block table, up to
# ``kv_len`` and no further, then the chunk's own K/V causally; the
# softmax's running max, sum and accumulator live in VMEM, so no gathered
# window and no [.., M, S] score tensor ever exists in HBM. What differs
# from decode:
#
#   * Grid (rows, query blocks). A program holds QUERY_BLOCK queries of one
#     row for EVERY head: a KV head's G x TQ query rows are its matmuls' M
#     (the wrapper lays q out as [B, Hkv, NQ, G*TQ, Dh], pre-scaled). Pages
#     are fetched with all KV heads at once, as decode fetches them (a DMA
#     a head would multiply the starts by Hkv: 30 for the hybrid's full
#     layers); the heads are a loop inside, their flash state in scratch.
#   * The call's key TILES form one sequence across programs, in the two
#     superpage buffers, the next always in flight: a program's history
#     superpages (cdiv(kv_len, super_tokens), none at kv_len 0), then the
#     chunk's key blocks 0..qb, each ONE copy of [Hkv, TQ, Dh] out of the
#     chunk's K/V [Hkv, B, T, Dh] in HBM. Key blocks above the diagonal
#     are never fetched; block qb is masked by position, as
#     ``window_attention`` masks it (key position <= query position, key
#     index < chunk_len).
#   * A program whose queries are all padding (``qb * TQ >= chunk_len``: a
#     padded row is ``chunk_len == 0``) fetches, waits for and computes
#     nothing, and writes zeros.
#
# Scores, max, exp and sums are float32; both products take operands in the
# pool's dtype with float32 accumulation; the output is q.dtype: what
# ``ops/attention.py:window_attention`` computes, nothing lower. This
# section's kernel reads a RECTANGLE of K/V rows of two pools; a rectangle
# of latent rows (one pool, every head the same row) is laid as a row of the
# packed body (the last section), and
# ``ops/attention.py:prefill_kernel_covers`` says which views a kernel
# covers (none: int8 scales, a sharded pool, a ring, a ``chunk_bias``; those
# keep the gathered window).
QUERY_BLOCK = 256    # chunk queries a program, chunk keys a tile
PREFILL_VMEM_BYTES = 64 << 20   # of a v5e's 128 MiB: buffers 8, q and o
                                # blocks twice 8, flash state 13, scores 16
# A masked score: finite, so that max and exp stay finite whatever a tile
# holds, and far below any real score (exp(_MASKED - m) is exactly 0).
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def prefill_tiles(t: int, num_heads: int, num_kv_heads: int, head_dim: int,
                  itemsize: int, block_size: int):
    """(superpage tokens, queries a program) for a chunk of ``t`` tokens:
    the decode kernel's superpage, and QUERY_BLOCK queries, halved while
    one program's rows over all heads (H x TQ x Dh: its q and o blocks and
    flash state) or one KV head's score rows (G x TQ) are too many."""
    sup = super_tokens(num_kv_heads, head_dim, itemsize, block_size)
    tq = min(t, QUERY_BLOCK, sup)
    g = num_heads // num_kv_heads
    while tq > block_size and tq % 2 == 0 and (
            num_heads * tq * head_dim > 8192 * LANES or g * tq > 2048):
        tq //= 2
    return sup, tq


def supports_pallas_prefill(t: int, num_heads: int, num_kv_heads: int,
                            head_dim: int, itemsize: int,
                            block_size: int) -> bool:
    """Whole lanes a head (no token packing: head_dim 64 stays on the
    window path), pages that tile a superpage, and a chunk that is whole
    query blocks of whole pages."""
    if head_dim % LANES or SUPER_TOKENS % block_size or block_size % 8 \
            or num_heads % num_kv_heads:
        return False
    _, tq = prefill_tiles(t, num_heads, num_kv_heads, head_dim, itemsize,
                          block_size)
    return t % tq == 0 and tq % block_size == 0


def _tile_sequence(fetch, chunk, chunk_lens_ref, fetched_ref, cleared, *,
                   program, programs, tq, tile_rows, tiles, chunk_at=None,
                   hist_from=None):
    """The prefill kernels' hand-off of the superpage buffers from program
    to program. Program (row, query block) reads its row's history
    superpages (``fetch``'s pages, ``fetch.sup`` keys each; none at kv_len
    0) and then key tiles of the chunk, ``tile_rows`` keys each, ONE copy a
    stream out of ``chunk`` (the chunk's rows in HBM, [heads, B, T, lanes] a
    stream): ``tiles(row, history tiles, block)`` in all, none where its
    ``tq`` queries are all padding. Chunk tile c of a program is
    ``chunk_at(row, blk, c)`` = (row of ``chunk``, first key): the row's
    own keys from c * tile_rows unless the caller says otherwise (a "row"
    of the packed kernel is a SEGMENT's share of a query block, and its
    keys lie in the one packed row from the tile that holds the segment's
    first token; under a span a block's first tile is the one that holds
    its first query's bound). ``hist_from(row, blk)``, under a span: the
    first history superpage the program reads (the row's count of them
    where it reads none); absent, 0. The call's tiles form one sequence
    across programs, tile n in buffer n % NUM_BUFS, the next always in
    flight. Returns (history tiles, tiles, ``advance``) of this program:
    ``advance(s)`` puts what computes after tile s in flight, waits for
    tile s and returns its buffer."""
    (b, qb), (num_rows, nq) = program, programs
    kv_lens_ref, sup = fetch.kv_lens_ref, fetch.sup
    if chunk_at is None:
        def chunk_at(row, blk, c):
            return row, c * tile_rows

    if hist_from is None:
        def hist_tiles(row, blk):
            return pl.cdiv(kv_lens_ref[row], sup)

        def hist_at(row, blk, s):
            return s
    else:
        def hist_tiles(row, blk):
            return pl.cdiv(kv_lens_ref[row], sup) - hist_from(row, blk)

        def hist_at(row, blk, s):
            return hist_from(row, blk) + s

    def tiles_of(row, blk):
        return jnp.where(blk * tq < chunk_lens_ref[row],
                         tiles(row, hist_tiles(row, blk), blk), 0)

    n_hist = hist_tiles(b, qb)
    n_tiles = tiles_of(b, qb)
    is_first = (b == 0) & (qb == 0)
    first = jnp.where(is_first, 0, fetched_ref[0])
    fetched_ref[0] = first + n_tiles

    def start_tile(row, blk, s, n):
        # Tile s of program (row, blk), the n-th of the call, goes in
        # flight into buffer n % NUM_BUFS. A program with no tiles issues
        # nothing.
        slot = jax.lax.rem(n, NUM_BUFS)
        nh = hist_tiles(row, blk)
        has = s < tiles_of(row, blk)

        @pl.when(has & (s < nh))
        def _():
            # A history superpage: page-granular copies, as decode's.
            fetch.start(row, hist_at(row, blk, s), slot)

        @pl.when(has & (s >= nh))
        def _():
            # A key tile of the chunk: contiguous.
            fetch.start_run(chunk, *chunk_at(row, blk, s - nh), tile_rows,
                            slot)

    # A masked key's weight (0) must not meet a non-finite value (see the
    # decode kernel): what holds values is cleared once a call; what tiles
    # leave behind is pool and chunk content, finite.
    @pl.when(is_first)
    def _():
        cleared[...] = jnp.zeros(cleared.shape, cleared.dtype)

    # The program before this one issued this one's first tile from its
    # last iteration, unless it had none (or there is none before).
    prev_row = jnp.where(qb > 0, b, jnp.maximum(b - 1, 0))
    prev_blk = jnp.where(qb > 0, qb - 1, nq - 1)

    @pl.when((n_tiles > 0) & (is_first | (tiles_of(prev_row, prev_blk) == 0)))
    def _():
        start_tile(b, qb, 0, first)

    wraps = qb + 1 == nq
    next_row = jnp.minimum(jnp.where(wraps, b + 1, b), num_rows - 1)
    next_blk = jnp.where(wraps, 0, qb + 1)
    has_next = jnp.logical_not(wraps) | (b + 1 < num_rows)

    def advance(s):
        n = first + s
        slot = jax.lax.rem(n, NUM_BUFS)
        last = s + 1 == n_tiles

        @pl.when(jnp.logical_not(last) | has_next)
        def _():
            start_tile(
                jnp.where(last, next_row, b), jnp.where(last, next_blk, qb),
                jnp.where(last, 0, s + 1), n + 1,
            )

        # By bytes, in runs of pages: a chunk tile counts as its pages.
        fetch.wait(jnp.where(s < n_hist,
                             fetch.pages_of(b, hist_at(b, qb, s)),
                             tile_rows // fetch.bs), slot)
        return slot

    return n_hist, n_tiles, advance


def _span_operand(span):
    """The layer's span as the kernels' scalar: [1] int32, at least 1."""
    return None if span is None else jnp.maximum(
        jnp.asarray(span, jnp.int32), 1).reshape(1)


def _prefill_kernel(
    # scalar prefetch
    layer_ref,          # SMEM [1] int32
    block_tables_ref,   # SMEM [B, Mb] int32
    kv_lens_ref,        # SMEM [B] int32: tokens of the row in the pool
    chunk_lens_ref,     # SMEM [B] int32: valid tokens of the row's chunk
    # inputs
    q_ref,              # VMEM [1, Hkv, 1, G*TQ, Dh] (pre-scaled)
    posq_ref,           # VMEM [1, 1, TQ, 1] int32: the block's positions
    posk_ref,           # VMEM [1, NQ, 1, TQ] int32: the row's, as rows
    kc_hbm,             # HBM  [Hkv, B, T, Dh]: the chunk's keys
    vc_hbm,             # HBM  [Hkv, B, T, Dh]
    k_hbm,              # HBM  [L, Hkv, num_slots, Dh]
    v_hbm,              # HBM  [L, Hkv, num_slots, Dh]
    # output
    o_ref,              # VMEM [1, Hkv, 1, G*TQ, Dh]
    # scratch (outlives a program: the buffers are handed on)
    k_buf,              # VMEM [NUM_BUFS, Hkv, super_tokens, Dh]
    v_buf,
    sem_k,              # DMA sems (NUM_BUFS,)
    sem_v,
    fetched_ref,        # SMEM [1] int32: tiles the programs before fetched
    m_ref,              # VMEM [Hkv, G*TQ, 1] f32: running max
    l_ref,              # VMEM [Hkv, G*TQ, 1] f32: running sum
    acc_ref,            # VMEM [Hkv, G*TQ, Dh] f32
    *,
    block_size: int,
    super_tokens: int,
    tq: int,
    q_per_kv: int,
    span_ref=None,      # SMEM [1] int32: the layer's span (absent: none)
):
    b, qb = pl.program_id(0), pl.program_id(1)
    num_rows, nq = pl.num_programs(0), pl.num_programs(1)
    layer = layer_ref[0]
    bs, sup, g = block_size, super_tokens, q_per_kv
    hkv = k_buf.shape[1]
    kv_len = kv_lens_ref[b]
    chunk_len = chunk_lens_ref[b]
    fetch = _PageFetch(
        [(k_hbm, k_buf, sem_k), (v_hbm, v_buf, sem_v)], bs, block_size=bs,
        super_tokens=sup, layer=layer, block_tables_ref=block_tables_ref,
        kv_lens_ref=kv_lens_ref)
    # Chunk key blocks 0..qb, TQ keys each; V is what holds values.
    bounds = {"tiles": lambda row, hist, blk: hist + blk + 1}
    if span_ref is not None:
        # Under a span (token i of a row sits at position kv_len + i) a
        # block reads from the history superpage and from the chunk tile
        # that hold the first key its FIRST query sees; later queries'
        # bounds lie further on and are masked.
        span = span_ref[0]

        def hist_from(row, blk):
            n = kv_lens_ref[row]
            lo = jnp.maximum(n + blk * tq - span + 1, 0)
            return jnp.where(lo < n, lo // sup, pl.cdiv(n, sup))

        def chunk_from(blk):
            return jnp.maximum(blk * tq - span + 1, 0) // tq

        bounds = {
            "tiles": lambda row, hist, blk: hist + blk - chunk_from(blk) + 1,
            "chunk_at": lambda row, blk, c: (row, (chunk_from(blk) + c) * tq),
            "hist_from": hist_from}
    n_hist, n_tiles, advance = _tile_sequence(
        fetch, (kc_hbm, vc_hbm), chunk_lens_ref, fetched_ref, v_buf,
        program=(b, qb), programs=(num_rows, nq), tq=tq, tile_rows=tq,
        **bounds)

    def block_positions():
        pos_q = posq_ref[0, 0]                               # [TQ, 1]
        return jnp.broadcast_to(pos_q[None], (g, tq, 1)).reshape(g * tq, 1)

    def flash_block(keys_of, mask_of):
        # One tile's keys against every head's query rows: the heads are a
        # loop, their flash state in scratch. Whole heads and whole tiles
        # at a time: what a block costs beside its scores is the state's
        # round trip (m, l and the accumulator: 3 x M x 128 lanes of
        # float32 read and written), so fewer, larger blocks win. On a v5e
        # a KV head's score block in pieces of 64, 128 and 256 query rows
        # took 2.4, 1.7 and 1.3 times as long, a history superpage in two
        # blocks of 256 keys 1.8 times as long as in one of 512, and
        # skipping the select where nothing is masked changed nothing
        # (PERF.md §6, PR 35).
        def head(hk, carry):
            q = q_ref[0, hk, 0]                              # [M, Dh]
            k, v = keys_of(hk)                               # [keys, Dh]
            scores = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                # [M, keys]
            scores = jnp.where(mask_of(), scores, _MASKED)
            m_prev = m_ref[hk]                               # [M, 1]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_ref[hk] = alpha * l_ref[hk] + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[hk] = alpha * acc_ref[hk] + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[hk] = m_new
            return carry

        jax.lax.fori_loop(0, hkv, head, 0)

    def tile(s, carry):
        slot = advance(s)

        @pl.when(s < n_hist)
        def _():
            # History: every key below kv_len is before every query.
            def mask():
                if span_ref is None:
                    pos = s * sup + jax.lax.broadcasted_iota(
                        jnp.int32, (1, sup), 1)
                    return pos < kv_len
                pos = (hist_from(b, qb) + s) * sup \
                    + jax.lax.broadcasted_iota(jnp.int32, (1, sup), 1)
                return (pos < kv_len) & (block_positions() - pos < span)

            flash_block(lambda hk: (k_buf[slot, hk], v_buf[slot, hk]), mask)

        @pl.when(s >= n_hist)
        def _():
            # The chunk's key block c <= qb, masked as window_attention
            # masks it: key position <= query position, key index <
            # chunk_len (all true below the diagonal block).
            c = s - n_hist
            if span_ref is not None:
                c = c + chunk_from(qb)

            def mask():
                pos_q = block_positions()
                idx = c * tq + jax.lax.broadcasted_iota(
                    jnp.int32, (1, tq), 1)
                seen = (posk_ref[0, c] <= pos_q) & (idx < chunk_len)
                if span_ref is not None:
                    seen = seen & (pos_q - posk_ref[0, c] < span)
                return seen

            flash_block(
                lambda hk: (k_buf[slot, hk, pl.ds(0, tq), :],
                            v_buf[slot, hk, pl.ds(0, tq), :]),
                mask)

        return carry

    @pl.when(n_tiles > 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        jax.lax.fori_loop(0, n_tiles, tile, 0)

        def write(hk, carry):
            out = acc_ref[hk] / jnp.maximum(l_ref[hk], 1e-30)
            o_ref[0, hk, 0] = out.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, hkv, write, 0)

    @pl.when(n_tiles == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret")
)
def paged_flash_prefill(
    q: jax.Array,             # [B, T, H, Dh] chunk queries (post-rope)
    k: jax.Array,             # [B, T, Hkv, Dh] chunk keys (post-rope)
    v: jax.Array,             # [B, T, Hkv, Dh]
    positions: jax.Array,     # [B, T] int32 absolute position per token
    chunk_lens: jax.Array,    # [B] int32 valid tokens per row
    k_pool: jax.Array,        # [L, Hkv, num_slots, Dh]
    v_pool: jax.Array,
    block_tables: jax.Array,  # [B, Mb] int32
    kv_lens: jax.Array,       # [B] int32: the row's tokens in the pool
    layer_idx: jax.Array,     # [] or [1] int32
    *,
    block_size: int,
    scale: Optional[float] = None,
    interpret: bool = False,
    span: Optional[jax.Array] = None,   # [] int32: the layer's span
) -> jax.Array:
    """Causal attention of a prefill chunk over its rows' history in the
    paged pool (slots below ``kv_lens``, read in place) and over itself:
    [B, T, H, Dh] in q.dtype, equal to ``window_attention`` over the
    gathered history. Positions must not decrease along a row and must
    increase over its valid tokens (key blocks above the diagonal are
    skipped by index). Rows of a padded query block are zeros; block-table
    entries past a row's live blocks, and what they point at, are never
    read. See the section comment for the design and
    ``supports_pallas_prefill`` for the shapes.

    ``span`` (ops/attention.py): a query at position i sees the keys at
    ``i - span < j <= i`` only, and token i of a row must sit at position
    ``kv_lens[row] + i`` (the engine's chunks do). A query block reads the
    history from the superpage, and the chunk from the tile, that hold its
    first query's bound: what lies wholly behind is neither fetched nor
    scored. Absent (static), the program is the unbounded one."""
    b, t, h, dh = q.shape
    l_, hkv, num_slots, _ = k_pool.shape
    g = h // hkv
    if scale is None:
        scale = dh ** -0.5
    sup, tq = prefill_tiles(t, h, hkv, dh, k_pool.dtype.itemsize,
                            block_size)
    nq, m = t // tq, g * tq
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    # [B, T, H, Dh] -> [B, Hkv, NQ, G*TQ, Dh]: a KV head's query rows of
    # one block are one matmul operand; scaled as window_attention scales.
    qf = (q.astype(jnp.float32) * scale).astype(k_pool.dtype)
    qf = qf.reshape(b, nq, tq, hkv, g, dh).transpose(0, 3, 1, 4, 2, 5)
    qf = qf.reshape(b, hkv, nq, m, dh)
    kc = k.transpose(2, 0, 1, 3).astype(k_pool.dtype)     # [Hkv, B, T, Dh]
    vc = v.transpose(2, 0, 1, 3).astype(v_pool.dtype)
    positions = positions.astype(jnp.int32)

    kernel = functools.partial(
        _prefill_kernel, block_size=block_size, super_tokens=sup, tq=tq,
        q_per_kv=g,
    )
    kernel, bound = _prefetched_behind(
        kernel, 4, span_ref=_span_operand(span))
    q_block = pl.BlockSpec((1, hkv, 1, m, dh),
                           lambda i, j, *_: (i, 0, j, 0, 0),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, nq, m, dh), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(bound),
            grid=(b, nq),
            in_specs=[
                q_block,
                pl.BlockSpec((1, 1, tq, 1), lambda i, j, *_: (i, j, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, nq, 1, tq), lambda i, j, *_: (i, 0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),   # the chunk's K/V and
                pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in
                pl.BlockSpec(memory_space=pl.ANY),   # HBM: the kernel
                pl.BlockSpec(memory_space=pl.ANY),   # copies tiles itself
            ],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((NUM_BUFS, hkv, sup, dh), k_pool.dtype),
                pltpu.VMEM((NUM_BUFS, hkv, sup, dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv, m, 1), jnp.float32),
                pltpu.VMEM((hkv, m, 1), jnp.float32),
                pltpu.VMEM((hkv, m, dh), jnp.float32),
            ],
        ),
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM_BYTES,
        ),
        name="paged_flash_prefill",
        interpret=interpret,
    )(
        layer, block_tables, kv_lens.astype(jnp.int32),
        chunk_lens.astype(jnp.int32), *bound,
        qf, positions.reshape(b, nq, tq, 1), positions.reshape(b, nq, 1, tq),
        kc, vc, k_pool, v_pool,
    )
    out = out.reshape(b, hkv, nq, g, tq, dh).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(b, t, h, dh)


# ------------------------------------------------------ prefill, a packed row
# A PACKED prefill dispatch is ONE row of T tokens in which up to S
# sequences' chunks ("segments") lie end to end from token 0, each with its
# own history in the pool; only the row's end is padding. The K/V kernel
# above, with one thing changed: a program is no longer (row, query block)
# but (segment, query block), a PAIR, for every query block a segment has
# tokens in.
#
#   * The query blocks stay TQ tokens at static offsets of the row, and a
#     KV head's G x TQ query rows one resident block; the pairs of a block
#     follow one another in the grid (at most NQ + S - 1 pairs: a segment
#     boundary inside the row adds one), the block's flash state stays in
#     scratch across them, and the last pair of a block writes it out. A
#     block no segment reaches has one pair that reads nothing: zeros.
#   * A pair reads its SEGMENT's history superpages (the segment's block
#     table and kv_len) and then the row's key tiles from the tile that
#     holds the segment's first token up to the diagonal, through the same
#     tile sequence (``_tile_sequence``: a pair is a "row" with one block).
#   * A pair touches only the query rows of its segment, in SUB-BLOCKS of
#     ``packed_sub_block`` tokens (x G rows a head): what a tile costs is
#     the flash state's round trip over the rows it visits (the section
#     above), so a segment of 100 tokens must not pay for the 256 of its
#     block. A sub-block two segments share is visited by both pairs;
#     queries are masked by segment (row index within [start, end)), keys
#     by segment and causally by row index (inside a segment that is the
#     order of positions). ``_MASKED`` is finite: a query that meets only
#     masked keys first is corrected by ``alpha`` when its own arrive, and
#     every query has at least its own key; one no segment owns stays
#     finite garbage, which nothing reads.
#   * A segment that fills whole query blocks visits what its row visits in
#     the rectangle kernel above, tile for tile.
#   * The body serves latent rows too (the next section): what it asks of a
#     pool is how a query block lies in VMEM and what a page's streams are,
#     and the two classes below say that for K/V rows and for latent rows.
PACKED_SUB_ROWS = 256    # query rows (G x tokens) a sub-block keeps at least:
                         # 16 segments in a 2048-token row at 8 query heads
                         # a KV head took 630 / 500 / 438 / 359 us a layer
                         # in sub-blocks of 256 / 128 / 64 / 32 tokens on a
                         # v5e (PERF.md section 6, PR 46)


PACKED_HISTORY_ROWS = 512    # ... and a history tile's of a WHOLE block: one
                             # suffix behind 6000 tokens at 4 query heads a
                             # KV head took 426 / 380 / 417 us in pieces of
                             # 1024 / 512 / 256 rows (the rectangle: 410)


def packed_sub_block(tq: int, q_per_kv: int, itemsize: int) -> int:
    """Tokens a sub-block of a packed query block holds: ``tq`` halved
    while G x tokens stays ``PACKED_SUB_ROWS`` rows (the matmuls' M) and
    whole sublane tiles of the dtype."""
    sb = tq
    while sb % 2 == 0 and (sb // 2) % (32 // itemsize) == 0 \
            and q_per_kv * (sb // 2) >= PACKED_SUB_ROWS:
        sb //= 2
    return sb


def supports_packed_prefill(t: int, num_heads: int, num_kv_heads: int,
                            head_dim: int, itemsize: int,
                            block_size: int) -> bool:
    """What ``supports_pallas_prefill`` asks, and query blocks of whole
    sublane tiles (a sub-block is sliced out of the block's token axis)."""
    if not supports_pallas_prefill(t, num_heads, num_kv_heads, head_dim,
                                   itemsize, block_size):
        return False
    _, tq = prefill_tiles(t, num_heads, num_kv_heads, head_dim, itemsize,
                          block_size)
    return tq % (32 // itemsize) == 0


class _HeadMajorBlock:
    """A packed query block over K/V rows: q, the output and the flash
    state are a KV head's [G, TQ, .] each, the heads a loop, K and V a
    stream each. A sub-block is a slice of the token axis, its rows (head,
    token)."""

    def __init__(self, q_ref, o_ref, bufs, state):
        self.q_ref, self.o_ref, self.state = q_ref, o_ref, state
        self.k_buf, self.v_buf = bufs
        self.hkv, self.g, self.dh = (
            self.k_buf.shape[1], q_ref.shape[3], q_ref.shape[5])
        self.score_rows = self.g        # of one matmul, a token

    def rows(self, j, size):
        return pl.ds(pl.multiple_of(j * size, size), size)

    def tokens(self, size):
        # The token of each of a sub-block's rows, from its first.
        return jax.lax.broadcasted_iota(
            jnp.int32, (self.g, size, 1), 1).reshape(self.g * size, 1)

    def queries(self, hk, rows, size):
        return self.q_ref[0, hk, 0, :, rows, :].reshape(
            self.g * size, self.dh)

    def keys_values(self, slot, hk, width):
        return (self.k_buf[slot, hk, pl.ds(0, width), :],
                self.v_buf[slot, hk, pl.ds(0, width), :])

    def get(self, ref, hk, rows, size):
        return ref[hk, :, rows, :].reshape(self.g * size, ref.shape[-1])

    def put(self, ref, hk, rows, size, x):
        ref[hk, :, rows, :] = x.reshape(self.g, size, ref.shape[-1])

    def each_head(self, fn):
        jax.lax.fori_loop(0, self.hkv, fn, 0)

    def write(self):
        _, l_ref, acc_ref = self.state

        def write(hk, carry):
            out = acc_ref[hk] / jnp.maximum(l_ref[hk], 1e-30)
            self.o_ref[0, hk, 0] = out.astype(self.o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, self.hkv, write, 0)


class _TokenMajorBlock:
    """A packed query block over LATENT rows: every head attends the one
    row, so a sub-block's tokens x heads are ONE matmul operand, q and the
    output in their own layout [TQ, H, .] (a block's [TQ, H, W] is
    [TQ * H, W] with no data moved where the heads fill whole sublane
    tiles: no transpose crosses HBM), the flash state flat [TQ * H, .], rows
    (token, head); the one stream is keys AND values, the values the first
    lanes of the buffer."""

    def __init__(self, q_ref, o_ref, bufs, state):
        self.q_ref, self.o_ref, self.state = q_ref, o_ref, state
        self.kv_buf, = bufs
        self.h, self.dv = q_ref.shape[2], o_ref.shape[-1]
        self.score_rows = self.h        # of one matmul, a token

    def rows(self, j, size):
        # (the sub-block's tokens, its rows of the flat state)
        m = size * self.h
        return (pl.ds(pl.multiple_of(j * size, size), size),
                pl.ds(pl.multiple_of(j * m, m), m))

    def tokens(self, size):
        return jax.lax.broadcasted_iota(
            jnp.int32, (size, self.h, 1), 0).reshape(size * self.h, 1)

    def queries(self, hk, rows, size):
        return self.q_ref[0, rows[0], :, :].reshape(
            size * self.h, self.q_ref.shape[-1])

    def keys_values(self, slot, hk, width):
        keys = self.kv_buf[slot, 0, pl.ds(0, width), :]
        return keys, keys[:, :self.dv]

    def get(self, ref, hk, rows, size):
        return ref[rows[1], :]

    def put(self, ref, hk, rows, size, x):
        ref[rows[1], :] = x

    def each_head(self, fn):
        fn(0, 0)

    def write(self):
        _, l_ref, acc_ref = self.state
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        self.o_ref[0] = out.reshape(self.o_ref.shape[1:]).astype(
            self.o_ref.dtype)


def _packed_prefill_kernel(
    # scalar prefetch, one entry a PAIR (segment, query block)
    layer_ref,          # SMEM [1] int32
    block_tables_ref,   # SMEM [P, Mb] int32: the pair's segment's
    kv_lens_ref,        # SMEM [P] int32: its tokens in the pool
    live_ref,           # SMEM [P] int32: its tokens in the block; 0: no pair
    blk_ref,            # SMEM [P] int32: the pair's query block
    start_ref,          # SMEM [P] int32: the segment's first token in the row
    end_ref,            # SMEM [P] int32: one past its last
    # inputs
    q_ref,              # VMEM (pre-scaled) [1, Hkv, 1, G, TQ, Dh] over K/V
                        # rows, [1, TQ, H, W] over latent rows
    *refs,              # N streams (K and V, or the one of latent rows):
                        # HBM  [heads, 1, T, lanes] a stream: the row's keys
                        # HBM  [L, heads, num_slots, lanes] a stream: pools
                        # output: VMEM, as q_ref ([1, TQ, H, Dv] latent)
                        # scratch (outlives a program: buffers and flash
                        # state are handed on), a stream each:
                        # VMEM [NUM_BUFS, heads, super_tokens, lanes],
                        # DMA sems (NUM_BUFS,); then
                        # SMEM [1] int32: tiles the programs before fetched
                        # VMEM f32 running max, running sum [.., 1] and
                        # accumulator: [Hkv, G, TQ, .] or [TQ * H, .]
    block_size: int,
    super_tokens: int,
    tq: int,
    sub_block: int,
    tile_rows: int,     # keys a tile of the ROW holds: TQ, or whole TQs
    key_end_ref=None,   # SMEM [P] int32: one past the segment's last KEY in
                        # the row, where its queries run on behind it (a
                        # rectangle's padded queries); absent: ``end_ref``
    span_ref=None,      # SMEM [1] int32: the layer's span (absent: none)
):
    """ONE body for both pools: what differs between K/V rows and latent
    rows is how a query block lies in VMEM (``_HeadMajorBlock``,
    ``_TokenMajorBlock``) and how many streams a page is."""
    p, pairs = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    bs, sup, sb, tk = block_size, super_tokens, sub_block, tile_rows
    ns = (len(refs) - 5) // 4                     # streams
    chunk, pools, o_ref = refs[:ns], refs[ns:2 * ns], refs[2 * ns]
    bufs, sems = refs[2 * ns + 1:3 * ns + 1], refs[3 * ns + 1:4 * ns + 1]
    fetched_ref, m_ref, l_ref, acc_ref = refs[4 * ns + 1:]
    block = (_TokenMajorBlock if ns == 1 else _HeadMajorBlock)(
        q_ref, o_ref, bufs, (m_ref, l_ref, acc_ref))
    kv_len = kv_lens_ref[p]
    blk, start, end = blk_ref[p], start_ref[p], end_ref[p]
    fetch = _PageFetch(
        list(zip(pools, bufs, sems)), bs, block_size=bs,
        super_tokens=sup, layer=layer, block_tables_ref=block_tables_ref,
        kv_lens_ref=kv_lens_ref)

    def first_tile(pair):
        return start_ref[pair] // tk

    def last_tile(pair):
        # The tile that holds the pair's block (whole: TQ divides it).
        # Where a tile IS a block (K/V rows) this and ``keys_seen`` below
        # keep their shorter spelling: those programs are pinned by text.
        return blk_ref[pair] if tk == tq else blk_ref[pair] * tq // tk

    hist_from = None
    if span_ref is not None:
        # Under a span (a segment's token i sits at position kv_len + i) a
        # pair reads from the history superpage and from the row tile that
        # hold the first key its FIRST query sees.
        span = span_ref[0]

        def first_query(pair):
            return jnp.maximum(start_ref[pair], blk_ref[pair] * tq)

        def hist_from(pair, _):
            n = kv_lens_ref[pair]
            lo = jnp.maximum(
                n + first_query(pair) - start_ref[pair] - span + 1, 0)
            return jnp.where(lo < n, lo // sup, pl.cdiv(n, sup))

        def first_tile(pair):
            return jnp.maximum(start_ref[pair],
                               first_query(pair) - span + 1) // tk

    # A pair is a row of one block: its segment's history, then the row's
    # key tiles from the segment's first up to the diagonal. What holds
    # values is the last stream's buffers.
    n_hist, n_tiles, advance = _tile_sequence(
        fetch, chunk, live_ref, fetched_ref, bufs[-1],
        program=(p, 0), programs=(pairs, 1), tq=tq, tile_rows=tk,
        tiles=lambda pair, hist, _:
            hist + last_tile(pair) - first_tile(pair) + 1,
        chunk_at=lambda pair, _, c: (0, (first_tile(pair) + c) * tk),
        hist_from=hist_from)

    # The block's flash state: begun by its first pair, written out by its
    # last (the pairs past the row's last all name the last block and read
    # nothing, so the call's last program writes that block).
    @pl.when((p == 0) | (blk_ref[jnp.maximum(p - 1, 0)] != blk))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # The sub-blocks that hold the segment's queries of this block.
    base = blk * tq
    j_lo = (jnp.maximum(start, base) - base) // sb
    j_hi = pl.cdiv(jnp.minimum(end, base + tq) - base, sb)

    whole = (start <= base) & (end >= base + tq)

    def flash_tile(slot, widths, whole_sb, key_mask, keys_seen):
        # The keys of the tile in buffer ``slot`` against the segment's
        # query rows, a sub-block at a time and a head at a time:
        # ``_prefill_kernel``'s flash block over G x SB rows of the
        # state, and over no more of the tile's
        # keys than the sub-block can see (``keys_seen(j, size)``, rounded
        # up to one of the static ``widths``): a short history or the
        # first sub-blocks of the diagonal see a fraction of their tile.
        # A segment that has the whole block wastes nothing at a boundary
        # and takes it in sub-blocks of ``whole_sb`` tokens, wide enough
        # to cost what the block costs the kernel above.
        def sub(size, j, carry):
            rows = block.rows(j, size)
            idx_q = base + j * size + block.tokens(size)
            own = (idx_q >= start) & (idx_q < end)
            seen = keys_seen(j, size)

            def flash(width):
                mask = own & key_mask(idx_q, width)

                def head(hk, carry):
                    q = block.queries(hk, rows, size)        # [M, lanes]
                    k, v = block.keys_values(slot, hk, width)
                    scores = jax.lax.dot_general(
                        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )                                        # [M, keys]
                    scores = jnp.where(mask, scores, _MASKED)
                    m_prev = block.get(m_ref, hk, rows, size)
                    l_prev = block.get(l_ref, hk, rows, size)
                    acc_prev = block.get(acc_ref, hk, rows, size)
                    m_new = jnp.maximum(
                        m_prev, jnp.max(scores, axis=-1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    pr = jnp.exp(scores - m_new)
                    l_new = alpha * l_prev + jnp.sum(
                        pr, axis=-1, keepdims=True)
                    acc_new = alpha * acc_prev + jax.lax.dot_general(
                        pr.astype(v.dtype), v,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    block.put(l_ref, hk, rows, size, l_new)
                    block.put(acc_ref, hk, rows, size, acc_new)
                    block.put(m_ref, hk, rows, size, m_new)
                    return carry

                block.each_head(head)

            below = 0
            for width in widths:
                pl.when((seen > below) & (seen <= width))(
                    functools.partial(flash, width))
                below = width
            return carry

        if whole_sb == sb:
            jax.lax.fori_loop(j_lo, j_hi, functools.partial(sub, sb), 0)
            return

        @pl.when(whole)
        def _():
            jax.lax.fori_loop(
                0, tq // whole_sb, functools.partial(sub, whole_sb), 0)

        @pl.when(jnp.logical_not(whole))
        def _():
            jax.lax.fori_loop(j_lo, j_hi, functools.partial(sub, sb), 0)

    # The key counts a block is compiled for: a history superpage's live
    # keys up to 128, 256 or all of it; a row tile's in quarters (or
    # sub-blocks, where those are wider). A whole block takes a history
    # tile PACKED_HISTORY_ROWS query rows at a time and a row tile at once.
    hist_widths = [w for w in (LANES, 2 * LANES) if w < sup] + [sup]
    step = max(sb, tk // 4)
    row_widths = list(range(step, tk + 1, step))
    hist_sb = tq
    while hist_sb // 2 >= sb \
            and block.score_rows * hist_sb > PACKED_HISTORY_ROWS:
        hist_sb //= 2

    def tile(s, carry):
        slot = advance(s)

        @pl.when(s < n_hist)
        def _():
            # The segment's history: every key below kv_len is before
            # every query of the segment.
            at = s if span_ref is None else hist_from(p, 0) + s

            def mask(idx_q, width):
                pos = at * sup + jax.lax.broadcasted_iota(
                    jnp.int32, (1, width), 1)
                if span_ref is None:
                    return pos < kv_len
                return (pos < kv_len) & (kv_len + idx_q - start - pos < span)

            flash_tile(slot, hist_widths, hist_sb, mask,
                       lambda j, size: jnp.minimum(kv_len - at * sup, sup))

        @pl.when(s >= n_hist)
        def _():
            # Key tile c of the row: the segment's own keys, causally; on
            # the diagonal a sub-block sees the keys up to its own end.
            c = first_tile(p) + s - n_hist

            def mask(idx_q, width):
                idx_k = c * tk + jax.lax.broadcasted_iota(
                    jnp.int32, (1, width), 1)
                seen = (idx_k >= start) & (idx_k <= idx_q)
                if key_end_ref is not None:
                    seen = seen & (idx_k < key_end_ref[p])
                if span_ref is not None:
                    seen = seen & (idx_q - idx_k < span)
                return seen

            def keys_seen(j, size):
                if tk == tq:
                    return jnp.where(c < blk, tq, (j + 1) * size)
                return jnp.minimum(base + (j + 1) * size - c * tk, tk)

            flash_tile(slot, row_widths, tq, mask, keys_seen)

        return carry

    jax.lax.fori_loop(0, n_tiles, tile, 0)

    @pl.when((p + 1 == pairs) | (blk_ref[jnp.minimum(p + 1, pairs - 1)] != blk))
    def _():
        block.write()


def packed_pairs(seg_lens: jax.Array, nq: int, tq: int):
    """The (segment, query block) pairs of a packed row, block by block,
    as arrays of NQ + S - 1 entries: (segment, block, the segment's first
    token in the row, one past its last, its tokens in the block). A block
    no segment reaches has one pair of 0 tokens; the entries past the last
    block's name that block, 0 tokens. ``seg_lens`` [S]: the segments lie
    end to end from token 0, the live ones first."""
    s = seg_lens.shape[0]
    ends = jnp.cumsum(seg_lens)
    starts = ends - seg_lens
    live = seg_lens > 0
    lo_tok = jnp.arange(nq, dtype=jnp.int32) * tq                  # [NQ]
    # Of block j: its first segment (those wholly before it come first),
    # and one past its last.
    lo = jnp.sum(live[None, :] & (ends[None, :] <= lo_tok[:, None]), axis=1)
    hi = jnp.sum(live[None, :] & (starts[None, :] < lo_tok[:, None] + tq),
                 axis=1)
    n = jnp.maximum(hi - lo, 0)
    count = jnp.maximum(n, 1)
    first = jnp.cumsum(count) - count                              # [NQ]
    p = jnp.arange(nq + s - 1, dtype=jnp.int32)
    blk = jnp.clip(jnp.sum(first[None, :] <= p[:, None], axis=1) - 1,
                   0, nq - 1).astype(jnp.int32)
    i = p - first[blk]
    has = i < n[blk]
    seg = jnp.where(has, jnp.minimum(lo[blk] + i, s - 1), 0).astype(jnp.int32)
    start = jnp.where(has, starts[seg], 0).astype(jnp.int32)
    end = jnp.where(has, ends[seg], 0).astype(jnp.int32)
    tokens = jnp.minimum(end, (blk + 1) * tq) - jnp.maximum(start, blk * tq)
    return seg, blk, start, end, jnp.where(has, tokens, 0).astype(jnp.int32)


def rectangle_pairs(chunk_lens: jax.Array, t: int, tq: int):
    """The pairs of a RECTANGLE laid as a row, in closed form: row r of
    ``chunk_lens`` [rows] is the segment that begins at token ``r * t``
    (``t`` whole query blocks), so block p of the row is segment
    ``p // (t // tq)``'s alone and the pairs are the row's blocks, one each
    (a block behind its row's chunk: 0 tokens). A segment's QUERIES run on
    to the end of its last live block: a live block's padded queries see
    their row's valid keys, as ``window_attention``'s do. So beside
    ``packed_pairs``' five, the last: one past the pair's segment's last
    KEY. Rows that hold nothing may stand anywhere."""
    per = t // tq                                   # blocks a row
    blk = jnp.arange(chunk_lens.shape[0] * per, dtype=jnp.int32)
    seg = blk // per
    lens, start = chunk_lens[seg], seg * t
    live = lens > (blk % per) * tq
    end = start + -(-lens // tq) * tq
    return seg, blk, start, end, jnp.where(live, tq, 0), start + lens


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "sub_block"),
)
def paged_flash_prefill_packed(
    q: jax.Array,             # [1, T, H, Dh] the row's queries (post-rope)
    k: jax.Array,             # [1, T, Hkv, Dh] the row's keys (post-rope)
    v: jax.Array,             # [1, T, Hkv, Dh]
    seg_lens: jax.Array,      # [S] int32 tokens of each segment of the row
    k_pool: jax.Array,        # [L, Hkv, num_slots, Dh]
    v_pool: jax.Array,
    block_tables: jax.Array,  # [S, Mb] int32, a segment each
    kv_lens: jax.Array,       # [S] int32: the segment's tokens in the pool
    layer_idx: jax.Array,     # [] or [1] int32
    *,
    block_size: int,
    scale: Optional[float] = None,
    interpret: bool = False,
    sub_block: Optional[int] = None,
    span: Optional[jax.Array] = None,   # [] int32: the layer's span
) -> jax.Array:
    """``paged_flash_prefill`` of a PACKED row: segment i is the row's
    tokens [sum(seg_lens[:i]), sum(seg_lens[:i + 1])), a chunk of a
    sequence whose history is the pool's slots below ``kv_lens[i]`` by
    ``block_tables[i]``; each attends its history and itself causally and
    nothing of its neighbours: [1, T, H, Dh] in q.dtype, a segment's
    tokens equal to ``paged_flash_prefill`` of the segment as a row of its
    own (one segment that fills the row: bit for bit). Live segments come
    first; tokens past the last are padding, and what the kernel writes
    there is finite and means nothing. See the section comment;
    ``supports_packed_prefill`` for the shapes; ``sub_block`` (tokens)
    overrides ``packed_sub_block`` for tests and sweeps. ``span``: as
    ``paged_flash_prefill``'s, a segment's token i at position
    ``kv_lens[segment] + i``; a (segment, query block) pair reads from the
    history superpage and the row tile that hold its first query's bound."""
    _, t, h, dh = q.shape
    hkv = k_pool.shape[1]
    g = h // hkv
    if scale is None:
        scale = dh ** -0.5
    itemsize = k_pool.dtype.itemsize
    sup, tq = prefill_tiles(t, h, hkv, dh, itemsize, block_size)
    sb = sub_block or packed_sub_block(tq, g, itemsize)
    nq = t // tq
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    seg, blk, start, end, tokens = packed_pairs(
        seg_lens.astype(jnp.int32), nq, tq)
    pairs = seg.shape[0]
    # [1, T, H, Dh] -> [1, Hkv, NQ, G, TQ, Dh]: as ``paged_flash_prefill``
    # lays a block out, its G x TQ rows not yet merged (a sub-block is a
    # slice of the token axis).
    qf = (q.astype(jnp.float32) * scale).astype(k_pool.dtype)
    qf = qf.reshape(1, nq, tq, hkv, g, dh).transpose(0, 3, 1, 4, 2, 5)
    kc = k.transpose(2, 0, 1, 3).astype(k_pool.dtype)     # [Hkv, 1, T, Dh]
    vc = v.transpose(2, 0, 1, 3).astype(v_pool.dtype)

    kernel = functools.partial(
        _packed_prefill_kernel, block_size=block_size, super_tokens=sup,
        tq=tq, sub_block=sb, tile_rows=tq,
    )
    kernel, bound = _prefetched_behind(
        kernel, 7, span_ref=_span_operand(span))
    # A pair's blocks of q and the output are its query block's: resident
    # while the block's pairs follow one another.
    q_block = pl.BlockSpec(
        (1, hkv, 1, g, tq, dh),
        lambda i, layer, bt, lens, live, blk, *_: (0, 0, blk[i], 0, 0, 0),
        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, hkv, nq, g, tq, dh), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7 + len(bound),
            grid=(pairs,),
            in_specs=[
                q_block,
                pl.BlockSpec(memory_space=pl.ANY),   # the row's K/V and
                pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in
                pl.BlockSpec(memory_space=pl.ANY),   # HBM: the kernel
                pl.BlockSpec(memory_space=pl.ANY),   # copies tiles itself
            ],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((NUM_BUFS, hkv, sup, dh), k_pool.dtype),
                pltpu.VMEM((NUM_BUFS, hkv, sup, dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv, g, tq, 1), jnp.float32),
                pltpu.VMEM((hkv, g, tq, 1), jnp.float32),
                pltpu.VMEM((hkv, g, tq, dh), jnp.float32),
            ],
        ),
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PREFILL_VMEM_BYTES,
        ),
        name="paged_flash_prefill_packed",
        interpret=interpret,
    )(
        layer, block_tables[seg], kv_lens.astype(jnp.int32)[seg] * (tokens > 0),
        tokens, blk, start, end, *bound,
        qf, kc, vc, k_pool, v_pool,
    )
    out = out.transpose(0, 2, 4, 1, 3, 5)         # [1, NQ, TQ, Hkv, G, Dh]
    return out.reshape(1, t, h, dh)


# ------------------------------------------ prefill, a row of latent rows
# The row over a LATENT pool (see "latent rows" above): the body above with
# ONE pool and one stream (a tile is keys AND values, cleared whole; the
# values the first ``value_dim`` lanes of the buffer, a slice in VMEM, free,
# as in ``_latent_decode_kernel``) and the query block token-major
# (``_TokenMajorBlock``). Absorbed latent attention is multi-query
# attention, so what differs follows from there being one KV head of a wide
# row: a query block holds TQ tokens for ALL heads as ONE matmul operand, M
# = H x TQ rows (``prefill_tiles`` at Hkv 1 and the row's width: 32 queries
# of 32 heads at 640 lanes), too few keys for a tile of the row (the flash
# state's round trip, M x value_dim float32, for 32 keys), so a row tile is
# ``packed_latent_tile`` keys, whole query blocks of them: a pair reads from
# the tile that holds its segment's first token to the one that holds its
# block, and a sub-block the keys up to its own end. A RECTANGLE of latent
# rows (``paged_flash_prefill_latent``) is the row ``[1, rows * T]`` whose
# segment r begins at ``r * T`` (``stride``): the tiles are chosen for T, so
# a segment owns whole query blocks (``rectangle_pairs``).
PACKED_LATENT_TILE = 256     # keys a row tile holds, a whole TQ where that
                             # is wider: 8 segments cut at random in a
                             # 1024-token row behind 124-271 tokens took
                             # 533 / 467 us a layer at 128 / 256 keys on a
                             # v5e, 8 x 128 behind 64 took 387 / 388 / 423
                             # at 128 / 256 / 512 (PERF.md section 6, PR 48)


def packed_latent_tile(t: int, tq: int) -> int:
    """Keys a row tile of the packed latent kernel holds."""
    return max(tq, min(t, PACKED_LATENT_TILE))


def supports_latent_prefill(t: int, num_heads: int, width: int,
                            value_dim: int, itemsize: int,
                            block_size: int) -> bool:
    """Whether the packed body tiles latent rows at ``t`` tokens (a packed
    row's length, a rectangle's T): what ``supports_packed_prefill`` asks at
    one KV head of ``width`` lanes, values that are whole lanes of the row,
    heads that fill whole sublane tiles of the dtype (a block's [TQ, H, W]
    is then [TQ * H, W] as it lies) and ``t`` tokens of whole key tiles of
    whole query blocks and, past a superpage, of whole superpages (the
    lengths it is warmed at)."""
    if value_dim % LANES or value_dim > width \
            or num_heads % (32 // itemsize) \
            or not supports_packed_prefill(t, num_heads, 1, width, itemsize,
                                           block_size):
        return False
    sup, tq = prefill_tiles(t, num_heads, 1, width, itemsize, block_size)
    tk = packed_latent_tile(t, tq)
    return tk % tq == 0 and t % tk == 0 and t % min(t, sup) == 0


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "value_dim", "scale", "interpret",
                     "sub_block", "key_tile", "stride"),
)
def paged_flash_prefill_packed_latent(
    q: jax.Array,             # [1, T, H, W] absorbed queries, zeros past the key
    rows: jax.Array,          # [1, T, 1, W] the row's latent rows
    seg_lens: jax.Array,      # [S] int32 tokens of each segment of the row
    kv_pool: jax.Array,       # [L, 1, num_slots, W] latent rows
    block_tables: jax.Array,  # [S, Mb] int32, a segment each
    kv_lens: jax.Array,       # [S] int32: the segment's tokens in the pool
    layer_idx: jax.Array,     # [] or [1] int32
    *,
    block_size: int,
    value_dim: int,
    scale: float,
    interpret: bool = False,
    sub_block: Optional[int] = None,
    key_tile: Optional[int] = None,
    stride: Optional[int] = None,
) -> jax.Array:
    """``paged_flash_prefill_packed`` over a latent pool: segment i is the
    row's tokens [sum(seg_lens[:i]), sum(seg_lens[:i + 1])), its history
    the pool's slots below ``kv_lens[i]`` by ``block_tables[i]``; every head
    attends the segment's history and the segment causally, keys the whole
    row, values its first ``value_dim`` lanes: [1, T, H, value_dim] in
    q.dtype, a segment's tokens equal to ``window_attention`` of the
    segment as a row of its own over its gathered rows. Live segments come
    first; tokens past the last are padding (finite, meaning nothing; a
    query block no segment reaches is zeros). The pool must be finite
    wherever a live segment's pages reach, padding lanes included. See the
    section comments and ``supports_latent_prefill``; ``sub_block``
    (tokens) and ``key_tile`` (keys) override ``packed_sub_block`` and
    ``packed_latent_tile`` for tests and sweeps.

    ``stride`` (static; ``paged_flash_prefill_latent``'s way in): the row is
    a RECTANGLE of T / stride rows laid end to end, segment i beginning at
    ``i * stride`` whatever ``seg_lens`` hold (``rectangle_pairs``), and
    the tiles are chosen for ``stride`` tokens."""
    _, t, h, w = q.shape
    itemsize = kv_pool.dtype.itemsize
    sup, tq = prefill_tiles(stride or t, h, 1, w, itemsize, block_size)
    sb = sub_block or packed_sub_block(tq, h, itemsize)
    tk = key_tile or packed_latent_tile(stride or t, tq)
    nq, m = t // tq, h * tq
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    if stride is None:
        seg, blk, start, end, tokens = packed_pairs(
            seg_lens.astype(jnp.int32), nq, tq)
        key_end = None
    else:
        seg, blk, start, end, tokens, key_end = rectangle_pairs(
            seg_lens.astype(jnp.int32), stride, tq)
    # Scaled as window_attention scales.
    qf = (q.astype(jnp.float32) * scale).astype(kv_pool.dtype)
    chunk = rows.transpose(2, 0, 1, 3).astype(kv_pool.dtype)  # [1, 1, T, W]

    kernel = functools.partial(
        _packed_prefill_kernel, block_size=block_size, super_tokens=sup,
        tq=tq, sub_block=sb, tile_rows=tk,
    )
    kernel, bound = _prefetched_behind(kernel, 7, key_end_ref=key_end)

    def block(lanes):
        # A pair's blocks of q and the output are its query block's.
        return pl.BlockSpec(
            (1, tq, h, lanes),
            lambda i, layer, bt, lens, live, blk, *_: (0, blk[i], 0, 0),
            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, t, h, value_dim), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7 + len(bound),
            grid=(seg.shape[0],),
            in_specs=[
                block(w),
                pl.BlockSpec(memory_space=pl.ANY),   # the row's rows and
                pl.BlockSpec(memory_space=pl.ANY),   # the pool stay in HBM
            ],
            out_specs=block(value_dim),
            scratch_shapes=[
                pltpu.VMEM((NUM_BUFS, 1, sup, w), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((NUM_BUFS,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((m, 1), jnp.float32),
                pltpu.VMEM((m, 1), jnp.float32),
                pltpu.VMEM((m, value_dim), jnp.float32),
            ],
        ),
        # Programs run in order: each hands its buffers to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PREFILL_VMEM_BYTES,
        ),
        name="paged_flash_prefill_packed_latent",
        interpret=interpret,
    )(
        layer, block_tables[seg], kv_lens.astype(jnp.int32)[seg] * (tokens > 0),
        tokens, blk, start, end, *bound,
        qf, chunk, kv_pool,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "value_dim", "scale", "interpret"),
)
def paged_flash_prefill_latent(
    q: jax.Array,             # [B, T, H, W] absorbed queries, zeros past the key
    rows: jax.Array,          # [B, T, 1, W] the chunk's latent rows
    chunk_lens: jax.Array,    # [B] int32 valid tokens per row
    kv_pool: jax.Array,       # [L, 1, num_slots, W] latent rows
    block_tables: jax.Array,  # [B, Mb] int32
    kv_lens: jax.Array,       # [B] int32: the row's tokens in the pool
    layer_idx: jax.Array,     # [] or [1] int32
    *,
    block_size: int,
    value_dim: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """``paged_flash_prefill`` over a latent pool, with no kernel of its
    own: the rectangle as the row [1, B * T] of
    ``paged_flash_prefill_packed_latent``, segment b at ``b * T`` (a
    reshape: nothing is copied), token i of a row at position
    ``kv_lens[row] + i``: [B, T, H, value_dim] in q.dtype, equal to
    ``window_attention`` over the gathered rows. A live query block's padded
    queries see their row's valid keys; a query block that is all padding
    is zeros and fetches nothing."""
    b, t, h, w = q.shape
    out = paged_flash_prefill_packed_latent(
        q.reshape(1, b * t, h, w), rows.reshape(1, b * t, 1, w), chunk_lens,
        kv_pool, block_tables, kv_lens, layer_idx, block_size=block_size,
        value_dim=value_dim, scale=scale, interpret=interpret, stride=t)
    return out.reshape(b, t, h, value_dim)
