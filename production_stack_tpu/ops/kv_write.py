"""In-place writes into the donated KV pools.

Every pool of the engine — payload ``[L, Hkv, num_slots, Dh]``, int8 scale
sidecar ``[L, Hkv, num_slots]``, speculative draft rings
``[Ld, Hd, slots, R, Dd]``, the persistent decode window — is indexed on
AXIS 2 and written by rows that arrive with the leading axes whole. The
obvious form, ``pool.at[:, :, idx].set(new)``, is a scatter on a middle
axis, and the TPU compiler runs such a scatter in a layout of its own
(slots major, heads minor): it copies the WHOLE pool into that layout, scatters,
and copies the whole pool back (PERF.md §6, PR 25: two pool-sized copies a
pool a dispatch, 23 ms of a v5e's time at a 4.8 GB pool, and a pool-sized
temporary). A ``dynamic_update_slice`` keeps the operand's layout, and XLA
updates a donated, loop-carried buffer with it in place.

So everything here is one loop of ``dynamic_update_slice``s of whole slabs
``[A, B, width, ...]`` along axis 2 (``write_slabs``). Tokens reach the
paged pools as per-row runs of consecutive positions, cut at block
boundaries into block-wide slabs; a block the run only partly covers is
read, merged and written back (``write_token_runs``), so no token-granular
write (256 B rows: ~2 GB/s on a v5e, ops/attention.py:gather_window) is
ever issued. The helpers adapt to nothing but shapes.
"""

import re
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

_HLO_DTYPE = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
              "int8": "s8", "int32": "s32"}
_COPY = re.compile(r"= (\w+\[[\d,]*\])\S* copy(?:-start)?\(")


def pool_copies(hlo_text: str, pools) -> List[str]:
    """The ``copy`` operations of a compiled program (``as_text()``) whose
    result has the shape and dtype of one of ``pools`` (arrays or
    ShapeDtypeStructs): what an in-place program has none of."""
    shapes = {
        "%s[%s]" % (_HLO_DTYPE[jnp.dtype(x.dtype).name],
                    ",".join(map(str, x.shape)))
        for x in pools
    }
    return [m.group(0) for m in _COPY.finditer(hlo_text)
            if m.group(1) in shapes]


def write_slabs(
    pools: Sequence[jax.Array],     # each [A, B, S, *W]
    srcs: Sequence[jax.Array],      # each [A, B, R, T, *W], paired with pools
    dst_start: jax.Array,           # [n] int32 — slab start on the pool's axis 2
    src_row: jax.Array,             # [n] int32 — index on the source's axis 2
    src_start: jax.Array,           # [n] int32 — slab start on the source's axis 3
    width: int,
    keep: Optional[jax.Array] = None,   # [n, width] bool; None = every entry
) -> Tuple[jax.Array, ...]:
    """For i = 0..n-1 IN ORDER, for every (pool, src) pair:

        pool[:, :, dst_start[i]:+width] = src[:, :, src_row[i], src_start[i]:+width]

    Entries whose ``keep`` is False retain the pool's content (the slab is
    read, merged, written back: a slab with nothing kept rewrites itself).
    Starts must leave the slab inside its array (``dynamic_slice`` would
    shift it silently). Sequential, so slabs may repeat a destination.
    """
    pools, srcs = tuple(pools), tuple(srcs)

    def body(i, pools):
        out = []
        for pool, src in zip(pools, srcs):
            lead, tail = pool.shape[:2], pool.shape[3:]
            zeros = (0,) * len(tail)
            slab = jax.lax.dynamic_slice(
                src, (0, 0, src_row[i], src_start[i], *zeros),
                (*lead, 1, width, *tail),
            )[:, :, 0].astype(pool.dtype)
            at = (0, 0, dst_start[i], *zeros)
            if keep is not None:
                old = jax.lax.dynamic_slice(pool, at, (*lead, width, *tail))
                mask = keep[i].reshape((1, 1, width) + (1,) * len(tail))
                slab = jnp.where(mask, slab, old)
            out.append(jax.lax.dynamic_update_slice(pool, slab, at))
        return tuple(out)

    return jax.lax.fori_loop(0, dst_start.shape[0], body, pools)


def read_state_rows(
    pools: Sequence[jax.Array],     # each [S, *W]: per-sequence state
    slots: jax.Array,               # [b] int32 — slot of each row
) -> Tuple[jax.Array, ...]:
    """``pool[slots]`` of every pool, [b, *W] each, as one
    ``dynamic_slice`` a row: whole contiguous slabs, where a gather of
    slabs this wide is taken apart lane block by lane block over the whole
    pool (seen in the program compiled for a v5e)."""
    pools = tuple(pools)

    def body(i, rows):
        out = []
        for pool, buf in zip(pools, rows):
            zeros = (0,) * (pool.ndim - 1)
            slab = jax.lax.dynamic_slice(
                pool, (slots[i], *zeros), (1, *pool.shape[1:]))
            out.append(jax.lax.dynamic_update_slice(buf, slab, (i, *zeros)))
        return tuple(out)

    return jax.lax.fori_loop(
        0, slots.shape[0], body,
        tuple(jnp.zeros((slots.shape[0], *p.shape[1:]), p.dtype)
              for p in pools))


def write_state_rows(
    pools: Sequence[jax.Array],     # each [S, *W]: per-sequence state
    rows: Sequence[jax.Array],      # each [b, *W], paired with pools
    slots: jax.Array,               # [b] int32 — slot of each row
) -> Tuple[jax.Array, ...]:
    """For i = 0..b-1 IN ORDER, for every (pool, rows) pair:

        pool[slots[i]] = rows[i]

    one ``dynamic_update_slice`` a row, on the pools' MAJOR axis: a row's
    state (the recurrent state of engine/runner.py, every layer of it) is
    one contiguous slab and whole, so there is nothing to merge.
    Sequential, so padded rows may all name the scratch slot."""
    pools, rows = tuple(pools), tuple(rows)

    def body(i, pools):
        out = []
        for pool, new in zip(pools, rows):
            zeros = (0,) * (pool.ndim - 1)
            slab = jax.lax.dynamic_slice(
                new, (i, *zeros), (1, *new.shape[1:]))
            out.append(jax.lax.dynamic_update_slice(
                pool, slab.astype(pool.dtype), (slots[i], *zeros)))
        return tuple(out)

    return jax.lax.fori_loop(0, slots.shape[0], body, pools)


def write_token_runs(
    pools: Sequence[jax.Array],     # each [L, Hkv, num_slots, *W]
    news: Sequence[jax.Array],      # each [L, Hkv, b, T, *W], paired with pools
    block_tables: jax.Array,        # [b, Mb] int32 — logical -> physical block
    start: jax.Array,               # [b] int32 — position of each row's token 0
    length: jax.Array,              # [b] int32 — tokens of the row that count
    block_size: int,
    source_start: Optional[jax.Array] = None,   # [b] int32 — see below
) -> Tuple[jax.Array, ...]:
    """Write row i's tokens j < length[i] to the slots of positions
    start[i] + j (slot = block_tables[i, pos // bs] * bs + pos % bs).
    Tokens at or beyond ``length`` and positions beyond the block table
    write nothing: the reserved null block 0 takes no garbage either.

    ``source_start`` given: ``news`` holds ONE row (``[L, Hkv, 1, T, *W]``:
    a packed prefill's row of segments) and run i's tokens are that row's
    from ``source_start[i]`` on; the runs fit the row together
    (``sum(length) <= T``).
    """
    if source_start is not None:
        return _write_packed_runs(pools, news, block_tables, start, length,
                                  block_size, source_start)
    bs = block_size
    b, t = news[0].shape[2:4]
    mb = block_tables.shape[1]
    nblk = (t + bs - 2) // bs + 1        # blocks a run of t tokens can touch
    c = jnp.arange(nblk, dtype=jnp.int32)
    off = start % bs
    lb = start[:, None] // bs + c[None, :]                        # [b, nblk]
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(lb, 0, mb - 1), axis=1
    )
    # Token index (within the run) of entry o of block c: negative before
    # the run's first token, >= length past its last.
    j = (c[None, :, None] * bs
         + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
         - off[:, None, None])                                    # [b, nblk, bs]
    keep = (j >= 0) & (j < length[:, None, None]) & (lb < mb)[:, :, None]
    # bs entries in front and at least bs behind, so every slab the loop
    # cuts lies inside the padded run.
    pad = (bs, (nblk + 1) * bs - t)
    padded = [
        jnp.pad(x, ((0, 0), (0, 0), (0, 0), pad) + ((0, 0),) * (x.ndim - 4))
        for x in news
    ]
    return write_slabs(
        pools, padded,
        dst_start=(phys * bs).reshape(-1),
        src_row=jnp.repeat(jnp.arange(b, dtype=jnp.int32), nblk),
        src_start=(bs + c[None, :] * bs - off[:, None]).reshape(-1),
        width=bs,
        keep=keep.reshape(-1, bs),
    )


def _write_packed_runs(pools, news, block_tables, start, length, block_size,
                       source_start):
    """``write_token_runs`` out of one packed row: the slabs are as many
    as T tokens in ``b`` runs can touch (a run of n tokens touches at most
    n // bs + 2 blocks), not ``b`` times a whole row's; slab n of the
    dispatch is block c of the run whose blocks it falls among."""
    bs = block_size
    t = news[0].shape[3]
    b, mb = block_tables.shape
    off = start % bs
    nblk = jnp.where(length > 0, (off + length - 1) // bs + 1, 0)     # [b]
    first = jnp.cumsum(nblk) - nblk
    n = jnp.arange(t // bs + 2 * b, dtype=jnp.int32)
    run = jnp.clip(jnp.sum(first[None, :] <= n[:, None], axis=1) - 1,
                   0, b - 1).astype(jnp.int32)
    c = n - first[run]       # past the last run's blocks: out of the run
    lb = start[run] // bs + c
    phys = block_tables[run, jnp.clip(lb, 0, mb - 1)]
    # Token index (within the run) of entry o of block c, as above.
    j = (c[:, None] * bs + jnp.arange(bs, dtype=jnp.int32)[None, :]
         - off[run][:, None])                                     # [n, bs]
    keep = (j >= 0) & (j < length[run][:, None]) & (lb < mb)[:, None]
    # bs entries in front and a block behind the row's last whole one, so
    # every slab that keeps a token lies inside the padded row; one that
    # keeps none is cut at the row's start.
    pad = (bs, bs + (-t) % bs)
    padded = [
        jnp.pad(x, ((0, 0), (0, 0), (0, 0), pad) + ((0, 0),) * (x.ndim - 4))
        for x in news
    ]
    return write_slabs(
        pools, padded,
        dst_start=phys * bs,
        src_row=jnp.zeros_like(run),
        src_start=jnp.where(
            keep.any(axis=1),
            bs + c * bs - off[run] + source_start[run], 0),
        width=bs,
        keep=keep,
    )
