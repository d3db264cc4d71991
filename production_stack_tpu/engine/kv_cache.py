"""Paged KV cache block manager with hash-based prefix caching.

This is host-side bookkeeping for the device-side KV slot pools
([L, num_blocks*block_size, Hkv, Dh] jax arrays owned by the ModelRunner).
It replaces the paged-KV + prefix-cache machinery of the reference's external
vLLM images, and emits the counters the reference router's scraper contract
requires (reference src/vllm_router/stats/engine_stats.py:128-155:
vllm:gpu_prefix_cache_hits_total / queries_total / gpu_cache_usage_perc).

Design:
  * Block 0 is the reserved null block (padding writes land there).
  * Full blocks are content-addressed: hash chain H(prev, tokens) -> block id.
  * Freed blocks that carry a hash go into an evictable LRU ("cached-free");
    they are resurrected on prefix hit or reclaimed (LRU) when the free list
    runs dry — KV stays warm across requests exactly like vLLM's prefix cache.
  * Copy-on-write is avoided by construction: shared (ref_count > 1 or cached)
    blocks are always FULL; writes only ever target a sequence's private tail
    block.
  * A model that declares recurrent state (models/config.py:CacheSpecs) has a
    second thing to hand out: STATE SLOTS, one per sequence, of the runner's
    state pools (slot 0 is the scratch slot of padded rows and is never
    handed out). The scheduler takes a sequence's blocks and its slot
    together and gives both back together. For such a model the prefix index
    still REGISTERS full blocks but a lookup SERVES no hit: a hit of p tokens
    needs the state after exactly p tokens, and nothing keeps one yet
    (ROADMAP R6). ``prefix_hits_unserved_total`` counts the tokens a
    K/V-only model would have been spared.
"""

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


def _block_hash(prev: bytes, tokens: Sequence[int]) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(prev)
    h.update(b"|")
    h.update(",".join(map(str, tokens)).encode())
    return h.digest()


class BlockPoolManager:
    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True,
                 num_state_slots: int = 0):
        assert num_blocks >= 2, "need at least null block + one usable block"
        self.num_blocks = num_blocks
        # State slots 1..num_state_slots (0 = none: a K/V-only model).
        self.num_state_slots = num_state_slots
        self._free_state_slots: List[int] = list(range(num_state_slots, 0, -1))
        self.state_slot_allocs_total = 0
        self.state_slot_waits_total = 0
        self.prefix_hits_unserved_total = 0
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        # Block 0 reserved as null.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        # content hash -> block id (full blocks only)
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_to_hash: Dict[int, bytes] = {}
        # content hash -> parent hash in its chain (the prev_hash it was
        # registered under; the hash seed for chain roots). The offload
        # spiller reads it to carry chain links into the shared tier, and
        # prefix_digest() walks it to publish chain structure.
        self._hash_parent: Dict[bytes, bytes] = {}
        # evictable: blocks with ref 0 still holding cached content (LRU order)
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        # blocks queued for offload spill: excluded from eviction until the
        # device->host read completes (production_stack_tpu/kv_offload/manager.py)
        self._spill_pinned: set = set()
        # prefix-cache counters (token granularity, monotonic)
        self.prefix_queries_total = 0
        self.prefix_hits_total = 0

    # ------------------------------------------------------------- accounting
    @property
    def num_free_blocks(self) -> int:
        # Spill-pinned evictable blocks are NOT reclaimable (_pop_free_block
        # skips them), so they must not be counted either — otherwise
        # can_allocate() overpromises and allocate_blocks() comes up short
        # when the free list is empty and every evictable block is pinned.
        pinned_evictable = sum(
            1 for b in self._spill_pinned if b in self._evictable
        )
        return len(self._free) + len(self._evictable) - pinned_evictable

    @property
    def num_used_blocks(self) -> int:
        return (self.num_blocks - 1) - self.num_free_blocks

    def usage(self) -> float:
        usable = self.num_blocks - 1
        return self.num_used_blocks / usable if usable else 0.0

    # ------------------------------------------------------------- allocation
    def _pop_free_block(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        # Reclaim the least-recently-used cached block, skipping any pinned
        # for an in-flight offload spill.
        for blk in self._evictable:
            if blk in self._spill_pinned:
                continue
            del self._evictable[blk]
            h = self._block_to_hash.pop(blk, None)
            if h is not None:
                self._hash_to_block.pop(h, None)
                self._hash_parent.pop(h, None)
            return blk
        return None

    # ---------------------------------------------------------- offload hooks
    def pin_for_spill(self, blk: int) -> None:
        self._spill_pinned.add(blk)

    def unpin_for_spill(self, blk: int) -> None:
        self._spill_pinned.discard(blk)

    def hash_of_block(self, blk: int) -> Optional[bytes]:
        return self._block_to_hash.get(blk)

    def contains_hash(self, h: bytes) -> bool:
        """Is this content hash resident in the device prefix index?"""
        return h in self._hash_to_block

    def parent_hash(self, h: bytes) -> Optional[bytes]:
        """Parent hash in ``h``'s chain (the seed for chain roots); None if
        ``h`` is no longer registered."""
        return self._hash_parent.get(h)

    # ----------------------------------------------------------- prefix index
    @property
    def prefix_index_size(self) -> int:
        """Content-addressed blocks currently resident (device prefix
        cache) — the pstpu:prefix_index_size gauge."""
        return len(self._hash_to_block)

    def prefix_digest(self, max_entries: int = 8192) -> Tuple[List[str], bool]:
        """Compact digest of the device-resident prefix index: truncated
        hex (16 chars = 8 bytes) of every content-addressed block hash,
        newest chains implicitly protected by the cap being far above real
        residency. Returns (entries, truncated). The router's cross-engine
        prefix index (docs/KV_ECONOMY.md) is built from these digests; the
        router hashes an incoming prompt with the engine's exact chain
        scheme and takes the longest contiguous run present here."""
        entries = []
        for h in self._hash_to_block:
            entries.append(h.hex()[:16])
            if len(entries) >= max_entries:
                return entries, True
        return entries, False

    # ------------------------------------------------------------ state slots
    @property
    def state_slots_in_use(self) -> int:
        return self.num_state_slots - len(self._free_state_slots)

    def allocate_state_slot(self) -> int:
        """A free state slot, or 0 where none is (counted as a wait: the
        caller puts the admission off). 0 too for a K/V-only model, whose
        sequences need none."""
        if not self.num_state_slots:
            return 0
        if not self._free_state_slots:
            self.state_slot_waits_total += 1
            return 0
        self.state_slot_allocs_total += 1
        return self._free_state_slots.pop()

    def free_state_slot(self, slot: int) -> None:
        """Give a slot back (0: nothing to give). Its content stays: the
        next sequence's first chunk starts from zeros, not from the slot
        (engine/runner.py:_prefill_impl)."""
        if slot:
            self._free_state_slots.append(slot)

    def can_allocate(self, n: int) -> bool:
        return self.num_free_blocks >= n

    def allocate_blocks(self, n: int) -> Optional[List[int]]:
        if not self.can_allocate(n):
            return None
        out = []
        for _ in range(n):
            blk = self._pop_free_block()
            if blk is None:
                # Defensive: roll back the partial allocation rather than
                # crash the engine loop if accounting and reclaimability ever
                # disagree (e.g. a spill pin landing mid-allocation).
                self.free_blocks(out)
                return None
            self._ref[blk] = 1
            out.append(blk)
        return out

    def lookup_prefix(self, token_ids: Sequence[int],
                      seed: bytes = b"") -> Tuple[List[int], int]:
        """Find the longest cached full-block prefix of ``token_ids``.

        Returns (cached_block_ids, num_cached_tokens). Does NOT take refs and
        does NOT touch the hit/query counters; pair with ``allocate_prompt``.
        At least one prompt token is always left uncached so prefill has a
        position to compute logits from. ``seed`` namespaces the hash chain:
        KV computed under different LoRA adapters must never be shared, so
        each adapter seeds its own chain (Sequence.hash_seed).
        """
        if not self.enable_prefix_caching or self.num_state_slots:
            return [], 0
        return self._longest_cached_prefix(token_ids, seed)

    def _longest_cached_prefix(self, token_ids: Sequence[int],
                               seed: bytes) -> Tuple[List[int], int]:
        # Leave >= 1 token to recompute.
        max_cached_tokens = len(token_ids) - 1
        usable_full_blocks = max_cached_tokens // self.block_size
        blocks: List[int] = []
        prev = seed
        for i in range(usable_full_blocks):
            chunk = token_ids[i * self.block_size:(i + 1) * self.block_size]
            h = _block_hash(prev, chunk)
            blk = self._hash_to_block.get(h)
            if blk is None:
                break
            blocks.append(blk)
            prev = h
        return blocks, len(blocks) * self.block_size

    def allocate_prompt(
        self, token_ids: Sequence[int], seed: bytes = b""
    ) -> Optional[Tuple[List[int], int]]:
        """Allocate the block table for a new prompt, reusing cached prefixes.

        Returns (block_ids, num_cached_tokens) or None if out of blocks.
        """
        if self.num_free_blocks == 0:
            return None  # cheap out: don't hash the prompt on a starved pool
        cached, n_cached = self.lookup_prefix(token_ids, seed)
        total_blocks = -(-len(token_ids) // self.block_size)
        n_new = total_blocks - len(cached)
        # Pin the cached blocks FIRST: reviving an evictable block shrinks the
        # free count, and an unpinned cached block could otherwise be evicted
        # out from under us by allocate_blocks itself.
        for blk in cached:
            self._take_ref(blk)
        fresh = self.allocate_blocks(n_new)
        if fresh is None:
            self.free_blocks(cached)  # roll back the pins
            return None
        # Count hit/query telemetry only for ADMITTED prompts, so retry loops
        # on a congested pool don't inflate the hit rate the router scrapes.
        self.prefix_queries_total += len(token_ids)
        self.prefix_hits_total += n_cached
        if self.num_state_slots and self.enable_prefix_caching:
            self.prefix_hits_unserved_total += self._longest_cached_prefix(
                token_ids, seed)[1]
        return cached + fresh, n_cached

    def append_block(self) -> Optional[int]:
        blocks = self.allocate_blocks(1)
        return blocks[0] if blocks else None

    def _take_ref(self, blk: int) -> None:
        if blk in self._evictable:
            del self._evictable[blk]
            self._ref[blk] = 1
        else:
            self._ref[blk] = self._ref.get(blk, 0) + 1

    # ----------------------------------------------------------- registration
    def register_full_block(
        self, blk: int, prev_hash: bytes, tokens: Sequence[int]
    ) -> bytes:
        """Content-address a block that just became full (prefill or decode)."""
        if not self.enable_prefix_caching:
            return b""
        h = _block_hash(prev_hash, tokens)
        existing = self._hash_to_block.get(h)
        if existing is not None and existing != blk:
            # Duplicate content raced in; keep the earlier block as canonical.
            return h
        self._hash_to_block[h] = blk
        self._block_to_hash[blk] = h
        self._hash_parent[h] = prev_hash
        return h

    def adopt_full_block(self, blk: int, h: bytes,
                         parent_hash: bytes) -> bool:
        """Content-address a block whose hash is ALREADY KNOWN (prewarm
        restores from the shared tier arrive keyed by store hash, with no
        token list to re-derive it from — docs/ELASTIC.md). The caller
        owns ``blk`` (ref 1 from allocate_blocks) and has written its KV;
        freeing it afterwards parks it in the evictable cached-free LRU
        where future prompts hit it exactly like a locally computed
        prefix block. False (and nothing registered) when the hash is
        already resident — the caller should free the duplicate block."""
        if not self.enable_prefix_caching or not h:
            return False
        if h in self._hash_to_block:
            return False
        self._hash_to_block[h] = blk
        self._block_to_hash[blk] = h
        self._hash_parent[h] = parent_hash
        return True

    # ----------------------------------------------------------------- free
    def free_blocks(self, blocks: Sequence[int]) -> None:
        for blk in blocks:
            ref = self._ref.get(blk, 0) - 1
            if ref > 0:
                self._ref[blk] = ref
                continue
            self._ref.pop(blk, None)
            if blk in self._block_to_hash:
                self._evictable[blk] = None
                self._evictable.move_to_end(blk)
            else:
                self._free.append(blk)

    def reset_prefix_cache(self) -> None:
        for blk in list(self._evictable):
            self._free.append(blk)
            h = self._block_to_hash.pop(blk, None)
            if h is not None:
                self._hash_to_block.pop(h, None)
                self._hash_parent.pop(h, None)
        self._evictable.clear()
