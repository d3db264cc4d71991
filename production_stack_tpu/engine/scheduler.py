"""Continuous-batching scheduler.

Replaces the continuous-batching scheduler of the reference's external vLLM
engines (SURVEY.md §2.2). Policy (vLLM-v0-style, TPU-shaped):

  * Prefill has priority: a waiting request is admitted and prefilled in
    token-budgeted CHUNKS (one sequence per prefill step keeps the compiled
    shape family small: [1, T_bucket]).
  * Otherwise all RUNNING sequences decode together in one [B_bucket, 1] step.
  * Preemption by recompute: when the block pool is exhausted, the
    lowest-priority running sequence is evicted (blocks freed, KV optionally
    spilled to the host offload pool) and re-queued at the front of WAITING.

The prefill/decode distinction is observable by the router's request-stats
plane (reference src/vllm_router/stats/request_stats.py:119-121), so it is
load-bearing, not an implementation detail.
"""

import enum
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence as Seq
from collections import deque

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_cache import BlockPoolManager
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.utils import (
    init_logger,
    pow2_bucket as _bucket,
    prefill_rectangles,
    prefill_row_cap,
    window_mb_bucket,
)

logger = init_logger(__name__)

# Fused-scan length grades with the number of active streams (SSE burst
# size / per-dispatch fixed cost tradeoff); runner.warmup() AOT-compiles
# each shape family. (max_running_bound, K_cap) pairs, ascending. The top
# tier is reached through config.num_decode_steps, whose default (32)
# bounds the expected mid-dispatch arrival wait (~K/2 steps of TTFT
# queueing) at a few percent of per-dispatch overhead amortization.
DECODE_STEP_TIERS = ((2, 8), (8, 32))
INTERACTIVE_DECODE_STEPS = DECODE_STEP_TIERS[0][1]

# Why a prefill admission pass took no more requests than it did
# (ScheduledBatch.stop), in the order _try_schedule_prefill meets the
# limits; "none": the pass emptied the queue. Each is a flag the operator
# holds (docs/OBSERVABILITY.md, "What stopped admission").
PREFILL_STOPS = ("rows", "seqs", "tokens", "window", "slots", "blocks")


def decode_step_cap(num_streams: int, num_decode_steps: int) -> int:
    """Fused-scan K cap for ``num_streams`` concurrent rows. The SINGLE
    grading rule shared by the scheduler (pre-loop + dispatched-rows
    re-grade) and runner.warmup — a tier change updated in only one place
    would silently re-introduce mid-serving cold compiles."""
    cap = max(1, num_decode_steps)
    for bound, tier_cap in DECODE_STEP_TIERS:
        if num_streams <= bound:
            return min(cap, tier_cap)
    return cap


class SequenceStatus(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED_STOPPED = "stop"
    FINISHED_LENGTH = "length"
    FINISHED_ABORTED = "abort"
    # Disagg prefill hop complete: KV + chain state published to the remote
    # store; a decode engine continues the stream (docs/DISAGG.md).
    FINISHED_HANDOFF = "handoff"

    @property
    def is_finished(self) -> bool:
        return self in (
            SequenceStatus.FINISHED_STOPPED,
            SequenceStatus.FINISHED_LENGTH,
            SequenceStatus.FINISHED_ABORTED,
            SequenceStatus.FINISHED_HANDOFF,
        )


@dataclass
class Sequence:
    request_id: str
    prompt_token_ids: List[int]
    sampling: SamplingParams
    eos_token_id: Optional[int] = None
    arrival_time: float = field(default_factory=time.monotonic)

    status: SequenceStatus = SequenceStatus.WAITING
    output_token_ids: List[int] = field(default_factory=list)
    # Tokens sampled by an ISSUED-but-unapplied dispatch (the pipelined
    # engine advances state at issue and applies tokens at fetch): their KV
    # is in the pool and their seeds consumed, but the ids are not yet on
    # the host. num_computed_tokens already includes them.
    inflight_steps: int = 0
    # True while the FINAL chunk of this row's prefill is issued but not yet
    # applied: the row's first token exists only in that dispatch's device
    # vector. The row is decode-eligible all the same: a decode dispatch
    # issued meanwhile chains its start token from that vector
    # (runner._chains), which stays the dispatch's ONE source because the
    # engine loop holds at most two dispatches in flight (_run_loop). The
    # flag keeps the interactive cap off such a row (its first token comes
    # at the prefill's apply, not from the scan) and is what
    # pstpu:decode_rows_joined_total counts.
    pending_prefill_apply: bool = False
    # True from the issue of the row's last prompt chunk until a decode
    # dispatch takes the row (pstpu:decode_rows_first_total counts those).
    awaits_first_decode: bool = False
    # Aligned with output_token_ids when sampling.logprobs is set: one
    # (chosen_logprob, [(token_id, logprob), ...]) per accepted token.
    output_logprobs: List = field(default_factory=list)
    block_ids: List[int] = field(default_factory=list)
    # Slot of the runner's state pools (a model that declares recurrent
    # state): taken with the blocks, given back with them; 0 = none.
    state_slot: int = 0
    num_computed_tokens: int = 0       # tokens whose KV is in the device pool
    num_cached_tokens: int = 0         # prefix-cache hits (telemetry)
    num_preemptions: int = 0
    # LoRA adapter index in the engine's registry (0 = base model) rides the
    # packed buffer so one batch can mix adapters; adapter_name keys the
    # prefix-cache namespace (models/lora.py).
    adapter_idx: int = 0
    adapter_name: Optional[str] = None
    # --- prefill/decode disaggregation (docs/DISAGG.md) ---
    # Transfer key for the disagg prefill hop: once the prompt is prefilled
    # and token 1 sampled, the engine publishes KV + chain state under this
    # key and finishes the sequence (FINISHED_HANDOFF). Such a row must
    # NEVER join a decode batch — if publication fails the row is aborted,
    # not silently decoded on a prefill-role engine.
    handoff_key: Optional[str] = None
    handoff_done: bool = False
    # Router-flagged fallback traffic: the request is served end-to-end
    # (unified) on this engine even when its role would normally refuse the
    # other phase — the degrade path when a disagg pool is down.
    disagg_fallback: bool = False
    # --- mid-stream resume (docs/RESILIENCE.md) ---
    # Number of output tokens PRE-SEEDED from the request's resume_tokens:
    # they were produced (and delivered) by a previous engine before it
    # died, so this engine rebuilds their KV through the normal
    # preemption-recompute/restore prefill path and continues decoding at
    # generation index resume_base. They are never re-counted in
    # generation_tokens_total (the original engine counted them).
    resume_base: int = 0
    _resume_counted: bool = False
    # --- observability (docs/OBSERVABILITY.md) ---
    # Monotonic time of this sequence's FIRST dispatch issue: closes the
    # queue-wait phase (pstpu:queue_wait_seconds observes
    # first_issue_time - arrival_time exactly once, in the engine loop).
    first_issue_time: Optional[float] = None

    @property
    def hash_seed(self) -> bytes:
        """Prefix-cache hash-chain seed: KV under different LoRA adapters is
        different data and must never be cache-shared — on device OR in the
        host/remote offload tiers. Keyed by adapter NAME, not registry
        index: indices are per-engine-process orderings and would alias
        different adapters across engines sharing a remote KV tier."""
        return b"" if not self.adapter_name else f"lora:{self.adapter_name}".encode()
    first_token_time: Optional[float] = None
    # prefix-cache hash chain bookkeeping
    _prev_hash: bytes = b""
    _num_hashed_blocks: int = 0

    @property
    def all_token_ids(self) -> List[int]:
        return self.prompt_token_ids + self.output_token_ids

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def prefill_done(self) -> bool:
        return self.num_computed_tokens >= len(self.prompt_token_ids)

    def finish_reason(self) -> Optional[str]:
        return self.status.value if self.status.is_finished else None


@dataclass
class ScheduledBatch:
    kind: str                        # "prefill" | "decode"
    seqs: List[Sequence]
    chunk_starts: List[int] = field(default_factory=list)  # prefill only
    chunk_lens: List[int] = field(default_factory=list)
    # decode only: scan length of the fused dispatch, and per-sequence budget
    # (a sequence with fewer allocated/needed steps than num_steps has its
    # excess writes masked to the null block and its excess tokens discarded).
    num_steps: int = 1
    decode_steps: List[int] = field(default_factory=list)
    # Set by advance_at_issue: per-row preemption epochs (apply_results
    # skips rows preempted while the dispatch was in flight) and, for
    # prefill, which rows completed their prompt in this chunk.
    epochs: List[int] = field(default_factory=list)
    finals: List[bool] = field(default_factory=list)
    # Set by the runner at decode issue (docs/PERF.md round 10): which
    # speculative dispatch variant actually ran — "off" (speculation
    # disabled), "linear", "tree", "adaptive", or "off-degrade" (adaptive
    # controller sent the whole batch down the plain scan). Attribution
    # for the flight recorder's decode_issue events; apply_results never
    # reads it (variable-emission reconciliation is shape-driven).
    spec_mode: str = "off"
    # prefill only, set by the scheduler: what it knew when the admission
    # pass stopped. `stop` is "none" (the queue was emptied) or the FIRST of
    # PREFILL_STOPS the pass met — where the rectangle it chose took fewer
    # rows than the candidate loop had gathered, the limit that bounded
    # the choice (`tokens` or `window`); `left_waiting`
    # counts the requests still waiting that a prefill could have taken.
    stop: str = "none"
    left_waiting: int = 0
    # prefill only, set by the scheduler: the chunks lie end to end in ONE
    # row (utils.prefill_rectangle's ``packed_tokens`` form is the shape).
    packed: bool = False
    # decode only, set by apply_results: the most tokens one row of the
    # dispatch delivered (0 until applied, and for a failed fetch): the
    # steps beyond it served no row (pstpu:decode_steps_empty_total).
    delivered_max: int = 0
    # decode only, set by the engine loop at issue (after a penalty
    # batch's drain, which brings every token to the host): rows whose
    # first token is still in the in-flight prefill's device vector.
    joined_rows: int = 0

    @property
    def num_tokens(self) -> int:
        if self.kind == "prefill":
            return sum(self.chunk_lens)
        return sum(self.decode_steps) or len(self.seqs)


def _packed_chunk_lens(remaining: List[int], budget: int) -> List[int]:
    """The chunks of a PACKED prefill dispatch: each of the ``n``
    candidates gets ``min(remaining, budget // n)``, what a rectangle of
    ``n`` rows would give it; then the slack the short ones leave goes to
    those that still have prompt left, in queue order, until the budget
    is full. Nobody's share shrinks and nobody is passed over: what
    leaves is the padding."""
    share = budget // len(remaining)
    lens = [min(r, share) for r in remaining]
    slack = budget - sum(lens)
    for i, r in enumerate(remaining):
        more = min(r - lens[i], slack)
        lens[i] += more
        slack -= more
    return lens


class Scheduler:
    def __init__(self, config: EngineConfig, block_manager: BlockPoolManager,
                 offload=None, decode_window_budget: Optional[int] = None,
                 prefill_window_budget: Optional[int] = None,
                 prefill_packed: bool = False):
        self.config = config
        # Which form a prefill dispatch takes: the runner's to say
        # (ModelRunner.prefill_packs), the engine hands it over. False: a
        # [rows, T] rectangle, a row a sequence; True: ONE row in which the
        # sequences' chunks lie end to end.
        self.prefill_packed = prefill_packed
        self.block_manager = block_manager
        self.offload = offload  # KVOffloadManager (host/remote KV tiers)
        # A dispatch with history gathers bucket(rows) x bucket(max_blocks)
        # blocks into a contiguous window copy; cap that product so a batch
        # of prefix-sharing long sequences can't materialize a window larger
        # than the budgeted HBM (advisor r2). Decode under the paged impl
        # reads the pool in place (no window): budget None = unlimited.
        self.decode_window_budget = decode_window_budget or (1 << 30)
        self.prefill_window_budget = prefill_window_budget or (1 << 30)
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self.seqs: Dict[str, Sequence] = {}
        self.num_preemptions_total = 0
        # Admission passes that scheduled NO prefill while requests waited
        # (the running set is full, or every candidate was starved of a
        # state slot or of blocks), by the limit that stopped them: beside
        # the dispatches' own `stop` in pstpu:prefill_stop_*_total.
        self.prefill_blocked: Dict[str, int] = dict.fromkeys(
            PREFILL_STOPS, 0)
        # Decode-priority row: a row the window budget skipped last dispatch
        # decodes FIRST next dispatch (as the leading row it schedules
        # unconditionally). Held as the Sequence itself, not an index — the
        # running list churns between dispatches (advisor r3 finding).
        self._decode_first: Optional[Sequence] = None
        # Observability hooks (docs/OBSERVABILITY.md), set by the engine:
        # on_preempt(request_id) at each preemption; on_restore(request_id,
        # restored_tokens, seconds) after a shared-tier restore round trip.
        # Plain callables invoked synchronously on the engine loop — None
        # keeps the scheduler hook-free (tests construct it standalone).
        self.on_preempt = None
        self.on_restore = None

    def _window_ok(self, rows: int, max_blocks: int, budget: int) -> bool:
        # Mirrors the runner's windowed-dispatch mb quantization
        # (runner._decode_mb / _prefill_mb): the budget must count the
        # blocks the dispatch will actually gather, not the live bucket.
        cfg = self.config
        return (
            _bucket(rows, 1, max(1, cfg.max_num_seqs))
            * window_mb_bucket(max_blocks, cfg.max_blocks_per_seq)
            <= budget
        )

    # ----------------------------------------------------------------- intake
    def add_sequence(self, seq: Sequence) -> None:
        if seq.num_tokens > self.config.max_model_len:
            raise ValueError(
                f"Prompt of {seq.num_tokens} tokens exceeds max_model_len "
                f"{self.config.max_model_len}"
            )
        bs = self.config.block_size
        usable = self.block_manager.num_blocks - 1
        if -(-seq.num_tokens // bs) > usable:
            raise ValueError(
                f"Prompt of {seq.num_tokens} tokens cannot fit the KV pool "
                f"({usable} blocks x {bs} tokens)"
            )
        self.seqs[seq.request_id] = seq
        self.waiting.append(seq)

    def abort(self, request_id: str) -> Optional[Sequence]:
        return self.finish(request_id, SequenceStatus.FINISHED_ABORTED)

    def finish(self, request_id: str, status: SequenceStatus) -> Optional[Sequence]:
        """Externally finish a request (abort, or stop-string match detected
        by the engine's detokenizer)."""
        seq = self.seqs.get(request_id)
        if seq is None or seq.status.is_finished:
            return None
        self._finish(seq, status)
        return seq

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # -------------------------------------------------------------- schedule
    def schedule(self, prefer_decode: bool = False) -> Optional[ScheduledBatch]:
        """One admissible batch. Default order is prefill-first (TTFT
        priority); ``prefer_decode`` inverts it — the overlap engine loop
        uses it to keep decode cadence while a prefill dispatch is already
        in flight in the other slot (Sarathi-style stall-free batching)."""
        if prefer_decode:
            batch = self._schedule_decode()
            if batch is not None:
                return batch
            return self._try_schedule_prefill()
        batch = self._try_schedule_prefill()
        if batch is not None:
            return batch
        return self._schedule_decode()

    def _try_schedule_prefill(self) -> Optional[ScheduledBatch]:
        """Admit waiting prompts into ONE batched prefill dispatch
        (concurrent arrivals must not serialize TTFT).

        FCFS over what is admissible: the candidates are the first
        ``min(prefill_row_cap, room)`` waiting requests that find blocks
        (and a state slot, where the model keeps one); starved prompts are
        skipped, NOT preempted-for (preempting here admits ping-pong
        livelock; only decode slot-appends preempt, which preserves FCFS
        progress). What the dispatch then is has two forms
        (``prefill_packed``: the runner's choice, by what its model keeps
        and its kernels cover):

        PACKED, one row of tokens: every candidate is taken and its chunk
        lies behind its predecessor's in ONE row (``_packed_chunk_lens``:
        an equal share of the budget each, then the slack the short ones
        leave to whoever has prompt left, in queue order); the program is
        the smallest one-row ``[1, T]`` of ``utils.prefill_rectangles``
        that holds the sum, so only the row's END is padding.

        A RECTANGLE, a row a sequence: the ``[rows, T]`` of
        ``utils.prefill_rectangles`` (area <= the token budget, always)
        whose rows carry the most live tokens ``sum(min(remaining, T))``
        over the first ``min(rows, candidates)`` candidates: the device
        computes the padded rectangle whatever it holds, so a dispatch's
        cost is its area and its worth is what it carries. Ties go to the
        smaller area, then to the longer chunk (fewer, longer chunks: the
        queue's head finishes sooner). A rectangle whose rows would gather
        a history window beyond ``prefill_window_budget`` is passed over;
        one row always goes. Each row's chunk is ``min(remaining, T)``.
        """
        cfg = self.config
        room = cfg.max_num_seqs - len(self.running)
        row_cap = prefill_row_cap(cfg)
        max_rows = min(row_cap, room)
        if not self.waiting:
            return None
        if max_rows <= 0:
            if self._num_prefillable():
                self.prefill_blocked["seqs"] += 1
            return None
        cands: List[Sequence] = []
        newly_allocated: set = set()
        stop = None       # the first limit this pass meets (PREFILL_STOPS)
        for cand in list(self.waiting):
            if not self._prefillable(cand):
                continue
            if len(cands) >= max_rows:
                stop = stop or ("rows" if row_cap <= room else "seqs")
                break
            if not cand.block_ids:
                # Blocks AND a state slot, or neither (a K/V-only model
                # needs no slot and is never refused one).
                if self.block_manager.num_state_slots:
                    cand.state_slot = \
                        self.block_manager.allocate_state_slot()
                    if not cand.state_slot:
                        stop = stop or "slots"
                        continue  # every slot is held; put off, not failed
                alloc = self.block_manager.allocate_prompt(
                    cand.all_token_ids, seed=cand.hash_seed
                )
                if alloc is None:
                    self._free_state_slot(cand)
                    stop = stop or "blocks"
                    continue  # starved; a later cand may already hold blocks
                cand.block_ids, cand.num_cached_tokens = alloc
                cand.num_computed_tokens = cand.num_cached_tokens
                cand._prev_hash = cand.hash_seed
                newly_allocated.add(cand.request_id)
                if self.offload is not None:
                    # Host/remote KV tiers may extend the cached prefix past
                    # what survived in device HBM (LMCache-equivalent path).
                    t_restore = time.monotonic()
                    restored = self.offload.try_restore(
                        cand.all_token_ids, cand.block_ids,
                        cand.num_computed_tokens, seed=cand.hash_seed,
                    )
                    cand.num_computed_tokens += restored
                    cand.num_cached_tokens += restored
                    if restored and self.on_restore is not None:
                        self.on_restore(
                            cand.request_id, restored,
                            time.monotonic() - t_restore,
                        )
            cands.append(cand)
        if not cands:
            if stop:
                self.prefill_blocked[stop] += 1
            return None
        # NOTE: a preempted sequence re-prefills prompt+output together
        # (num_tokens includes generated tokens).
        rems = [c.num_tokens - c.num_computed_tokens for c in cands]
        if self.prefill_packed:
            lens = _packed_chunk_lens(
                rems, prefill_rectangles(cfg, True)[-1][1])
        else:
            lens, stop = self._rectangle_chunk_lens(
                cands, rems, newly_allocated, stop)
        seqs = cands[:len(lens)]
        starts = [s.num_computed_tokens for s in seqs]
        for seq in seqs:
            self.waiting.remove(seq)
            seq.status = SequenceStatus.RUNNING
        left = self._num_prefillable()
        return ScheduledBatch(
            kind="prefill", seqs=seqs, chunk_starts=starts, chunk_lens=lens,
            stop=stop if stop and left else "none", left_waiting=left,
            packed=self.prefill_packed,
        )

    def _rectangle_chunk_lens(self, cands, rems, newly_allocated, stop):
        """The rectangle over the candidates (``_try_schedule_prefill``):
        (the chunk of each row taken, the pass's ``stop``). Rows pad to the
        ladder and chunks to their power-of-two bucket, and the PADDED
        area is what the device computes, so that is what the budget
        bounds."""
        cfg = self.config
        best = free = None   # (live, -area, t, rows taken); `free` is the
        #                      choice were there no window budget
        for rows, t in prefill_rectangles(cfg):
            n = min(rows, len(cands))
            rect = (sum(min(r, t) for r in rems[:n]), -rows * t, t, n)
            if free is None or rect > free:
                free = rect
            if best is not None and rect <= best:
                continue
            # A chunk with history gathers a [rows, max_blocks] window;
            # keep its bucketed size (at the PADDED row count) within the
            # window budget too. One row always goes.
            if rows == 1 or not any(
                c.num_computed_tokens > 0 for c in cands[:n]
            ) or self._window_ok(
                rows, max(len(c.block_ids) for c in cands[:n]),
                self.prefill_window_budget,
            ):
                best = rect
        _, _, chunk_cap, n = best
        if n < len(cands):
            # The rows gathered above were not all taken, so whatever
            # ended the candidate loop is not what bounds this dispatch:
            # the area bound did, or the window budget where it passed
            # over a rectangle of more rows.
            stop = "window" if free[3] > n else "tokens"
        # Candidates allocated THIS pass but not taken by the rectangle must
        # not sit in waiting pinning non-evictable blocks (they could starve
        # decode's append_block under memory pressure); release them — the
        # prefix cache makes the re-allocation next pass cheap.
        for cand in cands[n:]:
            if cand.request_id in newly_allocated:
                self.block_manager.free_blocks(cand.block_ids)
                self._free_state_slot(cand)
                cand.block_ids = []
                cand.num_computed_tokens = 0
                cand.num_cached_tokens = 0
                cand._prev_hash = cand.hash_seed
                cand._num_hashed_blocks = 0
        return [min(r, chunk_cap) for r in rems[:n]], stop

    def _prefillable(self, seq: Sequence) -> bool:
        """Role admission: a decode-role engine never schedules prefill
        batches for disagg-conforming traffic; it prefills only
        router-flagged fallback requests (decode-hop rows are restored
        straight to RUNNING, never queued here)."""
        return self.config.role != "decode" or seq.disagg_fallback

    def _num_prefillable(self) -> int:
        """Waiting requests a prefill pass could take."""
        return sum(map(self._prefillable, self.waiting))

    def _schedule_decode(self) -> Optional[ScheduledBatch]:
        if not self.running:
            return None
        bs = self.config.block_size
        # Streaming granularity (VERDICT r2 weak #5): the fused scan emits
        # tokens to clients once per dispatch, so K trades SSE burst size
        # against per-dispatch overhead. At high batch the aggregate
        # throughput justifies long bursts; with few interactive streams the
        # absolute throughput cost of short dispatches is small and latency
        # dominates.
        max_k = decode_step_cap(
            len(self.running), self.config.num_decode_steps
        )
        scheduled: List[Sequence] = []
        steps: List[int] = []
        snapshot = list(self.running)
        # Iteration starts at the row the window budget skipped last
        # dispatch, if any (order stays stable otherwise, preserving the
        # runner's persistent decode-window cache, which keys on identical
        # row order).
        ofs = 0
        if self._decode_first is not None:
            try:
                ofs = snapshot.index(self._decode_first)
            except ValueError:
                pass  # finished/preempted since; normal order
            self._decode_first = None
        first_skipped: Optional[Sequence] = None
        for seq in snapshot[ofs:] + snapshot[:ofs]:
            if seq not in self.running:
                # Preempted by an earlier iteration of this same pass.
                continue
            # A row whose last prompt chunk is still in flight
            # (pending_prefill_apply) is taken like any other: the runner
            # chains its start token from that prefill's device vector, so
            # it rides the train issued right behind its prefill and not
            # the one after.
            if seq.handoff_key is not None:
                # Disagg prefill hop: the row finishes at token 1 via the
                # handoff publish (engine loop); it never decodes here —
                # the decode-pool engine continues the stream.
                continue
            if self.config.role == "prefill" and not seq.disagg_fallback:
                # Role admission: a prefill-role engine never schedules
                # decode batches except for router-flagged fallback traffic.
                continue
            # Positions written this dispatch: pos .. pos+want-1. `want` is
            # capped by model-length capacity and the request's remaining
            # token budget (counting in-flight unapplied tokens) so the
            # fused scan rarely computes discarded steps.
            pos = seq.num_computed_tokens
            produced = len(seq.output_token_ids) + seq.inflight_steps
            if (
                seq.sampling.max_tokens - produced <= 0
                or self.config.max_model_len - pos <= 0
            ):
                # Fully dispatched: the in-flight apply will finish it.
                continue
            want = max(1, min(
                max_k,
                self.config.max_model_len - pos,
                seq.sampling.max_tokens - produced,
            ))
            need_blocks = (pos + want - 1) // bs + 1
            while len(seq.block_ids) < need_blocks:
                blk = self.block_manager.append_block()
                if blk is not None:
                    seq.block_ids.append(blk)
                    continue
                if len(seq.block_ids) * bs > pos:
                    break  # partial allocation still allows >= 1 step
                victim = self._pick_preemption_victim(exclude=scheduled)
                if victim is None or victim is seq:
                    # Cannot make space without killing `seq` itself;
                    # preempt seq and stop scheduling it this step.
                    self._preempt(seq)
                    break
                self._preempt(victim)
            if seq not in self.running:
                continue
            avail = len(seq.block_ids) * bs - pos
            if avail <= 0:
                continue
            mb_next = max(
                [len(seq.block_ids)] + [len(s.block_ids) for s in scheduled]
            )
            if scheduled and not self._window_ok(
                len(scheduled) + 1, mb_next, self.decode_window_budget
            ):
                if first_skipped is None:
                    first_skipped = seq
                continue  # window budget full; this row decodes next dispatch
            scheduled.append(seq)
            steps.append(min(want, avail))
        if first_skipped is not None and first_skipped in self.running:
            # Next dispatch starts AT the skipped row (it schedules
            # unconditionally as the first row), so a budget-bumped long row
            # cannot be starved by the same earlier rows forever.
            self._decode_first = first_skipped
        if not scheduled:
            return None
        # Re-grade K by the rows actually DISPATCHED: when the window budget
        # skipped rows, len(running) > len(scheduled) and the pre-loop tier
        # would emit a (small-rows, high-K) shape family that warmup never
        # compiled (warmup keys tiers by row bucket).
        max_k = min(
            max_k,
            decode_step_cap(len(scheduled), self.config.num_decode_steps),
        )
        # Interactive first dispatch: a row that would get its FIRST token
        # only when the whole fused dispatch returns (no token produced and
        # none in flight) would add the full K-step scan to its TTFT
        # (~0.8 s at 16 rows on a v5e, VERDICT r4 weak #2). Cap the scan
        # short when any scheduled row is such; the next dispatch resumes
        # the full tier.
        # A row joined behind its in-flight prefill is NOT such a row: its
        # first token is delivered at that prefill's apply, so the train
        # keeps its length (capping it would quadruple the prefill
        # dispatches a token at saturation).
        # NOTE on arrivals: a request landing MID-dispatch waits out the
        # in-flight fused scan before its prefill can start (prefill
        # priority applies between dispatches only), so the expected TTFT
        # queueing term is half the standing dispatch length — which is
        # why the top tier caps at 32 steps (DECODE_STEP_TIERS), not at a
        # latency-oblivious maximum. Event-driven K capping cannot help:
        # the queue is empty at schedule time whenever admission is
        # possible (prefill just ran), and capping on an INADMISSIBLE
        # backlog only quadruples per-dispatch overhead at saturation
        # (r5 review).
        if any(not s.output_token_ids and not s.inflight_steps
               for s in scheduled):
            max_k = min(max_k, INTERACTIVE_DECODE_STEPS)
        # K is PINNED at the graded cap, not bucketed by the largest per-row
        # budget: the runner's while_loop executes only the steps some row
        # still needs, so padding K costs unused ring-buffer bytes only —
        # while a live-bucketed K makes every power of two a distinct XLA
        # family that warmup cannot enumerate (VERDICT r4 weak #1).
        num_steps = max_k
        # Return blocks over-reserved for the pre-regrade `want` (the
        # allocation loop sized rows for up to the pre-loop max_k steps):
        # under a tight pool they would otherwise sit unused this dispatch
        # while starving prefill admissions.
        for i, seq in enumerate(scheduled):
            steps[i] = min(steps[i], num_steps)
            need = (seq.num_computed_tokens + steps[i] - 1) // bs + 1
            if len(seq.block_ids) > need:
                self.block_manager.free_blocks(seq.block_ids[need:])
                del seq.block_ids[need:]
        return ScheduledBatch(
            kind="decode", seqs=scheduled, num_steps=num_steps,
            decode_steps=steps,
        )

    def _pick_preemption_victim(self, exclude: Seq[Sequence]) -> Optional[Sequence]:
        for seq in reversed(self.running):
            if seq in exclude:
                continue
            if seq.handoff_key is not None:
                # A handoff row's KV may be mid-read by the (asynchronous)
                # publish; preempting would free — and let the pool
                # recycle — the very blocks being serialized. The row
                # finishes right after the publish anyway, so skipping it
                # cannot starve the pool for long.
                continue
            return seq
        return None

    def _preempt(self, seq: Sequence) -> None:
        logger.warning("Preempting request %s (recompute)", seq.request_id)
        self.num_preemptions_total += 1
        seq.num_preemptions += 1
        if self.on_preempt is not None:
            self.on_preempt(seq.request_id)
        if seq in self.running:
            self.running.remove(seq)
        self.block_manager.free_blocks(seq.block_ids)
        self._free_state_slot(seq)
        seq.block_ids = []
        seq.num_computed_tokens = 0
        # In-flight unapplied tokens are DISCARDED (apply_results skips
        # rows whose preemption epoch changed); recompute-by-prefill
        # regenerates them deterministically from the same seeds.
        seq.inflight_steps = 0
        seq.pending_prefill_apply = False
        seq.awaits_first_decode = False
        seq._prev_hash = seq.hash_seed
        seq._num_hashed_blocks = 0
        seq.status = SequenceStatus.WAITING
        self.waiting.appendleft(seq)

    # ------------------------------------------------------- post-step update
    def advance_at_issue(self, batch: ScheduledBatch) -> None:
        """Speculative state advance at dispatch ISSUE: KV positions, queue
        transitions, and in-flight generation accounting — everything
        schedule() needs to build the NEXT dispatch before this one's
        sampled tokens reach the host. apply_results later delivers the
        tokens (the pipelined engine issues N+1 between the two)."""
        batch.epochs = [s.num_preemptions for s in batch.seqs]
        if batch.kind == "prefill":
            requeue: List[Sequence] = []
            batch.finals = []
            for idx, seq in enumerate(batch.seqs):
                if seq.status.is_finished:
                    batch.finals.append(False)
                    continue  # aborted while scheduling was in flight
                seq.num_computed_tokens += batch.chunk_lens[idx]
                final = seq.num_computed_tokens >= seq.num_tokens
                batch.finals.append(final)
                if final:
                    # Prompt complete: the sampled (in-flight) next token
                    # moves the row to RUNNING for decode scheduling, at
                    # once: a decode issued before this dispatch's apply
                    # chains from it (see pending_prefill_apply).
                    seq.inflight_steps += 1
                    seq.pending_prefill_apply = True
                    seq.awaits_first_decode = True
                    self.running.append(seq)
                else:
                    # More chunks to go; requeue at the front (order kept).
                    seq.status = SequenceStatus.WAITING
                    requeue.append(seq)
            self.waiting.extendleft(reversed(requeue))
        else:
            for i, seq in enumerate(batch.seqs):
                if seq.status.is_finished:
                    continue
                seq.num_computed_tokens += batch.decode_steps[i]
                seq.inflight_steps += batch.decode_steps[i]
                seq.awaits_first_decode = False

    def _apply_valid(self, seq: Sequence, epoch: int) -> bool:
        """Results apply only to rows still in the generation that issued
        them: finished (abort/stop) and preempted-since-issue rows discard
        their in-flight tokens. (Non-final prefill rows are WAITING for
        their next chunk — still valid; preemption is distinguished by the
        epoch, not the queue.)"""
        return (
            not seq.status.is_finished
            and seq.num_preemptions == epoch
        )

    def apply_results(
        self, batch: ScheduledBatch, token_lists: List[List[int]],
        logprob_lists=None,
    ) -> tuple:
        """Deliver a fetched dispatch's outputs (a token list per sequence;
        empty for non-final prefill chunks; ``logprob_lists`` aligned
        per-token entries when any row requested logprobs). Returns
        (sequences that produced NEW tokens, number of tokens accepted).
        State was already advanced by advance_at_issue."""
        produced: List[Sequence] = []
        accepted = 0
        if batch.kind == "prefill":
            for idx, seq in enumerate(batch.seqs):
                if batch.finals[idx] and \
                        seq.num_preemptions == batch.epochs[idx]:
                    # This batch set the flag at issue; a preempted-since
                    # row's NEW prefill manages its own flag (epoch guard).
                    seq.pending_prefill_apply = False
                if not self._apply_valid(seq, batch.epochs[idx]):
                    continue
                self._register_full_blocks(seq)
                if batch.finals[idx] and token_lists[idx]:
                    seq.inflight_steps -= 1
                    self._append_token(
                        seq, token_lists[idx][0],
                        logprob_lists[idx][0]
                        if logprob_lists and logprob_lists[idx] else None,
                    )
                    accepted += 1
                    produced.append(seq)
        else:
            for i, (seq, toks) in enumerate(zip(batch.seqs, token_lists)):
                if not self._apply_valid(seq, batch.epochs[i]):
                    continue
                seq.inflight_steps -= batch.decode_steps[i]
                if self.config.speculative_num_tokens:
                    # A speculative dispatch emits a VARIABLE token count
                    # (acceptance-dependent, <= the budgeted steps);
                    # advance_at_issue advanced by the full budget, so
                    # reconcile the KV position to what the device
                    # actually committed. Safe because the speculative
                    # engine loop is strictly ordered (no other dispatch
                    # is issued between this one's issue and apply).
                    seq.num_computed_tokens -= max(
                        0, batch.decode_steps[i] - len(toks)
                    )
                took = 0
                lps = logprob_lists[i] if logprob_lists else None
                for j, tok in enumerate(toks):
                    if seq.status.is_finished:
                        break  # EOS/max_tokens hit mid-scan; rest discarded
                    self._append_token(
                        seq, tok, lps[j] if lps else None
                    )
                    took += 1
                accepted += took
                self._register_full_blocks(seq)
                if took:
                    produced.append(seq)
                    batch.delivered_max = max(batch.delivered_max, took)
        for seq in produced:
            if seq.status.is_finished and seq in self.running:
                self.running.remove(seq)
        return produced, accepted

    def update_after_step(
        self, batch: ScheduledBatch, token_lists: List[List[int]],
        logprob_lists=None,
    ) -> tuple:
        """Synchronous advance+apply (non-pipelined callers and tests)."""
        self.advance_at_issue(batch)
        return self.apply_results(batch, token_lists, logprob_lists)

    def _append_token(self, seq: Sequence, token: int, logprob=None) -> None:
        if seq.first_token_time is None:
            seq.first_token_time = time.monotonic()
        seq.output_token_ids.append(token)
        if seq.sampling.logprobs is not None:
            seq.output_logprobs.append(logprob)
        sp = seq.sampling
        n_out = len(seq.output_token_ids)
        if (
            not sp.ignore_eos
            and n_out >= sp.min_tokens
            and (
                (seq.eos_token_id is not None and token == seq.eos_token_id)
                or token in sp.stop_token_ids
            )
        ):
            self._finish(seq, SequenceStatus.FINISHED_STOPPED)
        elif n_out >= sp.max_tokens or seq.num_tokens >= self.config.max_model_len:
            self._finish(seq, SequenceStatus.FINISHED_LENGTH)

    def _finish(self, seq: Sequence, status: SequenceStatus) -> None:
        seq.status = status
        if seq in self.running:
            self.running.remove(seq)
        if seq in self.waiting:
            self.waiting.remove(seq)
        self.block_manager.free_blocks(seq.block_ids)
        self._free_state_slot(seq)
        seq.block_ids = []

    def _free_state_slot(self, seq: Sequence) -> None:
        self.block_manager.free_state_slot(seq.state_slot)
        seq.state_slot = 0

    def _register_full_blocks(self, seq: Sequence) -> None:
        if not seq.block_ids:
            return  # freed (abort/preempt) before this bookkeeping ran
        bs = self.config.block_size
        # num_computed_tokens may run ahead of the host-known token ids by
        # the in-flight amount (pipelined issue); hashing needs the ids, so
        # register only what the host has.
        full = min(seq.num_computed_tokens, len(seq.all_token_ids)) // bs
        tokens = seq.all_token_ids
        while seq._num_hashed_blocks < full:
            i = seq._num_hashed_blocks
            h = self.block_manager.register_full_block(
                seq.block_ids[i], seq._prev_hash, tokens[i * bs:(i + 1) * bs]
            )
            if self.offload is not None:
                self.offload.on_block_registered(h, seq.block_ids[i])
            seq._prev_hash = h
            seq._num_hashed_blocks += 1
