"""ServingEngine: the async continuous-batching inference engine.

Owns: tokenizer, ModelRunner (device state + jitted step), BlockPoolManager
(paged KV bookkeeping + prefix cache), Scheduler (continuous batching), and
per-request output streams. The engine loop runs model steps in a worker
thread so the asyncio event loop (HTTP serving) never blocks on the device.

Aborts are DEFERRED: client disconnects enqueue the request id and the loop
applies them between device steps — KV blocks are never freed while a step
that writes into them is still in flight.

This tier replaces the external vLLM engine images of the reference stack
(reference helm/templates/deployment-vllm-multi.yaml:58-134).
"""

import asyncio
import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import AsyncIterator, Dict, List, Optional, Set, Tuple

import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_cache import BlockPoolManager
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import (
    PREFILL_STOPS,
    Scheduler,
    Sequence,
    SequenceStatus,
)
from production_stack_tpu.engine.tokenizer import (
    IncrementalDetokenizer,
    get_tokenizer,
)
from production_stack_tpu.models import get_model
from production_stack_tpu.models.config import resolve_model_config
from production_stack_tpu.ops.attention import NO_SPAN, keys_in_span
from production_stack_tpu.parallel import make_mesh
from production_stack_tpu.protocols import random_uuid
from production_stack_tpu.tracing import (
    spans_dropped_total as _spans_dropped_total,
)
from production_stack_tpu.utils import init_logger, prefill_rectangle

logger = init_logger(__name__)


@dataclass
class RequestOutput:
    request_id: str
    text_delta: str = ""
    token_ids: List[int] = field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[str] = None
    num_prompt_tokens: int = 0
    num_output_tokens: int = 0
    num_cached_tokens: int = 0
    # When sampling.logprobs is set: one (chosen_logprob,
    # [(token_id, logprob), ...top-k]) per output token, aligned with
    # token_ids (None otherwise).
    logprobs: Optional[List] = None
    # time.monotonic() when the request was enqueued in the scheduler and
    # when its first token was appended (the engine's TTFT stamps): the
    # HTTP surface measures handler entry -> enqueue and first token ->
    # first chunk handed to the transport from them
    # (pstpu:http_ingress_seconds, pstpu:first_chunk_emit_seconds).
    arrival_time: Optional[float] = None
    first_token_time: Optional[float] = None


@dataclass
class _StreamState:
    queue: asyncio.Queue
    detok: IncrementalDetokenizer
    text: str = ""   # decoded output, already truncated at any stop match
    sent: int = 0    # chars delivered to the client so far


class ServingEngine:
    def __init__(
        self,
        config: EngineConfig,
        mesh=None,
        params=None,
        num_kv_blocks: Optional[int] = None,
    ):
        # Fast-start telemetry (docs/ELASTIC.md): construction begins the
        # startup clock; start() closes it once warmup finishes and the
        # engine is ready to serve (pstpu:startup_total_seconds).
        self._startup_t0 = time.monotonic()
        self.startup_total_seconds = 0.0
        # Cumulative seconds spent serving POST /prewarm pulls (the
        # router-driven hot-chain prefetch before a new engine takes load).
        self.startup_prewarm_seconds = 0.0
        self.prewarmed_blocks_total = 0
        self.config = config
        self.model_config = resolve_model_config(config.model)
        config.refuse_what_state_cannot_follow(self.model_config)
        config.refuse_what_latent_rows_cannot_follow(self.model_config)
        config.refuse_what_a_span_cannot_follow(self.model_config)
        self.tokenizer = get_tokenizer(config.model, self.model_config)
        self.mesh = mesh or make_mesh(
            dp=config.data_parallel_size,
            sp=config.sequence_parallel_size,
            tp=config.tensor_parallel_size,
        )
        # The roofline denominator follows the device this engine found
        # (None on the CPU backend; an unknown TPU kind raises here, at
        # startup, unless the operator gave the peak).
        from production_stack_tpu.perf.roofline import peak_hbm_gbps

        dev0 = self.mesh.devices.flat[0]
        self.hbm_peak_gbps = peak_hbm_gbps(
            dev0.platform, dev0.device_kind, config.hbm_peak_gbps
        )
        self.lora_registry = None
        if config.lora_modules:
            from production_stack_tpu.models.lora import (
                LoRARegistry,
                load_peft_adapter,
            )

            if not get_model(self.model_config).LORA_TARGETS:
                raise ValueError("LoRA serving is llama-family only")
            self.lora_registry = LoRARegistry(self.model_config)
            for name, path in config.lora_modules.items():
                self.lora_registry.add(
                    load_peft_adapter(name, path, self.model_config)
                )
        self.runner = ModelRunner(
            config, self.model_config, self.mesh,
            params=params, num_kv_blocks=num_kv_blocks,
            lora_registry=self.lora_registry,
        )
        self.block_manager = BlockPoolManager(
            self.runner.num_kv_blocks, config.block_size,
            config.enable_prefix_caching,
            # One slot per sequence the scheduler can hold; the runner's
            # pools have one more, the scratch slot 0.
            num_state_slots=max(0, self.runner.num_state_slots - 1),
        )
        self.offload = None
        if config.kv_offload_cpu or config.kv_remote_url:
            from production_stack_tpu.kv_offload import KVOffloadManager

            gb = config.kv_offload_max_cpu_gb or 4.0
            self.offload = KVOffloadManager(
                self.runner, self.block_manager,
                host_pool_bytes=(
                    int(gb * (1 << 30)) if config.kv_offload_cpu else 0
                ),
                remote_url=config.kv_remote_url,
                serde=config.kv_remote_serde,
                # Restore-over-recompute cost model (docs/KV_ECONOMY.md).
                bytes_per_token=config.kv_cache_bytes_per_token(
                    self.model_config
                ),
                link_gbps=config.kv_restore_link_gbps,
                prefill_tok_s=config.kv_restore_prefill_tok_s,
            )
        # Prefill/decode disaggregation (docs/DISAGG.md): non-unified roles
        # get a coordinator for the KV handoff plane (its own store
        # connection, separate from the offload spiller's).
        from production_stack_tpu.disagg.transfer import ENGINE_ROLES

        if config.role not in ENGINE_ROLES:
            raise ValueError(
                f"Unknown engine role {config.role!r} "
                f"(supported: {', '.join(ENGINE_ROLES)})"
            )
        self.disagg = None
        if config.role != "unified":
            from production_stack_tpu.disagg import DisaggCoordinator

            self.disagg = DisaggCoordinator(
                config, self.runner, self.block_manager
            )
        self.scheduler = Scheduler(
            config, self.block_manager, offload=self.offload,
            decode_window_budget=self.runner.decode_window_blocks,
            prefill_window_budget=self.runner.prefill_window_blocks,
            prefill_packed=self.runner.prefill_packs,
        )

        self._streams: Dict[str, _StreamState] = {}
        self._pending_aborts: Set[str] = set()
        # Decode-hop restores waiting for the engine loop: (Sequence,
        # HandoffManifest) pairs. Applied between device steps so the
        # host->device KV write is ordered with model dispatches.
        self._pending_restores: List = []
        # In-flight handoff publishes (background tasks): awaited at loop
        # exit so no accepted handoff is lost on shutdown.
        self._publish_tasks: Set = set()
        # Queued POST /prewarm pulls (docs/ELASTIC.md): (request, future)
        # pairs the engine loop serves between device steps — the
        # host->device KV writes must be ordered with model dispatches,
        # exactly like _apply_restores.
        self._pending_prewarms: List = []
        self._step_counter = 0
        self._new_work = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._running = False
        # Dispatch-pipeline telemetry (the overlap win must be observable,
        # not asserted): per-kind dispatch counts, how many fetches ran with
        # another dispatch still outstanding (overlap), and the cumulative
        # host-observed gap during which NOTHING was outstanding on device
        # between two dispatches (pipeline bubble).
        self.decode_dispatches_total = 0
        self.prefill_dispatches_total = 0
        self.fetches_total = 0
        self.overlapped_fetches_total = 0
        self.dispatch_gap_seconds_total = 0.0
        self._last_fetch_done: Optional[float] = None
        # Live roofline telemetry (docs/OBSERVABILITY.md fleet pane): a
        # rolling window of per-dispatch accounting tuples
        # (fetch_done_mono, issue->fetch seconds, train kind, tokens
        # emitted, target-model steps) appended at fetch from timestamps
        # the loop already takes host-side — zero new device syncs. The
        # pstpu:live_* gauges are derived from it on demand in stats().
        self._dispatch_window: deque = deque(maxlen=256)
        # Host-stall component of the pipeline bubble: fetch-done ->
        # next issue-START gap (dispatch_gap_seconds_total measures to
        # AFTER execute_async returns, so it folds compile time in; this
        # one isolates the host's own scheduling stall).
        self.host_stall_seconds_total = 0.0
        # Loop spans (flight_recorder.LoopSpans): the loop's phases as
        # annotations on the profiler's clock, and the per-phase seconds
        # that tile the loop's wall time (pstpu:loop_*_seconds_total).
        from production_stack_tpu.engine.flight_recorder import (
            LoopSpans,
            compile_clock,
        )

        self.loop_spans = LoopSpans()
        # Decode work counted where it happens (host integers the loop
        # holds; no device sync), all at APPLY so that over any
        # window row-steps less wasted row-steps is exactly the tokens
        # decode delivered. A STEP is one iteration of the fused decode
        # loop the device ran: the while_loop stops at the largest per-row
        # budget, the scan runs all K. A ROW-STEP is one real row in one
        # such step (padding rows of the shape bucket are not rows; a
        # BUCKET row-step counts them too: what a program that works on
        # every row of its shapes pays for). A row-step is WASTED when its
        # token was not delivered: the row hit EOS / max_tokens / a stop
        # string earlier in the train, was aborted or preempted, failed
        # the epoch check, or its fetch failed. Under a speculative mode a
        # step is one draft/verify cycle of the K the dispatch is allowed
        # (the host cannot see an early exit) and a row-step can deliver
        # up to N+1 tokens, so wasted is clamped at 0 there and says
        # little: read pstpu:spec_*.
        self.decode_steps_total = 0
        self.decode_row_steps_total = 0
        self.decode_bucket_row_steps_total = 0
        self.decode_row_steps_wasted_total = 0
        # Steps of an applied decode dispatch that NO row used: executed
        # steps less the most tokens one row delivered (every step of a
        # failed dispatch; 0 where a row ran the whole train). A step
        # costs the device the same at 2 rows as at 20, so this, not the
        # wasted row-steps, is the device time at stake; dispatch by
        # dispatch, empty steps x rows <= wasted row-steps.
        self.decode_steps_empty_total = 0
        # The hand-off from prefill to decode, counted at ISSUE: rows a
        # decode dispatch took for the first time since their last prompt
        # chunk, and those of them whose first token was still in the
        # in-flight prefill's device vector (they ride the train issued
        # right behind their prefill; the rest waited out its apply).
        self.decode_rows_first_total = 0
        self.decode_rows_joined_total = 0
        # What a prefill dispatch carried, counted at ISSUE beside
        # prefill_dispatches_total (the issue span carries the same
        # numbers): the tokens really prefilled, the shape the program
        # computes (utils.prefill_rectangle: rows x T, ONE row where the
        # sequences' chunks are packed end to end), the sequences it
        # carried, those of them that lay as segments of a packed row
        # (0 while every dispatch is a rectangle), the requests the
        # admission pass left waiting, and the limit that stopped it
        # (scheduler.PREFILL_STOPS; the scheduler's blocked passes are
        # added in stats()).
        self.prefill_tokens_issued_total = 0
        self.prefill_tokens_padded_total = 0
        self.prefill_rows_issued_total = 0
        self.prefill_segments_total = 0
        self.prefill_left_waiting_total = 0
        self.prefill_stops: Dict[str, int] = dict.fromkeys(PREFILL_STOPS, 0)
        # Keys the attention layers' queries see, exact on the host
        # (``_attn_keys``): summed over layers, for every prefill token at
        # issue and every delivered decode row-step at apply; ``held`` is
        # the same with no layer bounded (what the one block table keeps).
        # Both stay 0 for a model without a bounded layer.
        self.attn_keys_in_span_total = 0
        self.attn_keys_held_total = 0
        # Keys the window layers' rings hold for the sequences of every
        # delivered decode row-step (min(context, window) a layer), and the
        # keys of those sequences' contexts (what one pool would hold for
        # the same layers); both 0 for a model without a ring.
        self.ring_keys_held_total = 0
        self.ring_keys_context_total = 0
        # Compiles and persistent-cache loads WHILE SERVING
        # (flight_recorder.CompileClock): the process's clock, and its
        # reading when start() ended (warm-up's own work is
        # pstpu:startup_*'s).
        self._compile_clock = compile_clock()
        self._compiles_at_start = self._compile_clock.reading()
        # telemetry
        from production_stack_tpu.engine.metrics import (
            DispatchDurationHistograms,
            HttpSurfaceHistograms,
            LifecycleHistograms,
            RequestLatencyHistograms,
        )

        # Per-request flight recorder (docs/OBSERVABILITY.md): a bounded
        # in-memory ring of event timelines appended from the dispatch
        # points below (O(1) list appends, no syscalls) and served at
        # GET /debug/requests/{id}. None when --no-debug-endpoints.
        self.recorder = None
        if config.debug_endpoints:
            from production_stack_tpu.engine.flight_recorder import (
                FlightRecorder,
            )

            self.recorder = FlightRecorder(
                capacity=config.flight_recorder_capacity,
                max_events=config.flight_recorder_max_events,
            )
        # Per-phase latency histograms (always on — pure in-memory
        # observes): queue wait, prefill, decode trains, restores.
        self.lifecycle = LifecycleHistograms()
        # Per-train issue->fetch duration histograms (prefill / decode /
        # decode_spec), observed at fetch from the handle's issue stamp.
        self.dispatch_hists = DispatchDurationHistograms()
        # The HTTP surface's own time (server/api_server.py observes):
        # handler entry -> Sequence enqueued, first token -> first chunk
        # handed to the transport.
        self.http_surface = HttpSurfaceHistograms()
        self.scheduler.on_preempt = self._on_preempt
        self.scheduler.on_restore = self._on_restore
        self.start_time = time.monotonic()
        self.prompt_tokens_total = 0
        self.generation_tokens_total = 0
        # Mid-stream resume telemetry (docs/RESILIENCE.md): prompt+resume
        # tokens a resume request served from the device prefix cache or
        # the host/remote KV tiers instead of recomputing.
        self.resume_restored_tokens_total = 0
        self.last_step_time = time.monotonic()
        # TTFT + e2e latency histograms (the reference dashboard's two
        # distribution panels chart these exact series — VERDICT r4 #5).
        self.histograms = RequestLatencyHistograms()
        self._ttft_recorded: Set[str] = set()

    # --------------------------------------------------------------- lifecycle
    async def start(self) -> None:
        if self._running:
            return
        loop = asyncio.get_running_loop()
        if self.config.enable_warmup:
            await loop.run_in_executor(None, self.runner.warmup)
        else:
            # Overlapped weight loading without warmup: join here so the
            # engine never reports healthy with weights still in flight.
            await loop.run_in_executor(None, self.runner.wait_for_weights)
        # What is resident now that warm-up is over, and the line between
        # the memory ledger's two phases (a rise past here is serving's).
        await loop.run_in_executor(None, self.runner.build_memory_ledger)
        self.startup_total_seconds = time.monotonic() - self._startup_t0
        self._compiles_at_start = self._compile_clock.reading()
        self._running = True
        self._loop_task = asyncio.create_task(self._run_loop())
        dev = self.device_report()
        logger.info(
            "Engine started: model=%s kv_blocks=%d block_size=%d attn=%s "
            "mesh=%s platform=%s device_kind=%r devices=%d ids=%s; ready "
            "%.1fs after construction began (weights %.1fs, AOT prepass "
            "%.1fs, warm-up %.1fs)",
            self.config.model_name, self.runner.num_kv_blocks,
            self.config.block_size, self.runner.attn_impl,
            dict(self.mesh.shape), dev["platform"], dev["kind"],
            dev["count"], dev["ids"], self.startup_total_seconds,
            self.runner.startup_weight_load_seconds,
            self.runner.startup_compile_seconds,
            self.runner.startup_warmup_seconds,
        )

    async def stop(self) -> None:
        self._running = False
        self._new_work.set()
        if self._loop_task:
            await self._loop_task
            self._loop_task = None
        if self.offload is not None:
            self.offload.close()
        if self.disagg is not None:
            self.disagg.close()

    @property
    def offload_blocks_resident(self) -> int:
        """KV blocks currently resident in the host offload pool — the live
        count behind the pstpu:kv_offload_blocks gauge on BOTH metrics
        renderers (a stored counter here drifted to a permanent 0)."""
        if self.offload is None or self.offload.host_pool is None:
            return 0
        return self.offload.host_pool.stats()["entries"]

    @property
    def is_healthy(self) -> bool:
        return self._running and (
            self._loop_task is not None and not self._loop_task.done()
        )

    def active_request_ids(self) -> List[str]:
        """Request ids with a live output stream (drain/abort bookkeeping)."""
        return list(self._streams)

    # ----------------------------------------------------------------- intake
    async def generate(
        self,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[List[int]] = None,
        sampling: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        lora_adapter: Optional[str] = None,
        handoff_key: Optional[str] = None,
        handoff_state=None,
        disagg_fallback: bool = False,
        resume_tokens: Optional[List[int]] = None,
        resume_seed: Optional[int] = None,
    ) -> AsyncIterator[RequestOutput]:
        """Submit a request; yields streaming RequestOutput deltas.
        ``lora_adapter`` selects a registered adapter by name (None = base).

        Disagg hops (docs/DISAGG.md): ``handoff_key`` makes this the
        PREFILL hop — the prompt is prefilled, token 1 sampled, KV + chain
        state published under the key, and the stream finishes with reason
        "handoff". ``handoff_state`` (a HandoffManifest) makes this the
        DECODE hop — the published KV is rehydrated into the local pool and
        the stream continues from token 1 with no recompute.
        ``disagg_fallback`` marks router-flagged degrade-to-unified traffic
        so a role-split scheduler admits both phases for it.

        Mid-stream resume (docs/RESILIENCE.md): ``resume_tokens`` are
        output tokens a previous engine already produced (and delivered)
        before dying mid-stream. The sequence enters the normal prefill
        path with prompt+resume_tokens as its token chain — the prefix
        cache / host pool / shared tier restore whatever is resident and
        only the missing delta is recomputed — and decoding continues at
        generation index len(resume_tokens). With ``resume_seed`` (the
        original engine's resolved seed base, from its per-chunk resume
        payload) the continuation is token-identical to the uninterrupted
        run; stop strings are evaluated over the JOINED text, with the
        already-delivered region's holdback reconstructed exactly."""
        request_id = request_id or random_uuid("req-")
        sampling = sampling or SamplingParams()
        if (handoff_key or handoff_state is not None) and self.disagg is None:
            raise ValueError(
                "disagg handoff requested but this engine has no coordinator "
                "(--role unified)"
            )
        if (handoff_key or handoff_state is not None) and lora_adapter:
            raise ValueError("disagg handoff does not support LoRA adapters")
        if resume_tokens:
            if handoff_key or handoff_state is not None:
                raise ValueError(
                    "resume_tokens cannot be combined with a disagg handoff"
                )
            if len(resume_tokens) >= sampling.max_tokens:
                # An honest caller never resumes a finished stream; admitting
                # this would sample one token PAST max_tokens (the prefill's
                # final chunk always samples).
                raise ValueError(
                    f"resume_tokens ({len(resume_tokens)}) must be shorter "
                    f"than max_tokens ({sampling.max_tokens})"
                )
            if resume_seed is not None:
                from dataclasses import replace

                # The original engine's RESOLVED seed base: _seed_base then
                # reproduces the exact per-token seed schedule even for
                # requests that never carried an explicit seed.
                sampling = replace(sampling, seed=int(resume_seed))

        if handoff_state is not None:
            async for out in self._generate_from_handoff(
                handoff_state, sampling, request_id
            ):
                yield out
            return

        if prompt_token_ids is None:
            assert prompt is not None
            prompt_token_ids = self.tokenizer.encode(prompt)
        if not prompt_token_ids:
            prompt_token_ids = [self.tokenizer.eos_token_id or 0]
        adapter_idx = 0
        if lora_adapter is not None:
            if self.lora_registry is None:
                raise ValueError("no LoRA adapters are registered")
            adapter_idx = self.lora_registry.adapter_index(lora_adapter)
        seq = Sequence(
            request_id=request_id,
            prompt_token_ids=list(prompt_token_ids),
            sampling=sampling,
            eos_token_id=self.tokenizer.eos_token_id,
            adapter_idx=adapter_idx,
            adapter_name=lora_adapter if adapter_idx else None,
            handoff_key=handoff_key,
            # A resumed request must be locally servable end-to-end on any
            # role (the original handoff/affinity state died with its
            # engine), so it rides the same admission override as
            # router-flagged fallback traffic.
            disagg_fallback=disagg_fallback or bool(resume_tokens),
        )
        state = _StreamState(
            queue=asyncio.Queue(), detok=IncrementalDetokenizer(self.tokenizer)
        )
        if resume_tokens:
            # Pre-seed the already-produced tokens WITHOUT _append_token
            # (they were already checked for EOS/stop upstream — the stream
            # was interrupted, not finished) and rebuild the emission state
            # the dead engine had: text = detok(resume_tokens), sent = the
            # deterministic emit boundary (len - stop holdback). Both are
            # pure functions of the token list, so the continuation's first
            # delta starts EXACTLY where the delivered stream stopped — the
            # router splices with no byte overlap, and a stop match spanning
            # the splice is still found by the delta scan (its window
            # reaches max_stop chars back into the held-back region).
            seq.output_token_ids = list(resume_tokens)
            seq.resume_base = len(resume_tokens)
            if sampling.logprobs is not None:
                # Alignment padding: logprobs for the resumed region were
                # delivered by the original engine and are not recomputed.
                seq.output_logprobs = [None] * len(resume_tokens)
            pre = state.detok.step(list(resume_tokens))
            state.text = pre
            hold = max((len(s) for s in sampling.stop), default=1) - 1 \
                if sampling.stop else 0
            state.sent = max(len(pre) - hold, 0)
        self._streams[request_id] = state
        self.scheduler.add_sequence(seq)
        if self.recorder is not None:
            self.recorder.start(
                request_id, prompt_tokens=len(prompt_token_ids),
            )
            self.recorder.event(request_id, "enqueue", {
                "prompt_tokens": len(prompt_token_ids),
            })
            if resume_tokens:
                self.recorder.event(request_id, "resume", {
                    "resume_tokens": len(resume_tokens),
                })
        self.prompt_tokens_total += len(prompt_token_ids)
        self._new_work.set()
        try:
            while True:
                out: RequestOutput = await state.queue.get()
                yield out
                if out.finished:
                    break
        finally:
            self._streams.pop(request_id, None)
            if not seq.status.is_finished:
                self.abort(request_id)

    async def _generate_from_handoff(
        self, mani, sampling: SamplingParams, request_id: str
    ) -> AsyncIterator[RequestOutput]:
        """Decode hop: continue a stream from a consumed transfer bundle.

        Finished bundles (the prefill engine hit EOS/max_tokens/stop at
        token 1) replay the recorded result verbatim — stop-trim corner
        cases are not re-derived. Live bundles enqueue a restore the engine
        loop applies between device steps (KV write ordering)."""
        if mani.finish_reason is not None:
            # Token counters are NOT bumped here: the prefill engine already
            # counted this request's prompt + replayed tokens; counting them
            # again would double-book fleet-wide token totals.
            yield RequestOutput(
                request_id=request_id,
                text_delta=mani.final_text or "",
                token_ids=list(mani.output_token_ids),
                finished=True,
                finish_reason=mani.finish_reason,
                num_prompt_tokens=len(mani.prompt_token_ids),
                num_output_tokens=len(mani.output_token_ids),
                num_cached_tokens=mani.num_computed_tokens,
                logprobs=(
                    list(mani.output_logprobs)
                    if sampling.logprobs is not None
                    and mani.output_logprobs is not None else None
                ),
            )
            return
        if mani.block_size != self.config.block_size:
            raise ValueError(
                f"handoff block_size {mani.block_size} != engine block_size "
                f"{self.config.block_size} (pools must share the KV layout)"
            )
        if mani.kv_cache_dtype != self.config.kv_cache_dtype:
            # Mixed-dtype role pools must not splice KV: the decode engine
            # would reconstruct different values than the prefill engine
            # computed. Rejecting here surfaces a retryable failure the
            # router degrades to unified serving.
            raise ValueError(
                f"handoff kv_cache_dtype {mani.kv_cache_dtype!r} != engine "
                f"kv_cache_dtype {self.config.kv_cache_dtype!r} (role-split "
                f"pools must share --kv-cache-dtype)"
            )
        bs = self.config.block_size
        need = mani.num_blocks
        if (
            need > self.block_manager.num_blocks - 1
            or len(mani.prompt_token_ids) >= self.config.max_model_len
        ):
            raise ValueError(
                "handoff bundle exceeds this engine's KV pool / max_model_len"
            )
        if need * bs < mani.num_computed_tokens:
            raise ValueError("handoff bundle is missing KV blocks")
        seq = Sequence(
            request_id=request_id,
            prompt_token_ids=list(mani.prompt_token_ids),
            sampling=sampling,
            eos_token_id=self.tokenizer.eos_token_id,
            # A restored row preempted under KV pressure is requeued as a
            # recompute-by-prefill candidate; the transfer lease is already
            # consumed, so local end-to-end serving is its ONLY path — the
            # fallback flag keeps the decode-role prefill-admission gate
            # from starving it forever.
            disagg_fallback=True,
        )
        state = _StreamState(
            queue=asyncio.Queue(), detok=IncrementalDetokenizer(self.tokenizer)
        )
        self._streams[request_id] = state
        # Registered before the restore applies so a client disconnect while
        # queued aborts cleanly (scheduler.abort finds the sequence).
        self.scheduler.seqs[request_id] = seq
        if self.recorder is not None:
            self.recorder.start(
                request_id, prompt_tokens=len(mani.prompt_token_ids),
            )
            self.recorder.event(request_id, "enqueue", {
                "prompt_tokens": len(mani.prompt_token_ids),
                "disagg_decode_hop": True,
            })
        self._pending_restores.append((seq, mani))
        # prompt_tokens_total deliberately not bumped: the prefill engine
        # already counted this prompt (fleet-wide sums must not double-book
        # a disagg request's tokens).
        self._new_work.set()
        try:
            while True:
                out: RequestOutput = await state.queue.get()
                yield out
                if out.finished:
                    break
        finally:
            self._streams.pop(request_id, None)
            if not seq.status.is_finished:
                self.abort(request_id)

    async def embed(self, texts: List[str]):
        """Embed texts (mean-pooled trunk states). Returns (vectors [n, D]
        float32 numpy, total prompt tokens). Runs off-loop; does not touch
        the KV pool, so it is safe alongside in-flight generate steps."""
        loop = asyncio.get_running_loop()
        token_lists = [
            (self.tokenizer.encode(t) or [self.tokenizer.eos_token_id or 0])[
                : self.config.max_model_len
            ]
            for t in texts
        ]
        vecs = await loop.run_in_executor(None, self.runner.embed, token_lists)
        n_tokens = sum(len(t) for t in token_lists)
        self.prompt_tokens_total += n_tokens
        return vecs, n_tokens

    def abort(self, request_id: str) -> None:
        """Deferred abort: applied by the engine loop between device steps."""
        self._pending_aborts.add(request_id)
        self._new_work.set()

    # ------------------------------------------------------- observability
    def _on_preempt(self, request_id: str) -> None:
        if self.recorder is not None:
            self.recorder.event(request_id, "preempt")

    def _on_restore(self, request_id: str, tokens: int,
                    seconds: float) -> None:
        # Shared-tier I/M restore round trip (docs/KV_ECONOMY.md pipeline):
        # histogram + flight-record event, both from the engine loop.
        self.lifecycle.restore_round_trip.observe(seconds)
        if self.recorder is not None:
            self.recorder.event(request_id, "restore", {
                "tokens": tokens, "seconds": round(seconds, 6),
            })

    def _record_issue(self, batch, step: int, t_wall: float,
                      t_mono: float, compiled: float = 0.0,
                      hbm_rise: Optional[dict] = None) -> None:
        """Dispatch-issue anchor: close each fresh row's queue-wait phase
        and append the per-request issue event. O(rows) in-memory appends
        on the engine loop — no syscalls (PL008-clean: host-side only).

        ``t_wall``/``t_mono`` are captured BEFORE the runner's issue call:
        a cold shape family compiles for seconds inside it, and that time
        belongs to the dispatch's phase (issue -> fetch), not to an
        unattributed gap between phases — the phase spans must tile the
        request duration. ``compiled``: the seconds of that kind this
        issue really held (flight_recorder.annotated_issue), on each row's
        ``*_issue`` event where it is not 0; ``hbm_rise``: where the read
        after this enqueue found the allocator's peak higher, by how much
        and what of it is unexplained (MemoryLedger.rise_at)."""
        rec = self.recorder
        stalled = {"compiled": compiled} if compiled else {}
        if hbm_rise:
            stalled["hbm_rise"] = hbm_rise
        # Rows of the dispatch that carry recurrent state through it (every
        # real row of a model that declares some; absent otherwise).
        state_rows = {"state_rows": sum(
            1 for s in batch.seqs if s.state_slot)} \
            if self.block_manager.num_state_slots else {}
        # A packed prefill: how many sequences' chunks share its one row.
        segments = {"segments": len(batch.seqs)} \
            if batch.kind == "prefill" and batch.packed else {}
        for idx, seq in enumerate(batch.seqs):
            if seq.first_issue_time is None:
                seq.first_issue_time = t_mono
                self.lifecycle.queue_wait.observe(t_mono - seq.arrival_time)
                if rec is not None:
                    rec.event(seq.request_id, "schedule", {
                        "wait_s": round(t_mono - seq.arrival_time, 6),
                    }, t=t_wall)
            if rec is None:
                continue
            if batch.kind == "prefill":
                rec.event(seq.request_id, "prefill_issue", {
                    "step": step, "chunk": batch.chunk_lens[idx],
                    "start": batch.chunk_starts[idx], **segments,
                    **state_rows, **stalled,
                }, t=t_wall)
            else:
                data = {
                    "step": step, "rows": len(batch.seqs),
                    "k": batch.num_steps, "joined": batch.joined_rows,
                    **state_rows, **stalled,
                }
                if getattr(batch, "spec_mode", "off") != "off":
                    # Which speculative variant the runner actually
                    # dispatched (linear/tree/adaptive/off-degrade) —
                    # gamma=0 degradation is invisible in token counts
                    # alone.
                    data["spec_mode"] = batch.spec_mode
                rec.event(seq.request_id, "decode_issue", data, t=t_wall)

    def _record_fetch(self, batch, step: int, token_lists,
                      issue_time: float, spec_accepted_delta: int,
                      spec_drafts_delta: int = 0) -> None:
        """Dispatch-fetch anchor: per-train decode cadence histogram +
        per-request fetch events (tokens emitted, spec acceptance)."""
        now = time.monotonic()
        rec = self.recorder
        # The read after this sync found the allocator's peak higher.
        hbm_rise = self.runner.memory.rise_at(step, "fetch") \
            if rec is not None else None
        risen = {"hbm_rise": hbm_rise} if hbm_rise else {}
        if batch.kind == "decode":
            self.lifecycle.decode_train.observe(now - issue_time)
        for idx, seq in enumerate(batch.seqs):
            if batch.kind == "prefill":
                final = bool(batch.finals[idx]) if batch.finals else False
                if final and seq.first_issue_time is not None:
                    self.lifecycle.prefill.observe(
                        now - seq.first_issue_time
                    )
                if rec is not None:
                    rec.event(seq.request_id, "prefill_fetch", {
                        "step": step, "final": final,
                        "cached_tokens": seq.num_cached_tokens, **risen,
                    })
            elif rec is not None:
                data = {
                    "step": step,
                    "tokens": len(token_lists[idx])
                    if idx < len(token_lists) else 0,
                    "ms": round((now - issue_time) * 1000, 2), **risen,
                }
                if spec_accepted_delta:
                    # Explicitly BATCH-level: the device commits
                    # acceptance per dispatch, not per row — summing this
                    # across requests of one batch would overcount, so
                    # the key says so.
                    data["spec_accepted_batch"] = spec_accepted_delta
                if spec_drafts_delta:
                    # Drafted alongside accepted: the pair gives a
                    # per-dispatch acceptance ratio in the recorder
                    # timeline (adaptive gamma makes the denominator
                    # variable — accepted alone no longer implies it).
                    data["spec_drafts_batch"] = spec_drafts_delta
                rec.event(seq.request_id, "decode_fetch", data)

    # ----------------------------------------------------------- fast-start
    async def prewarm(self, top_k: int = 8, max_blocks: int = 256) -> dict:
        """Pull the shared tier's hottest prefix chains into the device
        prefix cache (POST /prewarm, docs/ELASTIC.md). Queued for the
        engine loop so the device KV writes are ordered with model
        dispatches; resolves with the pull's telemetry. Degrades to a
        no-op result (never an exception) without a shared tier."""
        if self.offload is None or self.offload.remote is None:
            return {"chains": 0, "blocks": 0,
                    "reason": "no shared tier configured (LMCACHE_REMOTE_URL"
                              " / --kv-remote-url)"}
        if not self._running:
            return {"chains": 0, "blocks": 0, "reason": "engine not running"}
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._pending_prewarms.append(
            ({"top_k": int(top_k), "max_blocks": int(max_blocks)}, fut)
        )
        self._new_work.set()
        return await fut

    async def _apply_prewarms(self) -> None:
        """Serve queued prewarm pulls between device steps (same ordering
        discipline as _apply_restores: the loop awaits the executor-run
        store fetch + device scatter, so no dispatch is issued
        concurrently)."""
        loop = asyncio.get_running_loop()
        pending, self._pending_prewarms = self._pending_prewarms, []
        for req, fut in pending:
            t0 = time.monotonic()
            try:
                res = await loop.run_in_executor(
                    None, self.offload.prewarm_hot_chains,
                    req["top_k"], req["max_blocks"],
                )
            except Exception as e:  # noqa: BLE001 — loop must survive
                logger.exception("Prewarm pull failed")
                res = {"chains": 0, "blocks": 0,
                       "reason": f"prewarm failed: {e}"}
            res["seconds"] = round(time.monotonic() - t0, 4)
            self.startup_prewarm_seconds += res["seconds"]
            self.prewarmed_blocks_total += res.get("blocks", 0)
            if not fut.done():
                fut.set_result(res)

    # ------------------------------------------------------------ engine loop
    def _attn_keys(self, starts, lengths) -> Tuple[int, int]:
        """(keys inside their layer's span, keys held) that runs of
        ``lengths`` tokens at positions ``starts`` on see, summed over the
        model's layers: the closed form of ops/attention.py:keys_in_span.
        (0, 0) for a model without a bounded layer."""
        spans = self.runner.layer_spans
        if spans is None:
            return 0, 0
        kinds, counts = np.unique(spans, return_counts=True)
        seen = sum(int(n) * int(keys_in_span(starts, lengths, kind).sum())
                   for kind, n in zip(kinds, counts))
        return seen, len(spans) * int(
            keys_in_span(starts, lengths, NO_SPAN).sum())

    def _count_decode(self, batch, delivered: int) -> None:
        """One applied decode dispatch into the step and row counters
        (defined in __init__); ``delivered`` tokens reached its rows, the
        most one row took is on the batch (``delivered_max``)."""
        steps = batch.num_steps
        if self.config.decode_loop != "scan" and batch.spec_mode == "off" \
                and batch.decode_steps:
            steps = min(steps, max(batch.decode_steps))
        row_steps = steps * len(batch.seqs)
        self.decode_steps_total += steps
        self.decode_row_steps_total += row_steps
        self.decode_bucket_row_steps_total += \
            steps * self.runner.decode_bucket(len(batch.seqs))
        self.decode_row_steps_wasted_total += max(0, row_steps - delivered)
        self.decode_steps_empty_total += max(0, steps - batch.delivered_max)

    async def _run_loop(self) -> None:
        """Two-slot pipelined dispatch loop (config.async_pipeline /
        config.pipeline_depth / config.overlap_dispatch).

        Each iteration FILLS the free dispatch slots — issuing is cheap
        (enqueue only, no device sync) — and only then FETCHES the oldest
        outstanding dispatch's tokens, so the blocking per-dispatch
        device->host sync overlaps the newer dispatches' execution. With
        overlap_dispatch the two slots can hold
        DIFFERENT kinds at once: a scheduling round produces a prefill
        batch and a decode batch when both are admissible, so a fresh
        prompt's prefill is issued while a fused decode scan is still in
        flight (it no longer waits out the scan behind a single slot) and
        decode keeps its cadence through a long prompt's chunk train
        (Sarathi-style stall-free batching).

        The scheduler's state is advanced speculatively at issue
        (advance_at_issue) and tokens are delivered at fetch
        (apply_results), strictly in issue order; rows that finish or get
        preempted while a dispatch is in flight simply discard its tokens
        for them (epoch check), and a chained dispatch's start tokens ride
        ONE device-resident last-token vector. That single source rests on
        this loop's depth and on nothing in the scheduler: see the clamp
        below. A row whose last prompt chunk is in flight therefore joins
        the decode train issued right behind it (it chains its start token
        from the prefill's vector) and does not sit out a whole train
        between its first and second token."""
        loop = asyncio.get_running_loop()
        cfg = self.config
        # Clamped to 2, and the single-source rule of token chaining (a
        # decode program takes ONE prev_last vector) rests on it: a decode
        # is issued only while fewer than `depth` dispatches are in flight,
        # so at most ONE other dispatch is unapplied then; fetches apply in
        # issue order, so every older dispatch's tokens are on the host
        # (or its rows aborted). Whatever that one dispatch is — a decode,
        # or a prefill whose final rows the scheduler now hands straight to
        # the next decode — it is the only vector a row's start token can
        # still live in. The penalty drain() only empties the pipeline
        # further; restores and prewarms add rows whose tokens are on the
        # host. runner._issue_decode checks it (its two RuntimeErrors). At
        # depth >= 3 a third decode could need chains from TWO unapplied
        # dispatches at once (a row the window budget skipped in the middle
        # one, or a prefill behind a decode) — and a device queue of 2
        # already hides the host round-trip.
        depth = max(1, min(2, cfg.pipeline_depth)) if cfg.async_pipeline \
            else 1
        if cfg.speculative_num_tokens:
            # Speculative dispatches emit a VARIABLE token count, so the
            # scheduler cannot advance state speculatively past an
            # unfetched dispatch (positions/block tables would assume the
            # full budget). Strict issue-fetch-apply ordering; the fused
            # draft/verify scan amortizes the round-trip over up to
            # K*(N+1) tokens instead (docs/PERF.md round 8).
            depth = 1
        overlap = cfg.overlap_dispatch and depth >= 2
        in_flight: deque = deque()  # (batch, step_id, DispatchHandle) FIFO
        from production_stack_tpu.engine import flight_recorder

        # The two executor-side parts of a dispatch read the allocator
        # once each for the runner's memory ledger.
        memory = self.runner.memory
        annotated = functools.partial(flight_recorder.annotated,
                                      memory=memory)
        annotated_issue = functools.partial(flight_recorder.annotated_issue,
                                            memory=memory)
        loop_span = self.loop_spans

        def abort_batch(batch):
            for seq in batch.seqs:
                aborted = self.scheduler.abort(seq.request_id)
                if aborted is not None:
                    self._process_output(aborted)

        async def apply_oldest():
            batch, step, handle = in_flight.popleft()
            self.fetches_total += 1
            if in_flight:
                # Another dispatch executes while this fetch blocks: the
                # round-trip is hidden.
                self.overlapped_fetches_total += 1
            spec0 = (self.runner.spec_accepted_tokens_total
                     if cfg.speculative_num_tokens else 0)
            spec_d0 = (self.runner.spec_draft_tokens_total
                       if cfg.speculative_num_tokens else 0)
            # `sync`: whether this fetch blocks on the device at all (a
            # prefill dispatch no row of which ended its prompt fetches
            # nothing) — the capture's reader pairs only those.
            sync = int(batch.kind == "decode" or any(batch.finals))
            failed = False
            with loop_span("pstpu.fetch", step=step, kind=batch.kind,
                           sync=sync):
                try:
                    tokens, lps = await loop.run_in_executor(
                        None, annotated, "pstpu.fetch.sync", step,
                        handle.fetch,
                    )
                except Exception:  # noqa: BLE001 — engine loop must survive
                    logger.exception("Dispatch fetch failed; aborting batch")
                    failed = True
            with loop_span("pstpu.apply", step=step, kind=batch.kind):
                if failed:
                    abort_batch(batch)
                    self._last_fetch_done = time.monotonic()
                    if batch.kind == "decode":
                        self._count_decode(batch, 0)
                    return
                self._record_fetch(
                    batch, step, tokens, handle.issue_time,
                    (self.runner.spec_accepted_tokens_total - spec0)
                    if cfg.speculative_num_tokens else 0,
                    (self.runner.spec_draft_tokens_total - spec_d0)
                    if cfg.speculative_num_tokens else 0,
                )
                self.last_step_time = self._last_fetch_done = \
                    time.monotonic()
                rings = self.runner.ring_layers \
                    if batch.kind == "decode" else 0
                bounded = batch.kind == "decode" \
                    and self.runner.layer_spans is not None
                if bounded or rings:
                    # The query of output token j sits at position
                    # prompt + j - 1.
                    before = [s.num_prompt_tokens + len(s.output_token_ids)
                              - 1 for s in batch.seqs]
                produced, accepted = self.scheduler.apply_results(
                    batch, tokens, lps
                )
                self.generation_tokens_total += accepted
                if bounded or rings:
                    after = [s.num_prompt_tokens + len(s.output_token_ids)
                             - 1 for s in batch.seqs]
                if rings:
                    steps = np.subtract(after, before)
                    self.ring_keys_held_total += rings * int(keys_in_span(
                        before, steps,
                        self.model_config.sliding_window).sum())
                    self.ring_keys_context_total += rings * int(
                        keys_in_span(before, steps, NO_SPAN).sum())
                if bounded:
                    keys_seen, keys_held = self._attn_keys(
                        before, np.subtract(after, before))
                    self.attn_keys_in_span_total += keys_seen
                    self.attn_keys_held_total += keys_held
                if batch.kind == "decode":
                    self._count_decode(batch, accepted)
                # Live roofline accounting (stats() folds the window into
                # the pstpu:live_* gauges): all values below are host-side
                # reads the loop already has — no device sync.
                # target_steps counts the target model's scan steps a
                # decode train ran, so emitted/target_steps is the
                # Leviathan'23 amortization factor (>1 only when
                # speculation pays).
                train = ("prefill" if batch.kind != "decode"
                         else "decode_spec" if batch.spec_mode != "off"
                         else "decode")
                duration = self._last_fetch_done - handle.issue_time
                target_steps = (len(batch.seqs) * batch.num_steps
                                if batch.kind == "decode" else 0)
                self.dispatch_hists.observe(train, duration)
                self._dispatch_window.append(
                    (self._last_fetch_done, duration, train, accepted,
                     target_steps)
                )
                for seq in produced:
                    self._process_output(seq)
                await self._publish_handoffs(produced)

        async def drain():
            while in_flight:
                await apply_oldest()

        def next_batch():
            if not overlap:
                return self.scheduler.schedule()
            kinds = {b.kind for b, _, _ in in_flight}
            # Balance the slots across kinds: with a prefill already in
            # flight, decode gets the free slot first (its streams must not
            # stall behind a chunk train); otherwise prefill-priority as
            # ever (TTFT). A single active kind still fills both slots.
            return self.scheduler.schedule(
                prefer_decode=("prefill" in kinds and "decode" not in kinds)
            )

        # The six loop_span phases below tile this loop's wall time: every
        # statement of an iteration is inside exactly one of them (spans
        # never nest; drain() between schedule and issue opens its own).
        while self._running:
            with loop_span("pstpu.housekeeping"):
                self._apply_pending_aborts()
                if self._pending_restores:
                    await self._apply_restores()
                if self._pending_prewarms:
                    await self._apply_prewarms()
            issue_failed = False
            while len(in_flight) < depth and not issue_failed:
                with loop_span("pstpu.schedule"):
                    batch = next_batch()
                if batch is None:
                    break
                # Penalty counts are built from APPLIED tokens; drain the
                # pipeline first so they are exact.
                if in_flight and any(
                    s.sampling.presence_penalty or s.sampling.frequency_penalty
                    for s in batch.seqs
                ):
                    await drain()
                step = self._step_counter
                self._step_counter += 1
                if batch.kind == "decode":
                    batch.joined_rows = sum(
                        s.pending_prefill_apply for s in batch.seqs)
                    carried = {"k": batch.num_steps,
                               "joined": batch.joined_rows}
                else:
                    # What the dispatch carries against the shape its
                    # program computes, and what stopped admission: the
                    # same numbers the counters below take.
                    tokens = sum(batch.chunk_lens)
                    prog_rows, prog_t = prefill_rectangle(
                        len(batch.seqs), max(batch.chunk_lens), cfg,
                        tokens if batch.packed else None)
                    segments = len(batch.seqs) if batch.packed else 0
                    keys_seen, keys_held = self._attn_keys(
                        batch.chunk_starts, batch.chunk_lens)
                    carried = {
                        "k": max(batch.chunk_lens), "tokens": tokens,
                        "prog_rows": prog_rows, "prog_t": prog_t,
                        "segments": segments,
                        "left": batch.left_waiting, "stop": batch.stop,
                        **({"keys_in_span": keys_seen,
                            "keys_held": keys_held} if keys_held else {}),
                    }
                with loop_span(
                    "pstpu.issue", step=step, kind=batch.kind,
                    rows=len(batch.seqs), **carried,
                ):
                    # Captured BEFORE the issue call: a cold-shape compile
                    # inside execute_async belongs to this dispatch's phase
                    # interval (see _record_issue).
                    issue_wall, issue_mono = time.time(), time.monotonic()
                    try:
                        # Issue in the executor: normally enqueue-only
                        # (~ms), but a cold shape family compiles for
                        # seconds and a penalty batch builds [b, vocab]
                        # counts — neither may freeze the event loop (SSE,
                        # health). Runner state stays effectively
                        # single-threaded: issue and fetch are each awaited
                        # before the next runner call.
                        handle, compiled = await loop.run_in_executor(
                            None, annotated_issue, step,
                            self.runner.execute_async, batch, step,
                        )
                    except Exception:  # noqa: BLE001 — loop must survive
                        logger.exception(
                            "Dispatch issue failed; aborting batch")
                        abort_batch(batch)
                        issue_failed = True
                        break
                    if compiled:
                        logger.warning(
                            "Dispatch %d compiled or cache-loaded a "
                            "program while serving (%.3f s): %s rows=%d "
                            "%s; requests %s", step, compiled, batch.kind,
                            len(batch.seqs), carried,
                            [s.request_id for s in batch.seqs[:4]])
                    if not in_flight and self._last_fetch_done is not None:
                        self.dispatch_gap_seconds_total += (
                            time.monotonic() - self._last_fetch_done
                        )
                        # issue_mono predates execute_async, so this
                        # isolates the host's own stall from any compile
                        # inside issue.
                        self.host_stall_seconds_total += max(
                            0.0, issue_mono - self._last_fetch_done
                        )
                    if batch.kind == "decode":
                        self.decode_dispatches_total += 1
                        self.decode_rows_first_total += sum(
                            s.awaits_first_decode for s in batch.seqs)
                        self.decode_rows_joined_total += batch.joined_rows
                    else:
                        self.prefill_dispatches_total += 1
                        self.prefill_tokens_issued_total += tokens
                        self.prefill_tokens_padded_total += \
                            prog_rows * prog_t
                        self.prefill_rows_issued_total += len(batch.seqs)
                        self.prefill_segments_total += segments
                        self.attn_keys_in_span_total += keys_seen
                        self.attn_keys_held_total += keys_held
                        self.prefill_left_waiting_total += \
                            batch.left_waiting
                        if batch.stop != "none":
                            self.prefill_stops[batch.stop] += 1
                    self.scheduler.advance_at_issue(batch)
                    self._record_issue(batch, step, issue_wall, issue_mono,
                                       compiled,
                                       memory.rise_at(step, "issue"))
                    in_flight.append((batch, step, handle))
            if in_flight:
                # Applying may finish rows and free blocks, unblocking
                # admission — the next iteration re-schedules right after.
                await apply_oldest()
                with loop_span("pstpu.housekeeping"):
                    await asyncio.sleep(0)
                continue
            if issue_failed:
                continue
            with loop_span("pstpu.idle"):
                self._new_work.clear()
                # Idle: drop the persistent decode window so its (up to
                # window-budget-sized) device buffers don't pin HBM.
                self.runner._win_cache = None
                if not self.scheduler.has_work() \
                        and not self._pending_restores:
                    try:
                        await asyncio.wait_for(self._new_work.wait(),
                                               timeout=1.0)
                    except asyncio.TimeoutError:
                        pass
                else:
                    # Work exists but nothing schedulable (pool starved by
                    # in-flight requests) — yield and retry.
                    await asyncio.sleep(0.001)
        # Drain on shutdown so no accepted tokens are lost, and let
        # in-flight handoff publishes finish so accepted transfers reach
        # the store.
        for _req, fut in self._pending_prewarms:
            if not fut.done():
                fut.set_result({"chains": 0, "blocks": 0,
                                "reason": "engine stopping"})
        self._pending_prewarms.clear()
        await drain()
        if self._publish_tasks:
            await asyncio.gather(*list(self._publish_tasks),
                                 return_exceptions=True)

    def _apply_pending_aborts(self) -> None:
        while self._pending_aborts:
            rid = self._pending_aborts.pop()
            seq = self.scheduler.abort(rid)
            if seq is not None:
                self._process_output(seq)

    # --------------------------------------------------- disagg handoff plane
    async def _apply_restores(self) -> None:
        """Rehydrate queued decode-hop transfers into the local KV pool.

        Driven by the engine loop between device steps (same ordering
        discipline as offload.try_restore): blocks are allocated, the
        published KV is scattered in (the device write — a multi-MB
        transfer and possibly a first-use scatter compile — runs on the
        worker executor so SSE/health never freeze; the loop awaits it, so
        no dispatch is issued concurrently), the already-sampled tokens are
        replayed through the normal append path (EOS/max_tokens/stop-token
        semantics re-applied deterministically), and the row joins RUNNING —
        the next decode dispatch continues it with zero recompute. A pool
        too full to allocate right now re-queues the restore; aborted-while-
        queued rows are dropped; a restore that fails outright (geometry
        mismatch, corrupt blob, device error) aborts ONLY its own request —
        the engine loop must survive."""
        loop = asyncio.get_running_loop()
        pending, self._pending_restores = self._pending_restores, []
        leftover = []
        for seq, mani in pending:
            if seq.status.is_finished:
                continue  # aborted while queued
            try:
                blocks = (
                    self.block_manager.allocate_blocks(mani.num_blocks)
                    if mani.num_blocks else []
                )
                if blocks is None:
                    leftover.append((seq, mani))
                    continue
                # Assigned before the write so a failure path (or a later
                # abort) frees them through the normal _finish bookkeeping.
                seq.block_ids = blocks
                if mani.num_blocks:
                    await loop.run_in_executor(
                        None, self.runner.write_blocks, blocks, mani.k,
                        mani.v, mani.k_scale, mani.v_scale,
                    )
                seq.num_computed_tokens = mani.num_computed_tokens
                seq.num_cached_tokens = mani.num_computed_tokens
                seq.status = SequenceStatus.RUNNING
                if self.recorder is not None:
                    self.recorder.event(seq.request_id, "handoff_restore", {
                        "blocks": mani.num_blocks,
                        "tokens": mani.num_computed_tokens,
                    })
                self.scheduler.running.append(seq)
                for i, tok in enumerate(mani.output_token_ids):
                    lp = None
                    if mani.output_logprobs and i < len(mani.output_logprobs):
                        lp = mani.output_logprobs[i]
                    if seq.status.is_finished:
                        break  # defensive: same finish logic ran upstream
                    self.scheduler._append_token(seq, tok, lp)
                # Content-address the restored full blocks: later sessions
                # with the same prefix hit this engine's device cache
                # directly. (Replayed tokens are not added to
                # generation_tokens_total — the prefill engine counted them
                # at its apply.)
                self.scheduler._register_full_blocks(seq)
                self._process_output(seq)
            except Exception:  # noqa: BLE001 — engine loop must survive
                logger.exception("Handoff restore failed; aborting %s",
                                 seq.request_id)
                aborted = self.scheduler.abort(seq.request_id)
                if aborted is not None:
                    self._process_output(aborted)
        self._pending_restores.extend(leftover)

    async def _publish_handoffs(self, produced: List[Sequence]) -> None:
        """Prefill hop completion: rows that just produced their first
        token and carry a transfer key get a BACKGROUND publish task
        (device read + serialize + store put must not stall the dispatch
        pipeline — on a prefill-role engine that would serialize every
        prompt behind the previous one's network put). While the publish
        is in flight the row sits in RUNNING but is excluded from decode
        batches (handoff_key gate) and from preemption victims (its blocks
        are mid-read); on completion the row finishes (FINISHED_HANDOFF
        frees its blocks into the prefix cache) and the /disagg/prefill
        response is emitted. Publish failure aborts the row so the
        router's resilience layer retries or degrades to unified serving —
        a prefill-role engine never silently starts decoding."""
        if self.disagg is None:
            return
        for seq in produced:
            if seq.handoff_key is None or seq.handoff_done:
                continue
            if not seq.prefill_done:
                continue
            seq.handoff_done = True
            st = self._streams.get(seq.request_id)
            final_text = (
                st.text if (st is not None and seq.status.is_finished)
                else None
            )
            task = asyncio.ensure_future(self._publish_one(seq, final_text))
            self._publish_tasks.add(task)
            task.add_done_callback(self._publish_tasks.discard)

    async def _publish_one(self, seq: Sequence,
                           final_text: Optional[str]) -> None:
        loop = asyncio.get_running_loop()
        try:
            ok = await loop.run_in_executor(
                None, self.disagg.publish_handoff, seq, final_text
            )
        except Exception:  # noqa: BLE001 — publish must fail cleanly
            logger.exception("KV handoff publish task failed")
            ok = False
        if self.recorder is not None:
            self.recorder.event(seq.request_id, "handoff_publish",
                                {"ok": ok})
        # finish + emit run in ONE loop slice (no awaits), so the scheduler
        # never observes a half-finished handoff row.
        if not seq.status.is_finished:
            self.scheduler.finish(
                seq.request_id,
                SequenceStatus.FINISHED_HANDOFF if ok
                else SequenceStatus.FINISHED_ABORTED,
            )
        self._emit_handoff_output(seq)

    def _emit_handoff_output(self, seq: Sequence) -> None:
        """The single (final) stream emission of a prefill-hop row — its
        incremental outputs are held back (see _process_output) so the
        /disagg/prefill response reflects the post-publish outcome."""
        st = self._streams.get(seq.request_id)
        if st is None:
            return
        st.queue.put_nowait(RequestOutput(
            request_id=seq.request_id,
            text_delta=st.text,
            token_ids=list(seq.output_token_ids),
            finished=True,
            finish_reason=seq.finish_reason(),
            num_prompt_tokens=seq.num_prompt_tokens,
            num_output_tokens=len(seq.output_token_ids),
            num_cached_tokens=seq.num_cached_tokens,
            logprobs=(
                list(seq.output_logprobs)
                if seq.sampling.logprobs is not None else None
            ),
        ))

    # ------------------------------------------------------------- emissions
    def _process_output(self, seq: Sequence) -> None:
        """Detokenize incrementally, apply stop-string semantics, emit delta.

        OpenAI contract: the stop sequence itself is EXCLUDED from the output.
        While a request has stop strings, the last len(longest_stop)-1 chars
        are held back so a stop match split across token boundaries is never
        partially delivered.
        """
        if (
            seq.first_token_time is not None
            and seq.request_id not in self._ttft_recorded
        ):
            self._ttft_recorded.add(seq.request_id)
            self.histograms.ttft.observe(
                seq.first_token_time - seq.arrival_time
            )
        if seq.status.is_finished:
            self._ttft_recorded.discard(seq.request_id)
            if self.recorder is not None:
                # Idempotent close of the flight record (stop-string
                # finishes re-enter _process_output below with the status
                # already terminal).
                self.recorder.finish(
                    seq.request_id, reason=seq.finish_reason(),
                    output_tokens=len(seq.output_token_ids),
                )
            # A finished sequence's speculative draft-ring slot goes back
            # to the free list (idempotent; no-op when spec is off).
            self.runner.release_spec_slot(seq.request_id)
            if seq.status is not SequenceStatus.FINISHED_ABORTED:
                self.histograms.e2e.observe(
                    time.monotonic() - seq.arrival_time
                )
        st = self._streams.get(seq.request_id)
        if st is None:
            return
        if seq.resume_base and not seq._resume_counted and seq.prefill_done:
            # Resume telemetry: tokens of prompt+resume_tokens served from
            # the device prefix cache or the host/remote tiers instead of
            # recomputed (the whole point of KV-backed resume).
            seq._resume_counted = True
            self.resume_restored_tokens_total += seq.num_cached_tokens
        finished = seq.status.is_finished
        delta = st.detok.step(seq.output_token_ids, flush=finished)
        st.text += delta
        stops = seq.sampling.stop
        if stops and delta:
            # Scan even when the request already finished (length/EOS): the
            # detokenizer may hold back bytes until the final flush, so a stop
            # match can first become visible in the finishing delta — OpenAI
            # semantics still require truncating there and reporting "stop".
            max_stop = max(len(s) for s in stops)
            start = max(0, len(st.text) - len(delta) - max_stop)
            idx = -1
            for s in stops:
                i = st.text.find(s, start)
                if i != -1 and (idx == -1 or i < idx):
                    idx = i
            if idx != -1:
                st.text = st.text[:idx]
                # Drop sampled-past-the-stop tokens (the fused K-step decode
                # can overshoot a stop match by up to K-1 tokens) so token_ids
                # and usage reflect the delivered text, not the speculation.
                # Binary search for the smallest kept prefix, then verify with
                # a short linear walk: decode length is NOT strictly monotone
                # in token count (a prefix ending in dangling UTF-8 bytes can
                # decode to several replacement chars that collapse once the
                # next token completes the sequence), so the search may land a
                # token off and the walk corrects it.
                toks = seq.output_token_ids
                lo, hi = 0, len(toks)
                while lo < hi:
                    mid = (lo + hi) // 2
                    if len(self.tokenizer.decode(toks[:mid])) < idx:
                        lo = mid + 1
                    else:
                        hi = mid
                while lo < len(toks) and \
                        len(self.tokenizer.decode(toks[:lo])) < idx:
                    lo += 1
                while lo > 0 and \
                        len(self.tokenizer.decode(toks[:lo - 1])) >= idx:
                    lo -= 1
                # Tokens below resume_base were counted by the ORIGINAL
                # engine, never by this one — don't un-count them here.
                self.generation_tokens_total -= max(
                    0, len(toks) - max(lo, seq.resume_base)
                )
                seq.output_token_ids = toks[:lo]
                if seq.output_logprobs:
                    del seq.output_logprobs[lo:]
                if finished:
                    seq.status = SequenceStatus.FINISHED_STOPPED
                else:
                    self.scheduler.finish(
                        seq.request_id, SequenceStatus.FINISHED_STOPPED
                    )
                finished = True
        if finished and self.recorder is not None:
            # Stop-string finishes flip `finished` AFTER the top-of-method
            # check ran; the recorder close is idempotent, so re-calling
            # here covers both orders.
            self.recorder.finish(
                seq.request_id, reason=seq.finish_reason(),
                output_tokens=len(seq.output_token_ids),
            )
        if seq.handoff_key is not None:
            # Prefill-hop rows defer emission to _emit_handoff_output: the
            # detok/stop state above still advances (final_text for finished
            # bundles), but the /disagg/prefill response must carry the
            # post-publish outcome, not a premature token delta. Aborts
            # (client gone, drain) must still unblock the handler's stream.
            if seq.status is SequenceStatus.FINISHED_ABORTED:
                self._emit_handoff_output(seq)
            return
        hold = 0 if finished or not stops else max(len(s) for s in stops) - 1
        emit_upto = max(len(st.text) - hold, st.sent)
        text_delta = st.text[st.sent:emit_upto]
        st.sent = emit_upto
        st.queue.put_nowait(RequestOutput(
            request_id=seq.request_id,
            text_delta=text_delta,
            token_ids=list(seq.output_token_ids),
            finished=finished,
            finish_reason=seq.finish_reason(),
            num_prompt_tokens=seq.num_prompt_tokens,
            num_output_tokens=len(seq.output_token_ids),
            num_cached_tokens=seq.num_cached_tokens,
            logprobs=(
                list(seq.output_logprobs)
                if seq.sampling.logprobs is not None else None
            ),
            arrival_time=seq.arrival_time,
            first_token_time=seq.first_token_time,
        ))

    # ------------------------------------------------------------------ stats
    def _offload_stat(self, attr: str) -> int:
        return getattr(self.offload, attr, 0) if self.offload else 0

    def _moe_counters(self) -> Dict[str, int]:
        """The sparse experts' counters by the names /metrics exports:
        pairs and the busiest expert's tokens over every call, distinct
        experts touched and calls for decode and for prefill apart (a
        decode call holds a step's rows, a prefill call a chunk's tokens:
        their means are different quantities)."""
        total = self.runner.fwd_stats_total
        dec, pre = total["decode"], total["prefill"]
        return {
            "moe_assignments_total":
                dec.get("assignments", 0) + pre.get("assignments", 0),
            "moe_expert_load_max_total":
                dec.get("expert_load_max", 0) + pre.get("expert_load_max", 0),
            "moe_experts_touched_total": dec.get("experts_touched", 0),
            "moe_layer_calls_total": dec.get("layer_calls", 0),
            "moe_prefill_experts_touched_total":
                pre.get("experts_touched", 0),
            "moe_prefill_layer_calls_total": pre.get("layer_calls", 0),
            # Only where the module counts them (a share of the experts).
            **({"moe_assignments_elsewhere_total":
                dec["assignments_elsewhere"] + pre["assignments_elsewhere"]}
               if "assignments_elsewhere" in dec else {}),
            # Only where the module's full layers select the keys they read
            # (a learned indexer): decode's and prefill's apart, as a
            # decode step READS what it selected and a chunk masks.
            **({"index_keys_visible_total": dec["index_keys_visible"],
                "index_keys_selected_total": dec["index_keys_selected"],
                "index_prefill_keys_visible_total":
                    pre["index_keys_visible"],
                "index_prefill_keys_selected_total":
                    pre["index_keys_selected"]}
               if "index_keys_visible" in dec else {}),
        }

    def _live_perf(self) -> Dict[str, float]:
        """Live roofline position from the rolling dispatch window
        (docs/OBSERVABILITY.md fleet pane): throughput over the window's
        wall span, the Leviathan'23 effective tokens per target-model
        step, and achieved-vs-roofline HBM bandwidth — the same
        arithmetic as bench.py's JSON line (shared
        production_stack_tpu/perf/roofline.py), but computed continuously
        against the CURRENT batch shape. Pure host-side dict math over
        timestamps the loop already took; an idle engine reports zeros."""
        # No peak (the CPU backend has no HBM): the roofline share is None
        # and the /metrics renderers export no sample for it — a share of
        # some accelerator's peak would be a number about nothing.
        has_peak = self.hbm_peak_gbps is not None
        out = {
            "live_tok_per_s": 0.0,
            "live_hbm_bw_pct": 0.0 if has_peak else None,
            "live_effective_tokens_per_target_step": 0.0,
        }
        win = list(self._dispatch_window)
        if not win:
            return out
        # Span from the oldest dispatch's ISSUE to the newest FETCH.
        span = max(win[-1][0] - (win[0][0] - win[0][1]), 1e-9)
        tok_s = sum(e[3] for e in win) / span
        out["live_tok_per_s"] = tok_s
        decode_steps = sum(e[4] for e in win)
        eff = 1.0
        if decode_steps:
            eff = sum(e[3] for e in win if e[4]) / decode_steps
            out["live_effective_tokens_per_target_step"] = eff
        if not has_peak:
            return out
        from production_stack_tpu.perf.roofline import roofline_components

        running = self.scheduler.running
        avg_ctx = (sum(s.num_tokens for s in running) / len(running)
                   if running else 1.0)
        dtype_bytes = {"bfloat16": 2.0, "float16": 2.0, "float32": 4.0}.get(
            self.config.dtype, 2.0
        )
        try:
            comp = roofline_components(
                self.config.model, dtype_bytes, self.config.kv_cache_dtype,
                max(1, len(running)), avg_ctx,
                peak_gbs=self.hbm_peak_gbps,
                tokens_per_target_step=max(1.0, eff),
                num_chips=max(1, self.mesh.size),
            )
            out["live_hbm_bw_pct"] = 100.0 * tok_s / comp["roofline_tok_s"]
        except Exception:  # noqa: BLE001 — unknown model alias: no ceiling
            pass
        return out

    def _memory_stats(self) -> Dict:
        """The memory ledger's side of ``stats()``: residents by device
        and holder (from the arrays themselves until ``start()`` has built
        the ledger), the fullest device's reading now, and the peak's
        rises by phase."""
        memory = self.runner.memory
        now = memory.reading()
        return {
            "hbm_resident_bytes": memory.residents_by_device
            or self.runner.resident_bytes(),
            "hbm_bytes_in_use": int(now.get("bytes_in_use", 0)),
            "hbm_peak_bytes": int(now.get("peak_bytes_in_use", 0)),
            "hbm_limit_bytes": int(now.get("bytes_limit", 0)),
            "hbm_reserved_bytes": int(now.get("bytes_reserved", 0)),
            "hbm_peak_rises": dict(memory.rises),
            "hbm_peak_rise_bytes": dict(memory.rise_bytes),
        }

    def device_report(self) -> Dict:
        """The devices of this engine's MESH, as JAX reports them."""
        import os

        devices = list(self.mesh.devices.flat)
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "ids": [d.id for d in devices],
            # Which of the host's chips the launcher gave this process
            # (benchmarks/stack.py:tpu_chip_env); None = all of them.
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        }

    def report(self) -> Dict:
        """What this engine actually runs on and how it started — the
        ``GET /version`` body beside the version (chip_smoke.py reads its
        verdict from here: the device is the one of the engine that
        served the requests, not one a harness assumed)."""
        from production_stack_tpu.engine.runner import _cache_entries

        r = self.runner
        cached = _cache_entries(r.compilation_cache_path)
        bytes_in_use, peak_bytes_in_use = {}, {}
        for d, stats in zip(self.mesh.devices.flat, r.device_memory()):
            if "bytes_in_use" in stats:
                bytes_in_use[str(d.id)] = int(stats["bytes_in_use"])
            if "peak_bytes_in_use" in stats:
                peak_bytes_in_use[str(d.id)] = int(
                    stats["peak_bytes_in_use"])
        return {
            "device": self.device_report(),
            "engine": {
                "model": self.config.model,
                "num_layers": self.model_config.num_layers,
                **r.residual_report(),
                **r.span_report(),
                **r.ring_report(),
                "mesh": dict(self.mesh.shape),
                "attn_impl": r.attn_impl,
                "pallas_interpret": r._pallas_interpret,
                "compilation_cache_dir": r.compilation_cache_path,
                "compilation_cache_entries":
                    None if cached is None else len(cached),
                "warmup_families": r.startup_warmed_families,
                "warmup_failures": r.startup_warmup_failures,
                "cache_hit_families": r.startup_cache_hit_families,
                "cache_miss_families": r.startup_cache_miss_families,
                "deferred_families": r.startup_deferred_families,
                # Of the hits: programs loaded from the runner's store,
                # none of them traced (engine/program_store.py).
                "loaded_families": r.startup_loaded_families,
                "compile_seconds": round(r.startup_compile_seconds, 3),
                "warmup_seconds": round(r.startup_warmup_seconds, 3),
                "weight_load_seconds": round(
                    r.startup_weight_load_seconds, 3
                ),
                "kv_blocks": r.num_kv_blocks,
                # Per-sequence recurrent state (0 / 0 for a K/V-only
                # model): slots the block manager hands out, and the
                # pools' bytes on the device.
                "state_slots": self.block_manager.num_state_slots,
                "state_bytes": r.state_pool_bytes,
                "kv_shard_shape": list(
                    r.kv_k.sharding.shard_shape(r.kv_k.shape)
                ),
                "bytes_in_use": bytes_in_use,
                # The allocator's high-water mark since process start
                # (program temporaries included, which bytes_in_use after
                # a window no longer holds).
                "peak_bytes_in_use": peak_bytes_in_use,
                "hbm_peak_gbps": self.hbm_peak_gbps,
            },
        }

    def stats(self) -> Dict:
        disagg = self.disagg.stats() if self.disagg is not None else {
            "kv_handoffs_total": 0,
            "kv_handoff_bytes_total": 0,
            "kv_handoff_seconds_total": 0.0,
            "kv_handoff_failures_total": 0,
        }
        compiles, compile_s = self._compile_clock.reading()
        return {
            "disagg_role": self.config.role,
            **disagg,
            "engine_uptime_seconds": time.monotonic() - self.start_time,
            "kv_offload_blocks": self.offload_blocks_resident,
            # KV-cache quantization (--kv-cache-dtype, docs/PERF.md round
            # 7): the pool's storage dtype, its DERIVED device bytes
            # (payload + scale sidecars — int8 buys ~2x blocks per byte),
            # and the pool bytes quantization avoided writing.
            "kv_cache_dtype": self.config.kv_cache_dtype,
            "kv_pool_bytes": self.runner.kv_pool_bytes,
            "kv_num_blocks": self.runner.num_kv_blocks,
            "kv_quant_bytes_saved_total":
                self.runner.kv_quant_bytes_saved_total,
            # Multi-chip serving (docs/PERF.md round 9): the mesh this
            # engine's dispatches shard over (the LIVE mesh — an explicit
            # mesh= override wins over the config axes), and what holds
            # each device's memory (the KV pool's footprint is the "kv"
            # holder).
            "mesh_tp_size": self.mesh.shape.get("tp", 1),
            "mesh_sp_size": self.mesh.shape.get("sp", 1),
            "mesh_devices": self.mesh.size,
            **self._memory_stats(),
            "num_requests_running": self.scheduler.num_running,
            "num_requests_waiting": self.scheduler.num_waiting,
            # Autoscaling signal (docs/SOAK.md): total backlog on this
            # engine — the per-pod HPA metric.
            "queue_depth": (
                self.scheduler.num_running + self.scheduler.num_waiting
            ),
            "kv_cache_usage": self.block_manager.usage(),
            "prefix_cache_hits": self.block_manager.prefix_hits_total,
            "prefix_cache_queries": self.block_manager.prefix_queries_total,
            # KV economy (docs/KV_ECONOMY.md): device prefix-index size +
            # shared-tier restore/eviction telemetry.
            "prefix_index_size": self.block_manager.prefix_index_size,
            # Recurrent-state slots (all 0 for a K/V-only model) and the
            # prefix hits a model with state could not be served.
            "state_slots_total": self.block_manager.num_state_slots,
            "state_slots_in_use": self.block_manager.state_slots_in_use,
            "state_slot_allocs_total":
                self.block_manager.state_slot_allocs_total,
            "state_slot_waits_total":
                self.block_manager.state_slot_waits_total,
            "prefix_hit_tokens_unserved_total":
                self.block_manager.prefix_hits_unserved_total,
            "kv_restore_saved_tokens_total": self._offload_stat(
                "restore_saved_tokens_total"
            ),
            "kv_shared_tier_hits_total": self._offload_stat(
                "shared_tier_hits_total"
            ),
            "kv_shared_tier_misses_total": self._offload_stat(
                "shared_tier_misses_total"
            ),
            "kv_chain_evictions_total": self._offload_stat(
                "chain_evictions_total"
            ),
            # Mid-stream resume (docs/RESILIENCE.md): prompt+resume tokens
            # a resume request served from cache/tiers instead of
            # recomputing.
            "resume_restored_tokens_total": self.resume_restored_tokens_total,
            # Speculative decoding (docs/PERF.md round 8): draft proposals
            # made / accepted and the lifetime acceptance rate. The bonus
            # token each cycle emits is counted in neither (acceptance is
            # a property of the DRAFT).
            "spec_enabled": 1 if self.config.speculative_num_tokens else 0,
            "spec_draft_tokens_total": self.runner.spec_draft_tokens_total,
            "spec_accepted_tokens_total":
                self.runner.spec_accepted_tokens_total,
            "spec_acceptance_rate": self.runner.spec_acceptance_rate,
            # Round 10: windowed acceptance (last <=64 fetches — the
            # lifetime rate freezes after long uptimes), served draft
            # depth under the adaptive controller, tree-node volume, the
            # mean per-sequence acceptance EMA, and how often the
            # controller degraded a whole dispatch to the plain scan.
            "spec_acceptance_rate_window":
                self.runner.spec_acceptance_rate_window,
            "spec_draft_depth": self.runner.spec_draft_depth_mean,
            "spec_tree_nodes_total": self.runner.spec_tree_nodes_total,
            "spec_acceptance_ema": self.runner.spec_acceptance_ema_mean,
            "spec_gamma0_dispatches_total":
                self.runner.spec_gamma0_dispatches_total,
            # Elastic fast-start (docs/ELASTIC.md): startup phase timings
            # + the warmup persistent-compile-cache hit/miss split.
            "startup_weight_load_seconds":
                self.runner.startup_weight_load_seconds,
            "startup_compile_seconds": self.runner.startup_compile_seconds,
            "startup_warmup_seconds": self.runner.startup_warmup_seconds,
            "startup_prewarm_seconds": self.startup_prewarm_seconds,
            "startup_total_seconds": self.startup_total_seconds,
            "startup_cache_hit_families":
                self.runner.startup_cache_hit_families,
            "startup_cache_miss_families":
                self.runner.startup_cache_miss_families,
            "startup_loaded_families":
                self.runner.startup_loaded_families,
            "num_preemptions": self.scheduler.num_preemptions_total,
            # Observability plane (docs/OBSERVABILITY.md): OTLP exporter
            # queue drops (0 with tracing off).
            "trace_spans_dropped_total": _spans_dropped_total(),
            "prompt_tokens_total": self.prompt_tokens_total,
            "generation_tokens_total": self.generation_tokens_total,
            "decode_dispatches_total": self.decode_dispatches_total,
            "prefill_dispatches_total": self.prefill_dispatches_total,
            "dispatch_overlap_ratio": (
                self.overlapped_fetches_total / self.fetches_total
                if self.fetches_total else 0.0
            ),
            "dispatch_gap_seconds_total": self.dispatch_gap_seconds_total,
            # Live roofline telemetry (docs/OBSERVABILITY.md fleet pane).
            "host_stall_seconds_total": self.host_stall_seconds_total,
            # Loop spans and decode work (see __init__).
            **self.loop_spans.counters(),
            "decode_steps_total": self.decode_steps_total,
            "decode_row_steps_total": self.decode_row_steps_total,
            "decode_bucket_row_steps_total":
                self.decode_bucket_row_steps_total,
            "decode_row_steps_wasted_total":
                self.decode_row_steps_wasted_total,
            "decode_steps_empty_total": self.decode_steps_empty_total,
            "decode_rows_first_total": self.decode_rows_first_total,
            "decode_rows_joined_total": self.decode_rows_joined_total,
            # What prefill dispatches carried and what stopped admission
            # (counted at issue; the scheduler's blocked passes beside the
            # dispatches' own stops), and compiles past warm-up.
            "prefill_tokens_issued_total": self.prefill_tokens_issued_total,
            "prefill_tokens_padded_total": self.prefill_tokens_padded_total,
            "prefill_rows_issued_total": self.prefill_rows_issued_total,
            "prefill_segments_total": self.prefill_segments_total,
            "attn_keys_in_span_total": self.attn_keys_in_span_total,
            "attn_keys_held_total": self.attn_keys_held_total,
            "ring_keys_held_total": self.ring_keys_held_total,
            "ring_keys_context_total": self.ring_keys_context_total,
            "prefill_left_waiting_total": self.prefill_left_waiting_total,
            **{f"prefill_stop_{stop}_total":
               n + self.scheduler.prefill_blocked[stop]
               for stop, n in self.prefill_stops.items()},
            "serving_compiles_total": compiles - self._compiles_at_start[0],
            "serving_compile_seconds_total":
                compile_s - self._compiles_at_start[1],
            # How often the sampler's conditional picks engage
            # (engine/sampling.py), counted by the runner at issue.
            "sample_dispatches_total": self.runner.sample_dispatches_total,
            "sample_dispatches_greedy_total":
                self.runner.sample_dispatches_greedy_total,
            "sample_dispatches_filtered_total":
                self.runner.sample_dispatches_filtered_total,
            # What the sparse experts were given (the runner reads the
            # forward's counters behind each fetch; zeros without experts).
            **self._moe_counters(),
            **self._live_perf(),
        }
