"""Engine configuration.

The reference configures its (external) engines through Helm values rendered
into vLLM CLI flags (reference helm/templates/deployment-vllm-multi.yaml:60-134:
--tensor-parallel-size, --max-model-len, --enable-prefix-caching, LMCACHE_*
env). EngineConfig is the in-repo equivalent; the same knob names are kept
where they exist so the chart stays recognizable.
"""

import os
from dataclasses import dataclass, field
from typing import Dict, Optional


# <checkout>/.pstpu_xla_cache — a FIXED path (the directory is part of the
# cache key), never one built from a temporary name, pid or time.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".pstpu_xla_cache",
)


@dataclass
class EngineConfig:
    model: str = "tiny-llama"
    dtype: str = "bfloat16"
    max_model_len: int = 2048
    # --- KV cache ---
    # Pool STORAGE dtype (compute stays self.dtype): "int8" stores K/V as
    # symmetric int8 with a per-(slot, head) bf16 scale
    # (ops/quantization.py), halving decode HBM byte traffic — the decode
    # roofline itself — and kv_offload/disagg wire bytes; the pool holds
    # ~2x the blocks in the same HBM budget. Readers dequantize inline
    # (window gather / XLA reference path / Pallas flash-decode kernel);
    # bf16 K/V never materialize in HBM on the paged path.
    kv_cache_dtype: str = "bfloat16"
    block_size: int = 16
    num_kv_blocks: Optional[int] = None     # explicit block count; else derived
    hbm_utilization: float = 0.9            # fraction of free HBM for KV pool
    enable_prefix_caching: bool = True
    # --- scheduler ---
    max_num_seqs: int = 64
    max_num_batched_tokens: int = 4096      # prefill dispatch token budget
    # Rows of one batched prefill dispatch, at most. None (the default, and
    # what every deployment file leaves it at): as many as the token budget
    # holds at the narrowest chunk bucket, max_num_batched_tokens //
    # prefill_t_floor (16 at 2048 tokens, 8 at 1024), within max_num_seqs.
    # A value set here still caps. Readers take the resolved value,
    # utils.prefill_row_cap(config); the programs that follow from it are
    # utils.prefill_rectangles(config).
    max_prefill_seqs: Optional[int] = None
    # MAX decode steps fused into ONE device dispatch (lax.scan inside the
    # jit): K*B tokens per host round-trip instead of B. Host-side stop
    # conditions (EOS, stop strings, aborts) are applied after the fetch, so
    # up to K-1 tokens per sequence are speculatively computed and
    # discarded. Each dispatch pays a fixed cost (host round-trips + the
    # window gather on the window attention path — ~100 ms at 16x2k-token
    # rows on a v5e), so K trades streaming granularity against that cost;
    # the scheduler grades K down as the number of active streams drops
    # (scheduler.py: 8 at <=2 streams, 32 at <=8) so interactive clients
    # keep sub-100ms bursts while saturated serving amortizes fully. 32 at
    # the top: a request arriving mid-dispatch waits out the in-flight
    # fused scan before its prefill can run, so K bounds the expected TTFT
    # queueing term (~K/2 steps) — 64 halved p50 TTFT headroom for ~3% of
    # dispatch-overhead amortization on the bench workload.
    num_decode_steps: int = 32
    # AOT-compile the primary decode/prefill shape families at startup
    # (ModelRunner.warmup). Off by default so tests and short-lived engines
    # don't pay it; the API server turns it on.
    enable_warmup: bool = False
    # --- parallelism (jax.sharding over the TPU slice mesh) ---
    tensor_parallel_size: int = 1
    sequence_parallel_size: int = 1         # ring-attention axis for long prefill
    data_parallel_size: int = 1
    # --- kernels ---
    # "auto"   -> "paged" (Pallas flash-decode against the HBM pool, no window
    #             copy) when the backend is a TPU, the model supports it
    #             (llama family; head_dim divides or is a multiple of 128 via
    #             lane packing), and the worst-case gathered window would be
    #             large; else "window".
    # "window" -> decode gathers the live KV into a contiguous per-dispatch
    #             window ("xla" accepted as a legacy alias).
    # "paged"  -> force the Pallas path ("pallas" accepted as an alias);
    #             raises if the model/block size can't satisfy the kernel's
    #             alignment constraints.
    attn_impl: str = "auto"
    # Fused-decode loop construct: "scan" runs all K steps unconditionally
    # (lax.scan — XLA pipelines/unrolls it aggressively); "while" runs
    # exactly the steps some row still needs (lax.while_loop; drain tails
    # skip padded iterations). A/B on the v5e bench (pipelined loop, r5):
    # scan 1743 tok/s vs while 1651 — with the per-dispatch sync hidden,
    # scan's compiler latitude beats the drain-tail savings, so scan is
    # the default; while remains for latency-odd workloads with many
    # short-budget rows.
    decode_loop: str = "scan"
    # Pipelined engine loop: issue dispatch N+1 before fetching N's tokens
    # (device-chained start tokens; scheduler state advanced speculatively
    # at issue). Hides the blocking per-dispatch device->host sync. False
    # restores strict issue-fetch-apply.
    async_pipeline: bool = True
    # Maximum dispatches outstanding on device at once (the engine loop
    # fills this many slots before blocking on the oldest fetch). 2 is the
    # two-slot pipeline: while one dispatch's fetch blocks, the other
    # executes. Ignored (treated as 1) when async_pipeline is False, and
    # clamped to 2 by the engine loop (a third outstanding decode could
    # need token chains from two unapplied dispatches at once — see
    # runner._chains).
    pipeline_depth: int = 2
    # Two-slot prefill/decode overlap: one scheduling round may produce BOTH
    # a prefill batch and a decode batch, so a fresh prompt's prefill is
    # issued while a fused decode scan is still in flight (and decode keeps
    # its cadence during a long prompt's chunk train) instead of the two
    # kinds strictly alternating through a single slot. Rows finishing
    # their prompt in an in-flight prefill join the decode issued right
    # behind it, chaining their start token from the prefill's device
    # vector (one source a decode: the loop's depth is 2). False is the
    # fallback to the round-5 one-batch-per-round loop.
    overlap_dispatch: bool = True
    # --- prefill/decode disaggregation (docs/DISAGG.md) ---
    # "unified" serves prompts end-to-end. "prefill" computes prompt KV +
    # the first token, publishes them to the remote KV store under the
    # request's transfer key, and finishes ("handoff"); its scheduler never
    # forms decode batches except for router-flagged fallback traffic.
    # "decode" rehydrates published KV into its own pool and continues the
    # stream from token 1 with no recompute; its scheduler never forms
    # prefill batches except for fallback traffic. Non-unified roles
    # require kv_remote_url (the handoff rides the offload store).
    role: str = "unified"
    # --- KV offload (LMCache-equivalent; env names mirror the reference chart)
    kv_offload_cpu: bool = field(
        default_factory=lambda: os.environ.get("LMCACHE_LOCAL_CPU", "").lower() == "true"
    )
    kv_offload_max_cpu_gb: float = field(
        default_factory=lambda: float(os.environ.get("LMCACHE_MAX_LOCAL_CPU_SIZE", "0") or 0)
    )
    kv_remote_url: Optional[str] = field(
        default_factory=lambda: os.environ.get("LMCACHE_REMOTE_URL") or None
    )
    kv_remote_serde: str = field(
        default_factory=lambda: os.environ.get("LMCACHE_REMOTE_SERDE", "naive")
    )
    # Restore-over-recompute admission (docs/KV_ECONOMY.md): on prefill the
    # offload manager restores the longest tier-resident prefix instead of
    # recomputing it when est. transfer time (bytes / link bandwidth) beats
    # est. prefill time (tokens / prefill throughput). Both estimates are
    # deliberately coarse knobs, not measurements: the decision only has to
    # be right in the regimes that matter (a 1000-token shared system
    # prompt is ~always worth restoring; a single cold block behind a slow
    # link is not).
    kv_restore_link_gbps: float = field(
        default_factory=lambda: float(
            os.environ.get("PSTPU_KV_RESTORE_LINK_GBPS", "2.0")
        )
    )
    kv_restore_prefill_tok_s: float = field(
        default_factory=lambda: float(
            os.environ.get("PSTPU_KV_RESTORE_PREFILL_TOK_S", "4000")
        )
    )
    # --- LoRA (vLLM --lora-modules convention: name -> PEFT checkpoint dir)
    lora_modules: Dict[str, str] = field(default_factory=dict)
    # --- speculative decoding (docs/PERF.md round 8) ---
    # Draft-ahead tokens per target step inside the fused decode scan:
    # each scan cycle runs the DRAFT model N+1 autoregressive steps, scores
    # all N+1 positions with ONE batched target forward, and accepts the
    # longest prefix of draft proposals that match the target's own
    # (seeded) samples — so spec-on output is TOKEN-IDENTICAL to spec-off
    # for greedy and seeded sampling, and the target model reads its
    # weights once per up-to-(N+1) emitted tokens instead of once per
    # token. 0 disables (the default serving path compiles no draft code).
    speculative_num_tokens: int = 0
    # Draft model (name or HF dir) — must share the target's vocabulary
    # (validated at config construction: a mismatched draft is a clean
    # startup error, never a mid-scan shape crash). The draft's KV lives in
    # a small per-sequence ring in the COMPUTE dtype (bf16 on TPU), never
    # in the paged pool.
    speculative_model: Optional[str] = None
    # Draft KV ring length in tokens (per sequence). 0 = max_model_len
    # (full draft context — highest acceptance, but draft-KV memory is
    # ring * (max_num_seqs + prefill_row_cap) * draft KV bytes/token and
    # is allocated OUTSIDE the paged pool's HBM budget); the bounded
    # default keeps spec-on startup safe at long context, at the cost of
    # the draft forgetting distant context (acceptance-only effect,
    # never correctness).
    speculative_draft_window: int = 1024
    # Adaptive per-sequence draft depth (docs/PERF.md round 10): a host-side
    # per-sequence acceptance EMA picks each row's draft depth gamma in
    # [0, speculative_num_tokens] at every dispatch — high-acceptance rows
    # draft deep, low-acceptance rows shrink toward gamma=0, and a dispatch
    # whose rows ALL sit at gamma=0 is issued down the plain non-speculative
    # path (zero draft steps, zero draft-ring traffic). Output stays
    # token-identical to spec-off/fixed-gamma: acceptance only ever gates
    # which DRAFT proposals may be accepted, never what the target samples.
    speculative_adaptive: bool = False
    # Token-tree draft width W (SpecInfer, arXiv:2305.09781): the draft
    # proposes W alternatives at the FIRST speculated position (the seeded
    # common-random-number sample plus the top W-1 other draft tokens) and
    # a linear continuation behind the first, all verified in ONE batched
    # target pass with the tree encoded as an additive attention-bias
    # segment. 1 = linear speculation (exactly the round-8 path).
    speculative_tree_width: int = 1
    # Adaptive-controller shape knobs (config-only; the two serving flags
    # above are the operator surface). ema_decay is the weight of the
    # newest per-dispatch acceptance observation; gamma_threshold is the
    # expected-value floor (gamma = largest g with ema^g >= threshold);
    # probe_period re-probes a gamma=0 row with gamma=1 every P dispatches
    # so collapsed rows can recover (0 disables probing).
    speculative_ema_decay: float = 0.35
    speculative_gamma_threshold: float = 0.5
    speculative_probe_period: int = 16
    # --- weights ---
    load_format: str = "auto"               # "auto" | "safetensors" | "dummy"
    seed: int = 0
    # --- compilation ---
    # Persistent XLA compile cache: step-shape compiles (seconds each on
    # TPU, tens of them per boot) are paid once per machine, not once per
    # process. $JAX_COMPILATION_CACHE_DIR, when set, places the cache from
    # outside and wins over this field (runner._setup_compilation_cache);
    # the default is one fixed git-ignored directory in the checkout, so
    # every process of a stack finds the same cache. Empty disables (only
    # while the variable is unset).
    compilation_cache_dir: str = DEFAULT_COMPILATION_CACHE_DIR
    # Fast-start weight/compile overlap (docs/ELASTIC.md): load checkpoint
    # weights on a background thread while warmup runs its compile-only
    # AOT prepass against abstract weights — the IO-bound and CPU-bound
    # halves of startup pipeline instead of serializing. Off by default so
    # tests and warmup-less engines keep the serial path; the API server
    # turns it on (like enable_warmup). Ignored with speculative decoding
    # (the draft shares/loads weights during construction).
    overlap_weight_load: bool = False
    # --- serving ---
    served_model_name: Optional[str] = None
    # --- observability (docs/OBSERVABILITY.md) ---
    # Per-request flight recorder + /debug endpoints (request timelines,
    # on-demand device profiling). Recorder appends are O(1) in-memory
    # list appends from the engine loop — no syscalls on the dispatch hot
    # path — so this stays on by default; False removes the /debug surface
    # entirely (plain 404) and records nothing.
    debug_endpoints: bool = True
    # Bounded ring: at most this many recent request timelines are kept,
    # each holding at most flight_recorder_max_events events (overflow is
    # counted on the record, never silently lost).
    flight_recorder_capacity: int = 256
    flight_recorder_max_events: int = 512
    # Peak HBM GB/s per chip for the live roofline telemetry
    # (pstpu:live_hbm_bw_pct): the denominator of the decode roofline the
    # engine reports its own position against. None = looked up by the
    # device kind the engine finds (perf/roofline.py:peak_hbm_gbps — an
    # unknown TPU kind is a startup error, the CPU backend reports no
    # roofline share at all); a value (or $PSTPU_PEAK_HBM_GBS) overrides.
    hbm_peak_gbps: Optional[float] = None

    def __post_init__(self):
        # Speculative decoding is validated at CONFIG PARSE TIME so a
        # mis-paired draft is a clean startup error, not a mid-scan shape
        # crash (docs/PERF.md round 8).
        # Multi-chip combos are validated at parse time too: a tp that
        # can't shard the scale pools, or spec-decoding on a mesh, must be
        # a clean config error at startup, never a sharded-dispatch shape
        # crash minutes into serving (docs/PERF.md round 9). Runs before
        # the draft resolution so the spec+tp pairing gets the error that
        # names both flags.
        self.validate_parallelism()
        if not self.speculative_num_tokens and (
            self.speculative_adaptive or self.speculative_tree_width > 1
        ):
            raise ValueError(
                "--speculative-adaptive/--speculative-tree-width modify the "
                "speculative decode train and require "
                "--speculative-num-tokens > 0 (plus --speculative-model)"
            )
        if self.speculative_num_tokens:
            self.resolved_draft_config()

    @property
    def mesh_devices(self) -> int:
        """Devices the serving mesh occupies (dp x sp x tp)."""
        return (self.data_parallel_size * self.sequence_parallel_size
                * self.tensor_parallel_size)

    def validate_parallelism(self) -> None:
        """Parse-time validation of the parallelism axes against the other
        knobs. Raises ValueError naming the exact flag pair at fault."""
        tp = self.tensor_parallel_size
        sp = self.sequence_parallel_size
        if tp < 1 or sp < 1 or self.data_parallel_size < 1:
            raise ValueError(
                "--tensor-parallel-size/--sequence-parallel-size/"
                "--data-parallel-size must all be >= 1, got "
                f"tp={tp} sp={sp} dp={self.data_parallel_size}"
            )
        if self.speculative_num_tokens and (tp > 1 or sp > 1):
            raise ValueError(
                "--speculative-num-tokens is incompatible with "
                "--tensor-parallel-size/--sequence-parallel-size > 1: "
                "speculative decoding currently requires a single-device "
                "mesh (tp=sp=1) — the draft-KV ring pools and the batched "
                "verify chunk are not mesh-sharded yet. Drop the "
                "speculative flags to serve on the mesh, or serve "
                "speculatively on one chip."
            )
        if tp > 1 and self.kv_cache_quantized:
            # The int8 scale sidecars [L, Hkv, slots] shard the kv-head
            # axis exactly like the payload pools (parallel/sharding.py:
            # kv_scale_sharding); an indivisible head count would silently
            # fall back to REPLICATED scale pools against SHARDED int8
            # payloads on the Pallas shard_map path. Assert the same
            # divisibility the head counts get, at parse time.
            from production_stack_tpu.models.config import (
                resolve_model_config,
            )

            mc = resolve_model_config(self.model)
            if mc.num_kv_heads % tp or mc.num_heads % tp:
                raise ValueError(
                    f"--kv-cache-dtype int8 with --tensor-parallel-size "
                    f"{tp} requires tp to divide the model's head counts "
                    f"(the per-(slot, head) scale pools are kv-head-"
                    f"sharded over the tp axis like the payload pools); "
                    f"model {self.model!r} has "
                    f"{mc.num_heads}/{mc.num_kv_heads} heads. Use a tp "
                    f"that divides both, or --kv-cache-dtype bfloat16."
                )

    @property
    def speculative_enabled(self) -> bool:
        return self.speculative_num_tokens > 0

    def resolved_draft_config(self):
        """Resolve + validate the speculative draft model config against
        this engine's target model. Raises ValueError on every
        incompatibility the fused draft/verify scan cannot serve."""
        from production_stack_tpu.models.config import resolve_model_config

        n = self.speculative_num_tokens
        if n < 0 or n > 16:
            raise ValueError(
                f"--speculative-num-tokens must be in [0, 16], got {n}"
            )
        if not self.speculative_model:
            raise ValueError(
                "--speculative-num-tokens > 0 requires --speculative-model "
                "(the draft; e.g. facebook/opt-125m, or the target model "
                "itself for a self-draft parity configuration)"
            )
        if self.kv_cache_quantized:
            raise ValueError(
                "speculative decoding requires --kv-cache-dtype bfloat16: "
                "the batched verify step attends the in-chunk draft KV "
                "unquantized, so int8 pools would break the spec-on == "
                "spec-off token-identity bar (draft KV is always kept in "
                "the compute dtype)"
            )
        if self.tensor_parallel_size > 1 or self.sequence_parallel_size > 1:
            # Kept for direct resolved_draft_config() callers; __post_init__
            # raises the same restriction from validate_parallelism first.
            raise ValueError(
                "--speculative-num-tokens is incompatible with "
                "--tensor-parallel-size/--sequence-parallel-size > 1: "
                "speculative decoding currently requires a single-device "
                "mesh (tp=sp=1) — the draft-KV ring pools and the batched "
                "verify chunk are not mesh-sharded yet"
            )
        w = self.speculative_tree_width
        if w < 1 or w > 8:
            raise ValueError(
                f"--speculative-tree-width must be in [1, 8], got {w} "
                f"(width 1 is linear speculation; wider trees multiply "
                f"verify-chunk FLOPs with sharply diminishing acceptance "
                f"returns past the first few alternatives)"
            )
        if not 0.0 < self.speculative_ema_decay <= 1.0:
            raise ValueError(
                f"speculative_ema_decay must be in (0, 1], got "
                f"{self.speculative_ema_decay}"
            )
        if self.speculative_gamma_threshold <= 0.0:
            raise ValueError(
                f"speculative_gamma_threshold must be > 0, got "
                f"{self.speculative_gamma_threshold} (values > 1 pin every "
                f"row to gamma=0 — the spec-off-degradation test "
                f"configuration)"
            )
        if self.speculative_probe_period < 0:
            raise ValueError(
                f"speculative_probe_period must be >= 0, got "
                f"{self.speculative_probe_period}"
            )
        target = resolve_model_config(self.model)
        draft = resolve_model_config(self.speculative_model)
        if draft.vocab_size != target.vocab_size:
            raise ValueError(
                f"speculative draft {self.speculative_model!r} is tokenizer-"
                f"incompatible with target {self.model!r}: draft vocab "
                f"{draft.vocab_size} != target vocab {target.vocab_size} "
                f"(draft proposals are accepted by token id, so the two "
                f"models must share one tokenizer/vocabulary)"
            )
        return draft

    @property
    def speculative_ring_len(self) -> int:
        """Draft KV ring length in tokens (0 = track the full context)."""
        w = self.speculative_draft_window
        if w <= 0:
            return self.max_model_len
        return min(w, self.max_model_len)

    def resolved_attn_impl(self, model_config) -> str:
        """Resolve the decode attention implementation for ``model_config``
        (see the attn_impl field comment for the semantics)."""
        from production_stack_tpu.models import get_model
        from production_stack_tpu.ops.pallas.paged_attention import (
            supports_pallas_decode,
        )

        # With tp>1 the KV pool is kv-head-sharded and the kernel runs under
        # shard_map over the tp axis, which is exact only when both head
        # counts divide tp (parallel/sharding.py falls back to replication
        # otherwise and the shard_map specs would be wrong).
        tp = self.tensor_parallel_size
        tp_ok = (
            tp == 1
            or (model_config.num_kv_heads % tp == 0
                and model_config.num_heads % tp == 0)
        )
        from production_stack_tpu.models import cache_specs

        specs = cache_specs(model_config)
        if specs.latent is not None:
            from production_stack_tpu.ops.pallas.paged_attention import (
                supports_latent_decode,
            )

            fits = supports_latent_decode(
                specs.latent.width, specs.latent.rank, self.block_size)
        else:
            # The row the kernel reads, which a module may pad or pack
            # (``cache_specs``), not the model's own head.
            fits = supports_pallas_decode(specs.paged_kv.head_dim,
                                          self.block_size)
        supported = (
            get_model(model_config).PAGED_DECODE_VALIDATED
            and fits and tp_ok
        )
        v = self.attn_impl
        if self.speculative_enabled and v in ("pallas", "paged"):
            raise ValueError(
                "speculative decoding requires the window attention path "
                "(the Pallas flash-decode kernel serves single-token "
                "queries; the batched verify step is a multi-token chunk) "
                "— drop attn_impl=paged or --speculative-num-tokens"
            )
        if v in ("xla", "window") or self.speculative_enabled:
            return "window"
        if v in ("pallas", "paged"):
            if not supported:
                raise ValueError(
                    f"attn_impl={v!r} requires a llama-family model whose "
                    f"head_dim divides or is a multiple of 128 (lane "
                    f"packing), with block_size dividing the superpage and "
                    f"divisible by the pack factor, and (for tp>1) head "
                    f"counts divisible by tensor_parallel_size; got "
                    f"arch={model_config.arch} "
                    f"head_dim={model_config.head_dim_} "
                    f"block_size={self.block_size} "
                    f"heads={model_config.num_heads}/"
                    f"{model_config.num_kv_heads} tp={tp}"
                )
            return "paged"
        if v != "auto":
            raise ValueError(f"Unknown attn_impl {v!r}")
        import jax

        if not supported or jax.default_backend() in ("cpu",):
            return "window"
        # Hybrid policy (r3 measurements, v5e): the window path amortizes one
        # gathered KV copy over the fused scan and wins while that copy is
        # modest (llama-1b @ live 1k: 235 vs 322 ms/dispatch); the paged
        # kernel reads the pool in place — no copy, no pool halving — and
        # wins once the live KV is large (llama-3b @ 8k: 451 vs 245 tok/s,
        # and window cannot represent 32k x batch at all). Cross over when
        # the worst-case window (every sequence at max_model_len) exceeds
        # ~4 GiB (between those two measured points). Costed in COMPUTE-
        # dtype bytes even for int8 pools: the gathered window materializes
        # DEQUANTIZED (gather_window out_dtype), so its HBM footprint — the
        # quantity the ~4 GiB crossover was tuned against — does not shrink
        # with the storage dtype.
        import jax.numpy as jnp

        kv = specs.paged_kv
        worst_window_bytes = (
            kv.layers * kv.kv_heads
            * (kv.head_dim + specs.second_pool_dim)
            * jnp.dtype(self.dtype).itemsize
            * self.max_model_len * self.max_num_seqs
        )
        return "paged" if worst_window_bytes > (4 << 30) else "window"

    def kv_cache_bytes_per_token(self, model_config) -> int:
        """Pool bytes one token occupies across all layers: K + V payload
        (or the one latent row, its padding to whole lanes included)
        in the pool's STORAGE dtype plus per-(slot, head) scale overhead
        when quantized (ops/quantization.py). Unquantized pools store the
        COMPUTE dtype (float32 pools cost 4 B/element, not bf16's 2). The
        single source for block sizing, engine.stats() pool-bytes
        reporting, and the bench roofline's KV term."""
        import jax.numpy as jnp

        from production_stack_tpu.models import cache_specs
        from production_stack_tpu.ops.quantization import SCALE_ITEMSIZE

        # The layers that keep K/V are the model module's to declare.
        specs = cache_specs(model_config)
        kv = specs.paged_kv
        if self.kv_cache_quantized:
            per_slot = specs.kv_pools * (kv.head_dim + SCALE_ITEMSIZE)
        else:
            # Both pools' rows: keys and values, or the latent row and
            # whatever lies beside it (an indexer's key, or nothing).
            per_slot = (kv.head_dim + specs.second_pool_dim) \
                * jnp.dtype(self.dtype).itemsize
        return kv.layers * kv.kv_heads * per_slot

    def state_bytes_per_seq(self, model_config) -> int:
        """Bytes of recurrent state one sequence holds whole, over every
        layer that declares some (0 for a K/V-only model)."""
        import jax.numpy as jnp
        import numpy as np

        from production_stack_tpu.models import cache_specs

        return sum(
            s.layers * int(np.prod(s.stored))
            * jnp.dtype(s.dtype or self.dtype).itemsize
            for s in cache_specs(model_config).state
        )

    def refuse_what_state_cannot_follow(self, model_config) -> None:
        """A model that declares recurrent state (models/config.py:
        CacheSpecs.state) holds, per sequence, something that is not keys
        and values of tokens: it cannot be cut at a token, copied by block
        or rolled back yet. Every feature that would need that is refused
        here, at start and by the declaration, never served wrong."""
        from production_stack_tpu.models import cache_specs

        if not cache_specs(model_config).state:
            return
        why = {
            "speculative decoding (--speculative-num-tokens)":
                bool(self.speculative_num_tokens),
            "KV offload and restore (--kv-offload-cpu / --kv-remote-url)":
                bool(self.kv_offload_cpu or self.kv_remote_url),
            "disaggregated prefill (--role prefill|decode)":
                self.role != "unified",
            "--kv-cache-dtype int8": self.kv_cache_quantized,
            "tensor or sequence parallelism (--tensor-parallel-size / "
            "--sequence-parallel-size > 1)":
                self.tensor_parallel_size > 1
                or self.sequence_parallel_size > 1,
            "LoRA adapters (--lora-modules)": bool(self.lora_modules),
        }
        asked = [name for name, on in why.items() if on]
        if asked:
            raise ValueError(
                f"model {self.model!r} keeps recurrent state per sequence, "
                f"which {'; '.join(asked)} cannot follow yet: the state has "
                f"no snapshot, block copy, rollback or sharding. Start "
                f"without it.")

    def refuse_what_latent_rows_cannot_follow(self, model_config) -> None:
        """A model whose paged rows are latent rows (models/config.py:
        CacheSpecs.latent: one pool a layer, no kv-head axis, sparse
        experts beside) is served by the plain path only. What would have
        to read, cut or shard such a row and cannot yet is refused here,
        at start, one sentence each."""
        from production_stack_tpu.models import cache_specs

        if cache_specs(model_config).latent is None:
            return
        why = {
            "tensor or sequence parallelism (--tensor-parallel-size / "
            "--sequence-parallel-size > 1): a latent row has no kv-head "
            "axis to shard and the experts no expert axis":
                self.tensor_parallel_size > 1
                or self.sequence_parallel_size > 1,
            "--kv-cache-dtype int8: a latent row is keys and values at "
            "once and has no per-row scale beside it":
                self.kv_cache_quantized,
            "KV offload and restore (--kv-offload-cpu / --kv-remote-url): "
            "the block serde moves two pools a layer":
                bool(self.kv_offload_cpu or self.kv_remote_url),
            "disaggregated prefill (--role prefill|decode): the handoff "
            "ships blocks through the same serde":
                self.role != "unified",
            "speculative decoding (--speculative-num-tokens): the verify "
            "step and the draft rings are keys and values of heads":
                bool(self.speculative_num_tokens),
            "LoRA adapters (--lora-modules): the absorbed projections and "
            "the experts have no delta path":
                bool(self.lora_modules),
        }
        asked = [name for name, on in why.items() if on]
        if asked:
            raise ValueError(
                f"model {self.model!r} caches one latent row a token, which "
                f"cannot follow: {'; '.join(asked)}. Start without it.")

    def refuse_what_a_span_cannot_follow(self, model_config) -> None:
        """A model with a layer whose attention is bounded (its module's
        ``bounded_layers``: a span of keys a query sees, ops/attention.py)
        is served by the executions that honour the bound: K/V rows of one
        chip through ``window_attention`` and the dense paged kernels.
        What would attend through another is refused here, at start."""
        from production_stack_tpu.models import get_model

        bounded = getattr(get_model(model_config), "bounded_layers", None)
        if bounded is None or not bounded(model_config):
            return
        why = {
            "speculative decoding (--speculative-num-tokens): the verify "
            "step's token tree has no bounded mask":
                bool(self.speculative_num_tokens),
            "LoRA adapters (--lora-modules): the gate and the experts have "
            "no delta path": bool(self.lora_modules),
            "--kv-cache-dtype int8: the paged kernels skip no superpage of "
            "an int8 pool's scales": self.kv_cache_quantized,
            "tensor parallelism (--tensor-parallel-size > 1): the sharded "
            "decode kernel takes no bound":
                self.tensor_parallel_size > 1,
            "sequence parallelism (--sequence-parallel-size > 1): the ring "
            "masks by causality alone": self.sequence_parallel_size > 1,
        }
        asked = [name for name, on in why.items() if on]
        if asked:
            raise ValueError(
                f"model {self.model!r} bounds the keys a query sees in "
                f"layers {bounded(model_config)}, which cannot follow: "
                f"{'; '.join(asked)}. Start without it.")

    def kv_cache_bytes_per_block(self, model_config) -> int:
        """Pool bytes one KV block occupies (block_size tokens)."""
        return self.block_size * self.kv_cache_bytes_per_token(model_config)

    @property
    def kv_cache_quantized(self) -> bool:
        from production_stack_tpu.ops.quantization import KV_CACHE_DTYPES

        if self.kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"Unknown kv_cache_dtype {self.kv_cache_dtype!r} "
                f"(supported: {', '.join(KV_CACHE_DTYPES)})"
            )
        return self.kv_cache_dtype == "int8"

    @property
    def model_name(self) -> str:
        return self.served_model_name or self.model

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_model_len // self.block_size)
