"""ModelRunner: owns device state and the jitted serving steps.

XLA discipline (the performance-critical part of the design — every item here
was profiled on a v5e in round 1/2):
  * The paged KV pool is read IN PLACE by the Pallas kernels (paged decode;
    a prefill chunk where ``prefill_reads_pool``: K/V rows and latent rows
    alike, on one device in the compute dtype) or, on the window paths
    (the window impl; a prefill over an int8 or sharded pool), gathered
    into a contiguous per-sequence WINDOW once per dispatch
    (ops/attention.py:gather_window); new KV is written back once at the
    end. Per-layer gathers/scatters against the pool cost ~7 ms
    per decode step (XLA gathers run at ~15% of HBM bandwidth; pool xs/ys in
    the layer scan copy the pool every layer); the hoisted form amortizes one
    gather over num_decode_steps * num_layers uses.
  * Every pool write is IN PLACE (ops/kv_write.py: block-wide
    dynamic_update_slices on the donated buffers). No dispatch program may
    read or write a whole pool: a middle-axis scatter did, twice per pool
    per dispatch (PERF.md §6, PR 25); audit_pool_programs() counts.
  * A fused decode dispatch runs K steps in one lax.scan: tokens produced
    mid-dispatch live in a small ring buffer [L, Hkv, B, K, Dh] that the
    attention reads alongside the window, so only ONE [K, B] device->host
    fetch happens per K*B tokens.
  * ALL small host inputs are packed into ONE int32 buffer per dispatch
    (floats bitcast): every host->device transfer is a fixed per-dispatch
    cost, so per-dispatch transfer count is 1 up + 1 down.
    Slot mappings, positions, per-step PRNG seeds, and window indices are
    derived ON DEVICE from block tables + scalars.
  * Step functions are traced per (batch_bucket, token_bucket,
    blocktable_bucket) shape family only; buckets are powers of two.
  * Sampling runs inside the same jit (sort-free: engine/sampling.py).
"""

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.flight_recorder import compile_clock
from production_stack_tpu.engine.memory_ledger import (
    MemoryLedger,
    analysis_of,
)
from production_stack_tpu.engine.program_store import (
    ProgramStore,
    source_digest,
)
from production_stack_tpu.engine.sampling import (
    sample_tokens,
    sampler_paths,
    sampling_scores,
)
from production_stack_tpu.engine.scheduler import ScheduledBatch, Sequence
from production_stack_tpu.models import get_model
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.attention import (
    NO_SPAN,
    KVView,
    gather_window,
    prefill_attn_path,
    prefill_kernel_covers,
    ring_step_path,
    segment_of_token,
)
from production_stack_tpu.ops import gated_delta, selective_scan, ssd
from production_stack_tpu.ops.kv_write import (
    pool_copies,
    read_state_rows,
    write_slabs,
    write_state_rows,
    write_token_runs,
)
from production_stack_tpu.parallel import kv_pool_sharding, param_shardings
from production_stack_tpu.parallel.mesh import Mesh
from production_stack_tpu.utils import (
    cdiv,
    init_logger,
    pow2_bucket as _bucket,
    prefill_rectangle,
    prefill_rectangles,
    prefill_row_cap,
    prefill_t_floor,
    window_mb_bucket,
)

logger = init_logger(__name__)

_SEED_MULT = np.uint32(1000003)
_POS_SENTINEL = np.int32(2**30)  # ring_pos value for not-yet-written entries
# int32 per-row scalar rows at the head of each packed host buffer; row 8 is
# the LoRA adapter index (0 = base model); rows 9/10 are the
# presence/frequency penalties (floats bitcast); row 11 is the TOKEN-CHAIN
# source: an index into the PREVIOUS dispatch's device-resident last-token
# vector (-1 = use the host tokens0 in row 0). Chaining lets the engine
# issue dispatch N+1 before fetching N's tokens — the blocking
# device->host sync then overlaps N+1's execution. Row 12 is the
# sequence's slot in the speculative draft-KV ring pools (0 when
# speculative decoding is off — the row is then never read), or, for a model
# that declares recurrent state (which refuses speculation), its slot in the
# state pools (0: the scratch slot of padded rows). Row 13 is the
# per-row speculative draft depth gamma in [0, speculative_num_tokens]
# (the round-10 adaptive controller's output; packed as N itself when the
# controller is off, never read without speculation).
NUM_SCALARS = 14
# Static buckets for the per-dispatch top-logprobs width: OpenAI completions
# allows logprobs<=5, chat top_logprobs<=20; two buckets bound the compiled
# variant count. 0 = the (default) no-logprobs variants.
LOGPROB_BUCKETS = (8, 20)


def logprobs_bucket(k: int) -> int:
    """Smallest static top-k bucket covering a requested logprobs width."""
    for b in LOGPROB_BUCKETS:
        if k <= b:
            return b
    return LOGPROB_BUCKETS[-1]


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def resolved_seed_base(request_id: str, sampling) -> int:
    """The uint32 seed base a request's token seeds derive from. Exposed
    (via the API server's per-chunk resume payload) so a mid-stream resume
    on a DIFFERENT engine process reproduces the exact seed schedule even
    for unseeded requests — ``hash(request_id)`` is randomized per process
    (PYTHONHASHSEED), so the resolved value must ride the wire."""
    base = sampling.seed if sampling.seed is not None \
        else (hash(request_id) & 0x7FFFFFFF)
    return int(base) & 0xFFFFFFFF


def _seed_base(seq: Sequence) -> np.uint32:
    return np.uint32(resolved_seed_base(seq.request_id, seq.sampling))


def _token_seed(seq: Sequence, gen_index: int) -> np.uint32:
    """Seed for the token at generation index ``gen_index`` of ``seq``.

    Per-sequence-deterministic: the same request produces the same tokens
    regardless of batching, scan length, or prefill/decode path. The device
    computes the same arithmetic in uint32 (see _derive_seeds)."""
    return np.uint32(
        (int(_seed_base(seq)) * int(_SEED_MULT) + gen_index) & 0xFFFFFFFF
    )


class SpecGammaController:
    """Host-side per-sequence draft-depth controller (docs/PERF.md round
    10). Tracks an acceptance EMA per request from the per-row
    draft/accept counts every speculative dispatch already fetches, and
    picks each row's next draft depth gamma with sampling.adaptive_gamma
    (largest g with ema^g >= threshold — Leviathan'23's expected-value
    model applied per sequence). Rows that collapse to gamma=0 are
    re-probed with gamma=1 every ``probe_period`` dispatches so a
    sequence whose output turns predictable again can recover. Purely
    deterministic given the observation trace — the EMA-convergence test
    drives it with a scripted one."""

    def __init__(self, n_max: int, decay: float, threshold: float,
                 probe_period: int):
        self.n_max = n_max
        self.decay = decay
        self.threshold = threshold
        self.probe_period = probe_period
        self._ema: Dict[str, float] = {}
        self._since_probe: Dict[str, int] = {}

    def update(self, request_id: str, drafted: int, accepted: int) -> None:
        """Fold one dispatch's (drafted, accepted) counts for a request
        into its EMA. A gamma=0 dispatch drafts nothing and is NOT an
        observation (the EMA must not drift on no data)."""
        if drafted <= 0:
            return
        obs = min(1.0, accepted / drafted)
        prev = self._ema.get(request_id, 1.0)
        self._ema[request_id] = (
            (1.0 - self.decay) * prev + self.decay * obs
        )

    def gamma(self, request_id: str) -> int:
        """Draft depth for the request's NEXT dispatch (optimistic full
        depth before the first observation)."""
        from production_stack_tpu.engine.sampling import adaptive_gamma

        g = adaptive_gamma(
            self._ema.get(request_id, 1.0), self.n_max, self.threshold
        )
        if g == 0 and self.probe_period > 0:
            waited = self._since_probe.get(request_id, 0) + 1
            if waited >= self.probe_period:
                self._since_probe[request_id] = 0
                return 1
            self._since_probe[request_id] = waited
        return g

    def ema(self, request_id: str) -> float:
        return self._ema.get(request_id, 1.0)

    def forget(self, request_id: str) -> None:
        self._ema.pop(request_id, None)
        self._since_probe.pop(request_id, None)

    def mean_ema(self) -> float:
        """Mean acceptance EMA over live (tracked) sequences — the
        pstpu:spec_acceptance_ema gauge (one gauge, not a per-request
        label set: request ids are unbounded-cardinality)."""
        if not self._ema:
            return 0.0
        return sum(self._ema.values()) / len(self._ema)


class DispatchHandle:
    """An issued device dispatch whose results are fetched lazily.

    fetch() performs the blocking device->host sync (idempotent; caches
    the result). The pipelined engine loop issues the NEXT dispatch before
    fetching, so the sync overlaps device execution. ``program`` says
    which program the dispatch enqueued (``ModelRunner.program``) and
    ``rows`` for how many sequences."""

    __slots__ = ("_fetch", "_result", "_done", "issue_time", "program",
                 "rows")

    def __init__(self, fetch_fn, program: Optional[Dict] = None,
                 rows: int = 0):
        self._fetch = fetch_fn
        self.program = program
        self.rows = rows
        self._result = None
        self._done = False
        self.issue_time = time.monotonic()

    def fetch(self):
        if not self._done:
            self._result = self._fetch()
            self._done = True
            self._fetch = None
        return self._result


def _setup_compilation_cache(cache_dir: str, device) -> Optional[str]:
    """Resolve this process's persistent XLA compile-cache directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: JAX has
    already read it into its own config, so that directory is used as it
    is — no ``jax.config.update`` of the directory, no sub-directory on
    top (the path is part of what a deployment mounts and what the next
    process must find again).

    Otherwise ``cache_dir`` (EngineConfig.compilation_cache_dir) gets a
    platform-fingerprint sub-directory (platform + device kind + jax
    version: CPU AOT artifacts replayed on a host with other machine
    features emit XLA warnings and can mis-specialize, VERDICT r3 weak #8)
    and JAX is pointed at it. Re-pointable: a later engine in the same
    process with another directory resets JAX's already-opened cache so
    the new directory takes effect.

    Where there is a cache, two PROCESS-WIDE JAX options are set with it
    (both are about what a cache key is; docs/OBSERVABILITY.md, "Compile
    cache"): no minimum compile time, and source locations of ONE frame
    (``jax_traceback_in_locations_limit``), which also shortens the
    locations in every program's text and in a trace's ``source`` fields
    to the operation's own line.

    Returns the directory the hit/miss accounting and the warmup manifest
    read; None (uncached) only when the variable is unset AND ``cache_dir``
    is empty."""
    import os
    import re

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if not cache_dir:
            return None
        fingerprint = re.sub(
            r"[^A-Za-z0-9_.-]+", "-",
            f"{device.platform}-{device.device_kind}-jax{jax.__version__}",
        )
        path = os.path.join(cache_dir, fingerprint)
        if jax.config.jax_compilation_cache_dir != path:
            from jax.experimental.compilation_cache import compilation_cache

            jax.config.update("jax_compilation_cache_dir", path)
            compilation_cache.reset_cache()
    # Every step compile is load-bearing for warm boot: the fast-start
    # warm-vs-cold bar (docs/ELASTIC.md) needs even sub-second CPU-CI
    # compiles cached, and the hit/miss accounting below reads "no new
    # artifact" as a hit — so no min-compile-time filter.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A Pallas kernel's serialized module is part of its program's cache
    # key and carries each operation's source location WITH its callers'
    # frames (ten by default). A kernel traced through few frames (the
    # hybrid's prefill kernel: eight below ``_prefill_impl``) then keys
    # differently from the AOT prepass, the execute pass and a deferred
    # variant's first use in serving, and every boot compiles again
    # (PERF.md §6, PR 35; §7, PR 29). One frame — the operation's own
    # line — is the same from every caller, and no longer moves with this
    # file's line numbers.
    jax.config.update("jax_traceback_in_locations_limit", 1)
    return path


def _cache_entries(cache_dir: Optional[str]) -> Optional[frozenset]:
    """Names of the persistent-cache artifacts (the ``*-cache`` files jax
    writes; ``-atime`` markers are touched on hits too, so only ``-cache``
    files distinguish a fresh compile from a cache load). A compile that
    adds a NAME is a miss — names, not a count, because a size-capped
    cache (JAX_COMPILATION_CACHE_MAX_SIZE) evicts while it writes. None
    when unreadable."""
    if not cache_dir:
        return None
    import os

    try:
        return frozenset(
            f for f in os.listdir(cache_dir) if f.endswith("-cache")
        )
    except OSError:
        return None


def _device_label(device) -> str:
    """``platform:id``: how metrics and the memory ledger name a device."""
    return f"{device.platform}:{device.id}"


def _bytes_by_device(arrays) -> Dict[str, int]:
    """``{"platform:id": bytes}`` these arrays lay on each device, each
    array once, from shapes and shardings alone."""
    out: Dict[str, int] = {}
    seen = set()
    for array in arrays:
        if id(array) in seen:
            continue
        seen.add(id(array))
        per_device = int(np.prod(array.sharding.shard_shape(array.shape),
                                 dtype=np.int64)) * array.dtype.itemsize
        for d in array.sharding.device_set:
            label = _device_label(d)
            out[label] = out.get(label, 0) + per_device
    return out


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        model_config: ModelConfig,
        mesh: Mesh,
        params: Optional[Dict] = None,
        num_kv_blocks: Optional[int] = None,
        lora_registry=None,
    ):
        self.config = config
        # {target: (A [L,Na+1,in,r], B [L,Na+1,r,out])} device stacks; rows
        # select adapters by index (models/lora.py:LoRARegistry). None/empty
        # keeps the traced graphs LoRA-free.
        self.lora_stacks = lora_registry.stacks() if lora_registry else None
        self.model_config = model_config
        self.mesh = mesh
        # What holds the devices' memory (engine/memory_ledger.py).
        self.memory = MemoryLedger(self.device_memory)
        # "paged": decode attends directly against the HBM pool inside the
        # Pallas flash-decode kernel (no gathered window copy, pool not
        # halved). "window": decode gathers the live KV into a contiguous
        # per-dispatch window (models the kernel can't serve: head_dim < 128).
        self.attn_impl = config.resolved_attn_impl(model_config)
        devices = list(mesh.devices.flat)
        # Interpret mode is for the CPU backend (tests); on a TPU the kernel
        # runs compiled — the engine reports which (ServingEngine.report).
        self._pallas_interpret = devices[0].platform == "cpu"
        # Whether an executable that was itself LOADED (JAX's cache
        # supplied it) may be stored again. XLA:CPU serializes such a one
        # without its kernels' functions, and the copy fails when it runs
        # ("Function wrapped_iota not found"); so on the CPU backend (tests,
        # rehearsals) only a program compiled here is stored, and one the
        # cache supplied is traced again by the next boot, as it always was.
        self._stores_loaded = devices[0].platform != "cpu"
        self.dtype = _dtype(config.dtype)
        # KV-cache STORAGE dtype (--kv-cache-dtype): int8 pools carry a
        # per-(slot, head) bf16 scale sidecar (ops/quantization.py) and
        # every reader dequantizes inline; compute stays self.dtype.
        self.kv_quantized = config.kv_cache_quantized
        self.kv_store_dtype = jnp.int8 if self.kv_quantized else self.dtype
        # Tokens written to a quantized pool (prefill + fused decode +
        # block restores), for the pstpu:kv_quant_bytes_saved_total series.
        self.kv_quant_tokens_written = 0
        # Resolved persistent-cache dir (None = uncached): warmup counts
        # per-family cache hits/misses against its artifact files, the
        # fast-start telemetry behind pstpu:startup_cache_hit_families.
        self.compilation_cache_path = _setup_compilation_cache(
            config.compilation_cache_dir, devices[0]
        )
        # Startup-phase telemetry (docs/ELASTIC.md): one-shot durations of
        # the weight-load / AOT-compile / warmup-execute phases plus the
        # per-compiled-variant persistent-cache hit/miss split.
        self.startup_weight_load_seconds = 0.0
        self.startup_compile_seconds = 0.0
        self.startup_warmup_seconds = 0.0
        self.startup_cache_hit_families = 0
        self.startup_cache_miss_families = 0
        self.startup_deferred_families = 0
        # Families warmup compiled+executed, and warmup stages that raised
        # (the AOT prepass and the execute pass each log and carry on so a
        # warmup fault never kills serving — counted here so a boot that
        # limped is visible in the engine's report, not only in its log).
        self.startup_warmed_families = 0
        self.startup_warmup_failures = 0
        # Dispatch programs this process holds as LOADED executables, by
        # ``program()["key"]`` (engine/program_store.py): compiled and
        # stored by a cold boot's warm-up, loaded by a manifest-verified
        # warm boot's. ``_dispatch`` calls a program through this table and
        # only one that is not in it through the jitted function, so a
        # program is resident once. ``_stored``: programs the store holds
        # that are loaded at first use (a warm boot's deferred variants).
        self._programs: Dict[str, "jax.stages.Compiled"] = {}
        self._store = None
        self._stored: frozenset = frozenset()
        # Programs a boot loaded without tracing them (each also counts as
        # a cache hit).
        self.startup_loaded_families = 0

        model = get_model(model_config)
        # What the architecture caches per sequence, as its module declares
        # it (models/config.py:CacheSpecs): the layers that keep paged K/V,
        # and any state a sequence holds whole. Pool shapes, the block
        # budget and the dispatch programs read these and never the
        # architecture's name.
        specs = model.cache_specs(model_config)
        self.kv_spec = specs.paged_kv
        self.state_specs = specs.state
        # Pools a layer keeps a token in (keys and values; one where the
        # rows are latent rows, which are both), and the width of a token's
        # row in the second pool: 0 where there is none (the second pool
        # then exists with no byte in it, so that every program keeps its
        # operands and the writes below adapt to nothing but shapes), or
        # the lanes of an indexer's key beside a latent row.
        self.kv_pools = specs.kv_pools
        self.kv_v_dim = specs.second_pool_dim
        # Lanes of a token's row that are its values: the row of the second
        # pool, or the head of a latent row (its compressed KV).
        self.kv_value_dim = self.kv_spec.head_dim if specs.latent is None \
            else specs.latent.rank
        # int32 counters the model's forward returns last, summed over its
        # layers (models/deepseek_v3.py: what the experts were given); a
        # dispatch sums them over its steps and hands them out beside its
        # pools, and they are read when a later fetch has made them ready.
        self.fwd_stats = tuple(getattr(model, "FORWARD_STATS", ()))
        # The states (by name) the module can carry across a segment
        # boundary inside a packed prefill row (``prefill_packs``).
        self.states_crossing_segments = frozenset(
            getattr(model, "STATES_CROSSING_SEGMENTS", ()))
        self.fwd_stats_total = {
            kind: dict.fromkeys(self.fwd_stats, 0)
            for kind in ("decode", "prefill")}
        self._fwd_stats_pending: List[Tuple[int, str, jax.Array]] = []
        self._fwd_stats_noted = 0
        # Slots of the state pools: one per sequence the scheduler can hold
        # (--max-num-seqs) plus slot 0, the scratch slot every padded row of
        # a dispatch reads and writes.
        self.num_state_slots = config.max_num_seqs + 1 \
            if self.state_specs else 0
        init_fn = self._init_fn = model.init_params
        self._forward, self._logits_fn = model.forward, model.compute_logits
        self._params = None
        self._param_thread = None
        self._param_error: Optional[BaseException] = None
        # Bytes the still-loading weights WILL occupy on EACH mesh device
        # (their tp shard) — subtracted from the free-HBM probe so a
        # deferred load can't let the KV pool over-commit the memory the
        # weights land in later.
        self._pending_param_bytes = 0
        defer = (
            params is None
            and config.enable_warmup
            and getattr(config, "overlap_weight_load", True)
            and not config.speculative_num_tokens
        )
        if params is not None:
            self._bind_params(params)
        elif defer:
            # Weight/compile overlap (docs/ELASTIC.md): weight loading is
            # disk/IO-bound while AOT warmup compilation is host-CPU-bound;
            # load in a background thread and let warmup() run its
            # compile-only prepass meanwhile. Everything needing concrete
            # weights goes through the ``params`` property, which joins.
            import threading

            abstract = jax.eval_shape(
                lambda: init_fn(
                    model_config, jax.random.PRNGKey(0), self.dtype
                )
            )
            self._pending_param_bytes = sum(
                int(np.prod(sh.shard_shape(leaf.shape)))
                * jnp.dtype(leaf.dtype).itemsize
                for leaf, sh in zip(
                    jax.tree.leaves(abstract),
                    jax.tree.leaves(
                        param_shardings(model_config, mesh, abstract)
                    ),
                )
            )
            self._param_thread = threading.Thread(
                target=self._load_params_background,
                daemon=True, name="weight-loader",
            )
            self._param_thread.start()
        else:
            t0 = time.monotonic()
            params, _ = self._load_or_init_params(
                model_config, config.model, init_fn
            )
            self._bind_params(params)
            self.startup_weight_load_seconds = time.monotonic() - t0

        # --- speculative decoding (docs/PERF.md round 8) ---------------
        # Draft model + per-sequence draft-KV rings. The draft never
        # touches the paged pool: its KV lives in [L_d, Hkv_d, S, R, Dh_d]
        # ring pools (S = sequence slots, R = ring tokens) in the COMPUTE
        # dtype, gathered into batch rows per dispatch and scattered back.
        # Allocated BEFORE the KV pool is sized: _derive_num_blocks hands
        # hbm_utilization of FREE device memory to the paged pool, so the
        # draft rings must already be resident or spec-on startup
        # over-commits HBM (the rings scale with slots x ring length —
        # bound them with --speculative-draft-window on big deployments).
        self.spec_n = int(config.speculative_num_tokens)
        if self.spec_n:
            self.spec_draft_config = config.resolved_draft_config()
            draft = get_model(self.spec_draft_config)
            d_init = draft.init_params
            self._draft_forward = draft.forward
            self._draft_logits = draft.compute_logits
            if config.speculative_model == config.model:
                # Self-draft: share the target's params outright (the
                # parity/bench configuration — identical weights make
                # greedy acceptance ~1.0 when the ring covers the context).
                self.spec_params = self.params
            else:
                self.spec_params, d_loaded = self._load_or_init_params(
                    self.spec_draft_config, config.speculative_model,
                    d_init,
                )
                if not d_loaded and config.load_format != "dummy":
                    # Correctness is unaffected (accepted tokens are
                    # always the TARGET's samples), so a random draft is
                    # otherwise invisible: acceptance ~0 and speculation
                    # becomes pure overhead.
                    logger.warning(
                        "Speculative draft %r resolved to RANDOM init "
                        "weights (not a local checkpoint dir): expect "
                        "~zero acceptance — speculation will cost "
                        "throughput, not add it",
                        config.speculative_model,
                    )
            self.spec_ring_len = config.speculative_ring_len
            # Slot capacity: every RUNNING row plus a prefill batch of
            # fresh prompts can hold a slot at once; LRU eviction below is
            # the backstop, never the plan.
            self.spec_num_slots = config.max_num_seqs + prefill_row_cap(config)
            self._alloc_spec_pools()
            from collections import OrderedDict

            self._spec_slots: "OrderedDict[str, int]" = OrderedDict()
            self._spec_free = list(range(self.spec_num_slots))
            # Per-request position (exclusive) the draft ring is warmed
            # to — the host-side ledger behind _spec_catch_up.
            self._spec_warmed: Dict[str, int] = {}
            # Telemetry (accumulated at fetch): proposals the draft made
            # and how many survived verification.
            self.spec_draft_tokens_total = 0
            self.spec_accepted_tokens_total = 0
            # --- round 10: tree verify + adaptive per-row gamma --------
            self.spec_tree_width = int(config.speculative_tree_width)
            if self.spec_tree_width > 1:
                from production_stack_tpu.ops.tree_mask import (
                    main_chain_indices, tree_attention_bias, tree_structure,
                )

                parents, depths = tree_structure(
                    self.spec_n, self.spec_tree_width
                )
                self._spec_tree_parents = parents        # np [T]
                self._spec_tree_depths = depths          # np [T]
                self._spec_tree_bias = jnp.asarray(
                    tree_attention_bias(parents)
                )                                        # [T, T] f32
                self._spec_main_chain = main_chain_indices(
                    self.spec_n, self.spec_tree_width
                )                                        # np [N+1]
            self.spec_adaptive = bool(config.speculative_adaptive)
            self._spec_controller = (
                SpecGammaController(
                    self.spec_n,
                    config.speculative_ema_decay,
                    config.speculative_gamma_threshold,
                    config.speculative_probe_period,
                ) if self.spec_adaptive else None
            )
            # Tree/depth telemetry: lifetime tree-node counter, served
            # draft-depth accumulators (sum of per-row gammas over live
            # verify cycles), gamma=0 full-degrade dispatch counter, and
            # a windowed per-fetch (drafts, accepted) deque behind
            # pstpu:spec_acceptance_rate_window (mirrors the router
            # engine_stats delta scraper: lifetime counters alone can't
            # show "acceptance collapsed five minutes ago").
            self.spec_tree_nodes_total = 0
            self.spec_draft_depth_sum = 0
            self.spec_live_cycles_total = 0
            self.spec_gamma0_dispatches_total = 0
            from collections import deque

            self._spec_window: "deque[Tuple[int, int]]" = deque(maxlen=64)
        else:
            self.spec_params = None
            self.spec_ring_len = 1
            self.spec_draft_tokens_total = 0
            self.spec_accepted_tokens_total = 0
            self.spec_tree_width = 1
            self.spec_adaptive = False
            self._spec_controller = None
            self.spec_tree_nodes_total = 0
            self.spec_draft_depth_sum = 0
            self.spec_live_cycles_total = 0
            self.spec_gamma0_dispatches_total = 0

        # pstpu:sample_dispatches_*: how often the sampler's skipped picks
        # stay skipped (engine/sampling.py), counted at issue from the
        # vectors the dispatch packs.
        self.sample_dispatches_total = 0
        self.sample_dispatches_greedy_total = 0
        self.sample_dispatches_filtered_total = 0

        # Before the K/V pool is sized from what is free (as the draft
        # rings above).
        self._alloc_state_pools()
        self.num_kv_blocks = num_kv_blocks or config.num_kv_blocks or \
            self._derive_num_blocks()
        self._alloc_kv_pools()

        from production_stack_tpu.parallel.mesh import AXIS_SP

        if mesh.shape[AXIS_SP] > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # Prefill activations shard the token axis over sp (see
            # models/llama.py forward docstring).
            self._act_sharding = NamedSharding(mesh, P(None, AXIS_SP, None))
        else:
            self._act_sharding = None
        self._decode = jax.jit(
            self._decode_impl,
            static_argnames=("b", "mb", "num_steps", "use_cached_window",
                             "has_penalties", "logprobs_k", "spec_on"),
            donate_argnums=(2, 3, 4, 5, 6, 7, 11, 12, 13, 14),
        )
        # Persistent decode window (window impl only): consecutive decode
        # dispatches over the SAME rows reuse the gathered window and append
        # each dispatch's new KV into it, instead of re-gathering the whole
        # live KV every dispatch (~80-100 ms fixed cost at 16x2k-token rows
        # on a v5e — r3 profiling). {ids, b, mb, end[], win=(k, v)}.
        self._win_cache = None
        # Token-chain state: recent dispatches' device-resident last-token
        # vectors + row mappings ({request_id: row index}) and preemption
        # epochs, so the next decode dispatch can start from tokens the
        # host has not fetched yet (pipelined engine loop). A LIST (newest
        # first) because the two-slot overlap loop can interleave kinds —
        # e.g. decode D1, prefill P1, decode D2: D2's rows chain from D1's
        # vector even though P1's entry is newer — and P1's final rows
        # chain from P1's vector when D2 is issued before P1's apply. Any
        # one decode still chains from a SINGLE source vector: the engine
        # loop holds at most two dispatches in flight, so only one is
        # unapplied when a decode is issued (engine._run_loop);
        # _issue_decode checks that invariant.
        self._b_max = _bucket(config.max_num_seqs, 1,
                              max(1, config.max_num_seqs))
        self._chains: List[Dict] = []
        # Entries only matter while their dispatch (or a row's last token)
        # is unapplied; with at most pipeline_depth dispatches outstanding,
        # the newest depth+1 token-producing entries cover every chainable
        # row.
        self._max_chains = max(2, getattr(config, "pipeline_depth", 2))
        # COMMITTED + mesh-replicated, so its pjit cache key matches the
        # chain vectors dispatches return (an uncommitted jnp.zeros would
        # key a separate executable variant — the committed/uncommitted
        # cache-key split that also bites the cached-window warmup).
        from jax.sharding import NamedSharding, PartitionSpec

        self._zero_last = jax.device_put(
            jnp.zeros((self._b_max,), jnp.int32),
            NamedSharding(mesh, PartitionSpec()),
        )
        self._prefill = jax.jit(
            self._prefill_impl,
            static_argnames=("b", "t", "mb", "has_window", "b_max",
                             "has_penalties", "logprobs_k", "segs"),
            donate_argnums=(2, 3, 4, 5, 8, 9, 10, 11),
        )

    # ----------------------------------------------------------------- weights
    @property
    def params(self):
        """The device-resident parameter tree. With overlapped weight
        loading (docs/ELASTIC.md) the first access joins the background
        loader thread, so every consumer — dispatch issue, warmup execute,
        embed — transparently waits for real weights while the AOT compile
        prepass ran concurrently."""
        if self._params is None and self._param_thread is not None:
            self.wait_for_weights()
        return self._params

    @params.setter
    def params(self, value) -> None:
        self._params = value

    @property
    def weights_ready(self) -> bool:
        return self._params is not None

    def wait_for_weights(self) -> None:
        """Join the background weight loader (no-op when weights are
        already bound). Re-raises the loader's failure — a broken
        checkpoint must fail startup exactly like the serial path did."""
        t = self._param_thread
        if t is not None:
            t.join()
            self._param_thread = None
        if self._param_error is not None:
            err, self._param_error = self._param_error, None
            raise err

    def _bind_params(self, params) -> None:
        shardings = param_shardings(self.model_config, self.mesh, params)
        self._params = jax.tree.map(jax.device_put, params, shardings)
        self._pending_param_bytes = 0

    def _load_params_background(self) -> None:
        t0 = time.monotonic()
        try:
            params, _ = self._load_or_init_params(
                self.model_config, self.config.model, self._init_fn
            )
            self._bind_params(params)
        except BaseException as e:  # noqa: BLE001 — re-raised on join
            self._param_error = e
        finally:
            self.startup_weight_load_seconds = time.monotonic() - t0

    def _load_or_init_params(self, model_config, source: str, init_fn):
        """Load a model's params from a local HF checkpoint dir, or init
        randomly (dummy/test configs). ONE loader for the target and the
        speculative draft so checkpoint-loading semantics can't diverge.
        Returns (params, loaded_from_checkpoint)."""
        import os

        if self.config.load_format != "dummy" and os.path.isdir(source):
            # Real checkpoint: shardings from the ABSTRACT tree, then each
            # tensor stack goes host->device already TP-placed.
            from production_stack_tpu.models.weights import load_hf_params

            abstract = jax.eval_shape(
                lambda: init_fn(
                    model_config, jax.random.PRNGKey(0), self.dtype
                )
            )
            shardings = param_shardings(model_config, self.mesh, abstract)
            return load_hf_params(
                model_config, source, self.dtype, shardings
            ), True
        return init_fn(
            model_config, jax.random.PRNGKey(self.config.seed), self.dtype
        ), False

    # ------------------------------------------------------------------ sizing
    def _alloc_kv_pools(self) -> None:
        """(Re)build the device KV pools: payload in the KV-cache storage
        dtype, plus — quantized mode — the per-(slot, head) dequant scale
        sidecars, kv-head-sharded like the payload."""
        mc, cfg = self.model_config, self.config
        num_slots = self.num_kv_blocks * cfg.block_size
        kv_shape = (self.kv_spec.layers, self.kv_spec.kv_heads, num_slots,
                    self.kv_spec.head_dim)
        kv_sh = kv_pool_sharding(mc, self.mesh)
        self.kv_k = jax.device_put(
            jnp.zeros(kv_shape, self.kv_store_dtype), kv_sh
        )
        self.kv_v = jax.device_put(
            jnp.zeros((*kv_shape[:3], self.kv_v_dim), self.kv_store_dtype),
            kv_sh
        )
        if self.kv_quantized:
            from production_stack_tpu.ops.quantization import SCALE_DTYPE
            from production_stack_tpu.parallel import kv_scale_sharding

            sc_shape = kv_shape[:-1]
            sc_sh = kv_scale_sharding(mc, self.mesh)
            self.kv_k_scale = jax.device_put(
                jnp.zeros(sc_shape, SCALE_DTYPE), sc_sh
            )
            self.kv_v_scale = jax.device_put(
                jnp.zeros(sc_shape, SCALE_DTYPE), sc_sh
            )
        else:
            self.kv_k_scale = self.kv_v_scale = None

    def _alloc_state_pools(self) -> None:
        """(Re)build the per-sequence state pools a model declares
        (``[slots, layers, *shape]`` each — slots first, so a sequence's
        state is one contiguous slab — zeroed, replicated; none for a
        K/V-only model). Every dispatch gathers its rows' slots once, carries
        the rows through its loops and writes them back in place once
        (ops/kv_write.py:write_state_rows), as the K/V pools are."""
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(self.mesh, PartitionSpec())
        self.state_pools = tuple(
            jax.device_put(
                jnp.zeros((self.num_state_slots, s.layers, *s.stored),
                          _dtype(s.dtype) if s.dtype else self.dtype), rep)
            for s in self.state_specs
        )

    @property
    def state_pool_bytes(self) -> int:
        return sum(int(p.size) * p.dtype.itemsize for p in self.state_pools)

    def _read_state_rows(self, state_pools, slots, fresh=None):
        """The rows' state [b, layers, ...] from their slots; rows that
        are ``fresh`` (a sequence's first chunk: the slot still holds its
        last owner's state) start from zeros."""
        with jax.named_scope("kv_write"), jax.named_scope("state_read"):
            rows = read_state_rows(state_pools, slots)
            if fresh is not None:
                rows = tuple(
                    jnp.where(fresh.reshape((-1,) + (1,) * (r.ndim - 1)),
                              jnp.zeros((), r.dtype), r)
                    for r in rows)
        return rows

    # -------------------------------------------------- speculative state
    def _alloc_spec_pools(self) -> None:
        """Per-sequence draft-KV ring pools [L_d, Hkv_d, S, R, Dh_d] plus
        the per-entry position plane [S, R] (sentinel = unwritten). Held in
        the COMPUTE dtype (bf16 on TPU) — the draft is small and its KV is
        never paged, offloaded, or quantized."""
        dmc = self.spec_draft_config
        s, r = self.spec_num_slots, self.spec_ring_len
        shape = (dmc.num_layers, dmc.num_kv_heads, s, r, dmc.head_dim_)
        ring_bytes = (
            2 * int(np.prod(shape)) * jnp.dtype(self.dtype).itemsize
        )
        logger.info(
            "Speculative draft-KV rings: %d slots x %d tokens "
            "(%.1f MiB total, draft=%s) — bound with "
            "--speculative-draft-window",
            s, r, ring_bytes / (1 << 20), dmc.name,
        )
        self.spec_k = jnp.zeros(shape, self.dtype)
        self.spec_v = jnp.zeros(shape, self.dtype)
        self.spec_pos = jnp.full((s, r), _POS_SENTINEL, jnp.int32)

    @functools.cached_property
    def _reset_spec_slot_jit(self):
        def reset(spec_pos, slot):
            return spec_pos.at[slot].set(_POS_SENTINEL)
        return jax.jit(reset, donate_argnums=(0,))

    def spec_slot(self, request_id: str) -> int:
        """Get-or-allocate the sequence's draft-ring slot. Fresh
        allocations reset the slot's position plane so a previous owner's
        ring entries can never be attended (wrong draft context is an
        acceptance problem, not a correctness one — but a free one to
        avoid). Falls back to LRU eviction if the free list is empty."""
        slot = self._spec_slots.get(request_id)
        if slot is not None:
            self._spec_slots.move_to_end(request_id)
            return slot
        if self._spec_free:
            slot = self._spec_free.pop()
        else:
            evicted, slot = self._spec_slots.popitem(last=False)
            # The evicted stream's ring is gone: drop its warm ledger too,
            # or _spec_catch_up would consider it warm forever and never
            # re-ingest (permanent acceptance collapse for that stream).
            self._spec_warmed.pop(evicted, None)
            logger.warning(
                "Draft-ring slots exhausted; evicting %s (cold draft "
                "context lowers acceptance for that stream only)", evicted,
            )
        self.spec_pos = self._reset_spec_slot_jit(
            self.spec_pos, jnp.int32(slot)
        )
        self._spec_slots[request_id] = slot
        self._spec_warmed[request_id] = 0
        return slot

    def release_spec_slot(self, request_id: str) -> None:
        """Return a finished sequence's draft-ring slot (idempotent)."""
        if not self.spec_n:
            return
        self._spec_warmed.pop(request_id, None)
        if self._spec_controller is not None:
            self._spec_controller.forget(request_id)
        slot = self._spec_slots.pop(request_id, None)
        if slot is not None:
            self._spec_free.append(slot)

    @functools.cached_property
    def _spec_ingest_jit(self):
        """Draft catch-up dispatch: replay tokens the TARGET never
        prefilled on this engine — device prefix-cache hits, shared-tier
        restores, disagg decode hops — through the DRAFT model so its
        ring still holds the context (a cold ring collapses acceptance;
        the whole long-history workload is cache hits). One row per call;
        T is a static bucket."""
        dmc = self.spec_draft_config
        r_len = self.spec_ring_len

        def ingest(dparams, spec_k, spec_v, spec_pos, slot, tokens,
                   start, length, *, t: int):
            dnl, dhkv, ddh = (dmc.num_layers, dmc.num_kv_heads,
                              dmc.head_dim_)
            sl = jnp.clip(slot, 0, spec_pos.shape[0] - 1)[None]
            drk = spec_k[:, :, sl]                  # [Ld, Hd, 1, R, Dd]
            drv = spec_v[:, :, sl]
            drp = spec_pos[sl]                      # [1, R]
            iota_t = jnp.arange(t, dtype=jnp.int32)
            positions = (start + iota_t)[None, :]
            d_max = self._spec_draft_max_pos
            _, dk, dv = self._draft_forward(
                dparams, dmc, tokens[None, :],
                jnp.minimum(positions, d_max - 1), length[None],
                KVView(ring_k=drk, ring_v=drv, ring_pos=drp),
            )
            in_chunk = iota_t[None, :] < length
            widx = jnp.where(
                in_chunk, positions % r_len, r_len
            ).reshape(-1)
            drk = drk.reshape(dnl, dhkv, r_len, ddh).at[:, :, widx].set(
                dk.reshape(dnl, dhkv, t, ddh), mode="drop"
            ).reshape(dnl, dhkv, 1, r_len, ddh)
            drv = drv.reshape(dnl, dhkv, r_len, ddh).at[:, :, widx].set(
                dv.reshape(dnl, dhkv, t, ddh), mode="drop"
            ).reshape(dnl, dhkv, 1, r_len, ddh)
            drp = drp.reshape(-1).at[widx].set(
                positions.reshape(-1), mode="drop"
            ).reshape(1, r_len)
            return self._write_spec_rows(
                spec_k, spec_v, spec_pos, sl, drk, drv, drp
            )

        return jax.jit(ingest, static_argnames=("t",),
                       donate_argnums=(1, 2, 3))

    def _spec_catch_up(self, seq, upto: int) -> None:
        """Ensure the sequence's draft ring covers context up to position
        ``upto`` (exclusive): ingest the most recent min(R, upto) tokens
        the ring has not seen. Acceptance-only machinery — never output
        correctness — but without it a prefix-cache hit leaves the draft
        proposing from near-zero context."""
        rid = seq.request_id
        warmed = self._spec_warmed.get(rid, 0)
        if warmed >= upto:
            return
        r_len = self.spec_ring_len
        # Contiguous-or-windowed: continue from what the ring holds, or —
        # when the gap exceeds the ring — just (re)ingest the last R
        # tokens (a full-ring rewrite, masking out every stale entry).
        lo = max(0, upto - r_len, min(warmed, upto))
        toks = seq.all_token_ids[lo:upto]
        if not toks:
            self._spec_warmed[rid] = upto
            return
        slot = self.spec_slot(rid)
        t = _bucket(len(toks), 16, max(16, 1 << (r_len - 1).bit_length()))
        padded = np.zeros((t,), np.int32)
        padded[:len(toks)] = toks
        self.spec_k, self.spec_v, self.spec_pos = self._spec_ingest_jit(
            self.spec_params, self.spec_k, self.spec_v, self.spec_pos,
            jnp.int32(slot), jnp.asarray(padded), jnp.int32(lo),
            jnp.int32(len(toks)), t=t,
        )
        self._spec_warmed[rid] = upto

    @property
    def _spec_draft_max_pos(self) -> int:
        """Position clamp for DRAFT forwards. RoPE models (llama family)
        take any position — clamping below the target's own bound would
        desynchronize draft and target rotary phases past the clamp and
        collapse acceptance (measured: ~0.78 -> 0.04 at 2k context).
        OPT-style learned position tables are bounded by the embedding
        table size (acceptance-only saturation beyond it)."""
        dmc = self.spec_draft_config
        bound = get_model(dmc).position_bound(dmc)
        if bound is None:
            return self.config.max_model_len
        return min(self.config.max_model_len, bound)

    def _spec_pool_args(self):
        """(draft_params, spec_k, spec_v, spec_pos) dispatch inputs — the
        live pools when speculative decoding is on, donation dummies
        otherwise (never read in that mode)."""
        if self.spec_n:
            return self.spec_params, self.spec_k, self.spec_v, self.spec_pos
        # Distinct arrays: the pool slots are donated, and XLA rejects the
        # same buffer donated twice in one call.
        return (jnp.zeros((1,), self.dtype), jnp.zeros((1,), self.dtype),
                jnp.zeros((1,), self.dtype), jnp.zeros((1,), jnp.int32))

    @staticmethod
    def _write_spec_rows(spec_k, spec_v, spec_pos, slot_idx, drk, drv, drp):
        """Write the batch's draft-ring rows (drk/drv [Ld, Hd, b, R, Dd],
        drp [b, R]) back to slots ``slot_idx`` [b] of the draft pools, in
        place (ops/kv_write.py). A slot outside the pool drops its row:
        the host packs one for padding rows, so their stale copies never
        clobber a live slot."""
        n = spec_pos.shape[0]
        b = slot_idx.shape[0]
        spec_k, spec_v, spec_pos = write_slabs(
            (spec_k, spec_v, spec_pos[None, None]),
            (drk[:, :, :, None], drv[:, :, :, None],
             drp[None, None, :, None]),
            dst_start=jnp.clip(slot_idx, 0, n - 1),
            src_row=jnp.arange(b, dtype=jnp.int32),
            src_start=jnp.zeros((b,), jnp.int32),
            width=1,
            keep=((slot_idx >= 0) & (slot_idx < n))[:, None],
        )
        return spec_k, spec_v, spec_pos[0, 0]

    def _rebind_spec_pools(self, k, v, pos) -> None:
        if self.spec_n:
            self.spec_k, self.spec_v, self.spec_pos = k, v, pos

    @property
    def spec_acceptance_rate(self) -> float:
        """Lifetime fraction of draft proposals that survived verification
        (the bonus token is never counted in either side)."""
        if not self.spec_draft_tokens_total:
            return 0.0
        return self.spec_accepted_tokens_total / self.spec_draft_tokens_total

    @property
    def spec_acceptance_rate_window(self) -> float:
        """Acceptance over the last <=64 fetches only — the windowed
        companion to the lifetime ``spec_acceptance_rate`` (which a long
        uptime freezes: an hour of 0.8 acceptance hides a collapse to
        0.1 for many minutes). Same delta-window idea as the router's
        engine_stats per-interval cache-hit scraper."""
        drafts = sum(d for d, _ in self._spec_window) if self.spec_n else 0
        if not drafts:
            return 0.0
        return sum(a for _, a in self._spec_window) / drafts

    @property
    def spec_draft_depth_mean(self) -> float:
        """Mean SERVED draft depth per live verify cycle (sum of per-row
        gammas / live cycles). Equals speculative_num_tokens exactly in
        fixed mode; under the adaptive controller it is the actual depth
        the fleet is paying for."""
        if not self.spec_live_cycles_total:
            return 0.0
        return self.spec_draft_depth_sum / self.spec_live_cycles_total

    @property
    def spec_acceptance_ema_mean(self) -> float:
        """Mean per-sequence acceptance EMA over live sequences (0.0 when
        the adaptive controller is off)."""
        if self._spec_controller is None:
            return 0.0
        return self._spec_controller.mean_ema()

    # ---------------------------------------------------------------- memory
    def device_memory(self) -> List[Dict]:
        """Per device of the engine's mesh, in ``mesh.devices.flat`` order,
        its ``memory_stats()`` (``{}`` where the backend reports none: the
        CPU). The one place the engine reads the allocator: ``GET
        /version``, the pool's sizing and the memory ledger, whose
        ``fullest`` picks the device every HBM number is about."""
        return [d.memory_stats() or {} for d in self.mesh.devices.flat]

    def device_labels(self) -> List[str]:
        """``platform:id`` of the mesh's devices, in ``device_memory``'s
        order."""
        return [_device_label(d) for d in self.mesh.devices.flat]

    @staticmethod
    def program(kind: str, family, has_penalties: bool = False,
                logprobs_k: int = 0, spec_on: bool = True) -> Dict:
        """How the memory ledger names one dispatch program: the kind,
        the family (decode ``(b, mb, K, cached window)``, prefill ``(b, t,
        mb, has window)``) and the sampling variant, the static arguments
        that make it a program of its own."""
        family = [int(x) for x in family]
        key = f"{kind}{family}".replace(" ", "") \
            + ("+pen" if has_penalties else "") \
            + (f"+lp{logprobs_k}" if logprobs_k else "") \
            + ("" if spec_on else "+plain")
        return {"key": key, "kind": kind, "family": family,
                "has_penalties": bool(has_penalties),
                "logprobs_k": int(logprobs_k), "spec_on": bool(spec_on)}

    def resident_arrays(self) -> Dict[str, List]:
        """The arrays this runner keeps on the devices, by holder
        (memory_ledger.HOLDERS less ``other``). A draft that is the
        target itself holds no weights of its own."""
        weights = jax.tree.leaves(self._params)
        spec = []
        if self.spec_n:
            mine = {id(x) for x in weights}
            spec = [self.spec_k, self.spec_v, self.spec_pos] + [
                x for x in jax.tree.leaves(self.spec_params)
                if id(x) not in mine]
        return {
            "weights": weights,
            "kv": [self.kv_k, self.kv_v] + (
                [self.kv_k_scale, self.kv_v_scale] if self.kv_quantized
                else []),
            "state": list(self.state_pools),
            "spec": spec,
            "lora": jax.tree.leaves(self.lora_stacks or {}),
        }

    def resident_bytes(self) -> Dict[str, Dict[str, int]]:
        """``{"platform:id": {holder: bytes}}`` for every device of the
        mesh: what ``resident_arrays`` lays on it, from each array's shape
        and sharding (no buffer is touched, so a pool a dispatch has just
        donated reads like any other). With tp>1 the pools are kv-head-
        sharded and a device holds ~1/tp of them; a replicated fallback
        shows as every device holding the whole pool."""
        out = {label: {} for label in self.device_labels()}
        for holder, arrays in self.resident_arrays().items():
            for label, nbytes in _bytes_by_device(arrays).items():
                out.setdefault(label, {})[holder] = nbytes
            for named in out.values():
                named.setdefault(holder, 0)
        return out

    def build_memory_ledger(self) -> None:
        """Enter what is resident into ``self.memory`` (the engine calls
        this once, when ``start()`` ends and nothing is in flight): the
        named holders from the arrays the runner holds, the 16 largest
        groups of live arrays outside them ON EACH DEVICE of the mesh, and
        ``other`` from the allocator's own count. Lowers and compiles
        nothing."""
        named = self.resident_arrays()
        mine = {id(x) for arrays in named.values() for x in arrays}
        labels = self.device_labels()
        groups: Dict[Tuple, Dict] = {}
        for array in jax.live_arrays():
            if id(array) in mine:
                continue
            for label, nbytes in _bytes_by_device([array]).items():
                if label not in labels:
                    continue
                group = groups.setdefault(
                    (label, tuple(array.shape), str(array.dtype)),
                    {"device": label, "shape": list(array.shape),
                     "dtype": str(array.dtype), "bytes": 0, "count": 0})
                group["bytes"] += nbytes
                group["count"] += 1
        largest = [g for label in labels for g in sorted(
            (g for g in groups.values() if g["device"] == label),
            key=lambda g: -g["bytes"])[:16]]
        self.memory.build(
            self.resident_bytes(), largest,
            # Replicated: a device holds each pool whole.
            {spec.name: max(_bytes_by_device([pool]).values(), default=0)
             for spec, pool in zip(self.state_specs, self.state_pools)})
        logger.info(
            "Memory ledger (%s): residents %s; %d programs measured; "
            "peak rose %d times in warm-up",
            self.memory.device,
            {h: f"{b / 1e9:.3f} GB"
             for h, b in self.memory.residents.items()},
            len(self.memory.programs), self.memory.rises["warmup"])

    @property
    def kv_pool_bytes(self) -> int:
        """Derived device bytes of the KV pool (payload + scale sidecars) —
        surfaced through engine.stats() so operators can see what an int8
        pool actually bought at equal HBM budget."""
        return self.num_kv_blocks * self.config.kv_cache_bytes_per_block(
            self.model_config
        )

    @property
    def kv_quant_bytes_saved_total(self) -> int:
        """Monotonic counter: pool bytes a quantized cache avoided writing
        versus storing the same tokens in the compute dtype (0 when the KV
        cache is not quantized)."""
        if not self.kv_quantized:
            return 0
        mc, cfg = self.model_config, self.config
        unquantized = (
            self.kv_pools * self.kv_spec.layers * self.kv_spec.kv_heads
            * self.kv_spec.head_dim * jnp.dtype(self.dtype).itemsize
        )
        saved = max(0, unquantized - cfg.kv_cache_bytes_per_token(mc))
        return self.kv_quant_tokens_written * saved

    def _derive_num_blocks(self) -> int:
        """Size the KV pool from free device memory (TPU HBM).

        Pool bytes follow the KV-CACHE storage dtype (+ per-slot scale
        overhead when quantized — config.kv_cache_bytes_per_block), so an
        int8 pool holds ~2x the blocks of a bf16 pool in the same budget.
        The gathered decode/prefill WINDOW is a dequantized compute-dtype
        copy, so its reservation is costed in compute-dtype bytes."""
        mc, cfg = self.model_config, self.config
        bytes_per_block = cfg.kv_cache_bytes_per_block(mc)
        window_bytes_per_block = (
            self.kv_spec.layers * cfg.block_size * self.kv_spec.kv_heads
            * (self.kv_spec.head_dim + self.kv_v_dim)
            * jnp.dtype(self.dtype).itemsize
        )
        # The budget is PER DEVICE: the least free HBM over the devices of
        # the engine's own mesh (not whatever jax.local_devices()[0] is).
        # Only the CPU backend reports no memory stats (tests size the pool
        # explicitly or take this nominal 2 GiB); an accelerator that
        # cannot say what is free is an error, never a guessed pool.
        free_bytes = None
        for dev, stats in zip(self.mesh.devices.flat, self.device_memory()):
            if "bytes_limit" in stats:
                free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
                free_bytes = free if free_bytes is None \
                    else min(free_bytes, free)
            elif dev.platform != "cpu":
                raise RuntimeError(
                    f"{dev} reports no memory_stats()['bytes_limit'] "
                    f"(got {stats!r}): cannot size the KV pool from free "
                    f"HBM — pass --num-kv-blocks to size it explicitly"
                )
        if free_bytes is None:
            free_bytes = 2 << 30
        # Overlapped weight loading: the weights may not be device-resident
        # yet when the pool is sized — reserve their full footprint out of
        # the probe or the pool would over-commit the HBM they land in.
        free_bytes = max(0, free_bytes - self._pending_param_bytes)
        # The state pools are resident already (bytes_in_use holds them);
        # a decode dispatch besides carries its rows' state through its
        # loop as a temporary, the widest row bucket's worth.
        free_bytes = max(0, free_bytes - _bucket(
            cfg.max_num_seqs, 1, max(1, cfg.max_num_seqs)
        ) * cfg.state_bytes_per_seq(mc))
        # With tp>1 the pool (and the window gathered from it) is
        # kv-head-sharded, so each device holds 1/shards of every block
        # (1 when the heads don't divide tp and the pool is replicated —
        # read off the pool's own sharding rule, not restated here).
        shards = self.kv_spec.kv_heads // kv_pool_sharding(
            mc, self.mesh
        ).shard_shape((1, self.kv_spec.kv_heads, 1, 1))[1]
        bytes_per_block = -(-bytes_per_block // shards)
        window_bytes_per_block = -(-window_bytes_per_block // shards)
        budget = int(free_bytes * cfg.hbm_utilization)
        if self.attn_impl == "window":
            # The decode window is a gathered (dequantized) copy of the live
            # KV (up to the whole pool), so budget for pool + window rather
            # than pool alone. The scheduler additionally caps each
            # dispatch's bucketed rows x blocks window at pool size (window
            # budgets below).
            n = budget // (bytes_per_block + window_bytes_per_block)
        elif self.prefill_reads_pool:
            # Neither decode nor prefill copies the pool: all of it is pool.
            n = budget // bytes_per_block
        else:
            # Paged decode never copies the pool, but chunked PREFILL still
            # gathers a [rows, max_blocks] history window here (an int8
            # pool, tp or sp > 1, rows no prefill kernel tiles); reserve
            # the worst-case bucketed prefill window out of the pool budget.
            reserve_bytes = min(
                _bucket(prefill_row_cap(cfg), 1, max(1, cfg.max_num_seqs))
                * _bucket(cfg.max_blocks_per_seq, 1,
                          max(1, cfg.max_blocks_per_seq))
                * window_bytes_per_block,
                budget // 2,
            )
            self._prefill_window_blocks = max(
                1, reserve_bytes // window_bytes_per_block
            )
            n = (budget - reserve_bytes) // bytes_per_block
        n = max(2, min(n, cfg.max_blocks_per_seq * cfg.max_num_seqs + 1))
        logger.info(
            "KV pool: %d blocks x %d tokens (%.1f MiB per device over %d "
            "kv shard(s), kv_cache_dtype=%s, attn=%s)",
            n, cfg.block_size, n * bytes_per_block / (1 << 20), shards,
            cfg.kv_cache_dtype, self.attn_impl,
        )
        return n

    @property
    def decode_window_blocks(self) -> int:
        """Per-dispatch block budget for the DECODE gathered window: the
        scheduler keeps bucket(rows) * bucket(max_blocks_per_row) under this
        (a gathered window duplicates shared prefix blocks per row and pads
        to power-of-two buckets, so it can exceed the LIVE pool bytes —
        advisor r2 finding). Paged decode reads the pool in place: no cap."""
        if self.attn_impl != "window":
            return 1 << 30
        return self.num_kv_blocks

    @property
    def prefill_window_blocks(self) -> int:
        """Per-dispatch block budget for the PREFILL history window, where
        chunks past the first gather one; no cap where the history is read
        in place (``prefill_reads_pool``), as for paged decode."""
        if self.prefill_reads_pool:
            return 1 << 30
        if self.attn_impl == "window":
            return self.num_kv_blocks
        # Set by _derive_num_blocks; explicit num_kv_blocks configs skip the
        # derivation, so fall back to the pool size.
        return getattr(self, "_prefill_window_blocks", self.num_kv_blocks)

    # --------------------------------------------------------- shape families
    def _decode_mb(self, live_blocks: int) -> int:
        """Static block-table width for a decode dispatch.

        Paged decode PINS mb at the max bucket: the Pallas kernel's page loop
        is bounded by the live kv_len (ops/pallas/paged_attention.py —
        ``n_super = cdiv(kv_len, SUPER_TOKENS)``), so a wider block table
        costs only SMEM bytes and a slightly larger packed host buffer —
        and collapses decode to ONE mb family, which warmup compiles
        exactly. The round-4 bench regression was live-bucketed decode mb
        families warmup never covered (VERDICT r4 weak #1).

        The window impl gathers mb*block_size slots per row, so there mb
        stays cost-proportional but quantized (utils.window_mb_bucket) to a
        four-value ladder warmup can enumerate."""
        cfg = self.config
        if self.attn_impl == "paged":
            return _bucket(cfg.max_blocks_per_seq, 1,
                           max(1, cfg.max_blocks_per_seq))
        return window_mb_bucket(live_blocks, cfg.max_blocks_per_seq)

    def _prefill_t_buckets(self) -> List[int]:
        """Every chunk-length bucket a prefill dispatch can take
        (``utils.prefill_rectangles``): the powers of two from
        ``prefill_t_floor`` up, within the token budget."""
        return sorted({t for _, t in prefill_rectangles(self.config)})

    @functools.cached_property
    def prefill_reads_pool(self) -> bool:
        """Whether a prefill chunk reads its rows' history IN PLACE from
        the paged pool (``attend`` over a view that holds the pool: the
        Pallas flash kernel in a program lowered for a TPU,
        ops/attention.py:_attend_chunk_over_pool) instead of from a window
        gathered once a dispatch. The view ``_prefill_impl`` builds, the
        families warm-up and the AOT prepass enumerate, the scheduler's
        window budget and the pool's window reserve all follow it, and it
        is ``attend``'s own predicate (ops/attention.py:
        prefill_kernel_covers) asked of EVERY chunk length this config can
        dispatch: paged attention over K/V rows in two pools or latent rows
        in one (each has its kernel) in the compute dtype (no int8 scales)
        on a mesh of one device (a sharded pool or a sequence-parallel
        chunk keeps its gathered window), at a row width, head count,
        block size and chunk buckets the kernel tiles. Were the two ever
        to disagree, ``attend`` raises while the program is traced, at
        warm-up; ``GET /debug/programs`` reports this beside what each
        prefill program holds."""
        mc = self.model_config
        sharded = self.mesh.size > 1
        return self.attn_impl == "paged" and all(
            prefill_kernel_covers(
                t, mc.num_heads, self.kv_spec.kv_heads,
                self.kv_spec.head_dim, self.kv_value_dim,
                self.config.block_size, (self.dtype,),
                latent=self.kv_pools == 1,
                scales=self.kv_quantized, kv_sharded=sharded, ring=sharded)
            for t in self._prefill_t_buckets())

    @functools.cached_property
    def prefill_packs(self) -> bool:
        """Which of its two forms a prefill dispatch takes, decided HERE
        for the scheduler (which packs), the engine loop (which counts)
        and this runner (which warms and issues). True: ONE row of tokens,
        ``[1, T]``, in which the sequences' chunks lie end to end as
        segments, so that only the row's end is padding. False: a
        ``[rows, T]`` rectangle, a row a sequence, every row padded to T.

        A row can be packed where whatever ties a token to its sequence
        can tell the segments apart: the chunk reads its paged rows in
        place (K/V rows in two pools or latent rows in one) and the flash
        prefill kernel covers every packed row this config can dispatch
        (``prefill_reads_pool``, and ``prefill_kernel_covers(...,
        packed=True)``); every state the model keeps a sequence is one its
        module declares to cross a segment boundary inside the row (its
        ``STATES_CROSSING_SEGMENTS``: a finite window of inputs does, a
        scan that runs a row of ONE sequence from ONE slot does not); and
        no per-row operand rides the forward (LoRA's adapter of a row, the
        speculative draft's ring of a row)."""
        mc = self.model_config
        return self.prefill_reads_pool \
            and all(s.name in self.states_crossing_segments
                    for s in self.state_specs) \
            and not self.lora_stacks and not self.spec_n and all(
                prefill_kernel_covers(
                    t, mc.num_heads, self.kv_spec.kv_heads,
                    self.kv_spec.head_dim, self.kv_value_dim,
                    self.config.block_size, (self.dtype,),
                    latent=self.kv_pools == 1, packed=True)
                for _, t in prefill_rectangles(self.config, True))

    @property
    def _prefill_segs(self) -> int:
        """Sequences a packed prefill program has scalars for (0: this
        runner's dispatches are rectangles)."""
        cfg = self.config
        return _bucket(prefill_row_cap(cfg), 1, max(1, cfg.max_num_seqs)) \
            if self.prefill_packs else 0

    def _prefill_mb(self, live_blocks: int, has_window: bool,
                    rows: int = 1) -> int:
        """Static block-table width for a prefill dispatch: pinned at the
        max bucket when no window is gathered (the block tables feed the
        pool write's slot mapping and, where the history is read in place,
        a page loop bounded by the row's length: padding is free, as in
        ``_decode_mb``), quantized when a chunk with history gathers its
        [rows, mb*block_size] window — unless the window is pinned too
        (_pins_prefill_window)."""
        cfg = self.config
        full = _bucket(cfg.max_blocks_per_seq, 1,
                       max(1, cfg.max_blocks_per_seq))
        if not has_window or self._pins_prefill_window(rows, full):
            return full
        return window_mb_bucket(live_blocks, cfg.max_blocks_per_seq)

    def _pins_prefill_window(self, rows: int, full_mb: int) -> bool:
        """Only where a history window is still gathered (not where
        ``prefill_reads_pool``: every benchmark configuration reads its
        pool in place, the hybrid's full layers since PR 35, the
        latent-row models since PR 39; what is left is what the predicate
        refuses: an int8 pool, tp or sp over 1 (where the engine serves the
        model with them at all), heads or widths no kernel tiles). A model that declares recurrent state, or whose paged
        rows are latent rows, then gathers its prefill history window at
        the full width whatever the rows hold, where the window budget
        allows that many blocks: ONE windowed family a (rows, t) instead
        of three. Its cached rows are cheap to gather (K/V in a minority
        of layers: 0.19 GB a row at the benchmark's widths; a latent row a
        ninth of a K/V row of heads: 0.03 GB); its prefill programs are
        twice a dense model's size, and a deployment's programs have to
        fit the compile cache's size cap together (PERF.md §6, PR 31: 78
        programs of 3.4 MB against 192 MiB evicted one another and every
        boot compiled everything; PR 33: 70 of 3.6 MB did the same). What
        it costs a latent model: its window attention contracts the whole
        window, 3072 keys where the history may be 64 (PERF.md §6, PR 39:
        half of a prefill dispatch at the benchmark's widths)."""
        return (bool(self.state_specs) or self.kv_pools == 1) and \
            rows * full_mb <= self.prefill_window_blocks

    # --------------------------------------------------------- device helpers
    def _scale_pool_args(self):
        """The (kv_k_scale, kv_v_scale) dispatch inputs: the live scale
        pools when the KV cache is quantized, fresh [1]-shaped donation
        dummies otherwise (the impls never read them in that mode; same
        idiom as the fresh-gather window dummies)."""
        if self.kv_quantized:
            return self.kv_k_scale, self.kv_v_scale
        from production_stack_tpu.ops.quantization import SCALE_DTYPE

        return jnp.zeros((1,), SCALE_DTYPE), jnp.zeros((1,), SCALE_DTYPE)

    def _rebind_scale_pools(self, kv_ks, kv_vs) -> None:
        """Rebind the donated scale pools from a dispatch's outputs
        (quantized mode only; dummies are dropped)."""
        if self.kv_quantized:
            self.kv_k_scale, self.kv_v_scale = kv_ks, kv_vs

    def _fwd_stats_zero(self):
        """A dispatch's forward counters before its first step (``()``
        where the model returns none: the program is then the same,
        operand for operand)."""
        return jnp.zeros((len(self.fwd_stats),), jnp.int32) \
            if self.fwd_stats else ()

    def _note_fwd_stats(self, kind: str, dev) -> int:
        """Keep a dispatch's forward counters until a fetch has made them
        ready; returns the number of dispatches noted so far, which that
        dispatch's fetch hands to ``_drain_fwd_stats``."""
        self._fwd_stats_noted += 1
        self._fwd_stats_pending.append((self._fwd_stats_noted, kind, dev))
        return self._fwd_stats_noted

    def _drain_fwd_stats(self, upto: int) -> None:
        """Add to the totals the forward counters of the dispatches noted
        no later than number ``upto``. Called from a fetch that has just
        read dispatch ``upto``'s tokens: dispatches complete in order, so
        these few scalars are ready and the read waits for nothing (a
        dispatch issued AFTER it may still run, and is left alone). A
        prefill chunk that fetches nothing leaves its counters to the next
        fetch."""
        pending = self._fwd_stats_pending
        while pending and pending[0][0] <= upto:
            _, kind, dev = pending.pop(0)
            total = self.fwd_stats_total[kind]
            for name, value in zip(self.fwd_stats, np.asarray(dev)):
                total[name] += int(value)

    def _derive_seeds(self, seed_base, gen0, j):
        """uint32 seed per row for generation index gen0+j; must match
        _token_seed exactly (same wrap-around arithmetic)."""
        return (
            seed_base * _SEED_MULT
            + (gen0 + j.astype(np.uint32))
        ).astype(jnp.uint32)

    # ------------------------------------------------------------------ decode
    def _decode_impl(self, params, packed, kv_k, kv_v, kv_ks, kv_vs,
                     win_k_in, win_v_in, counts0, prev_last, dparams,
                     spec_k, spec_v, spec_pos, state_pools, *, b: int,
                     mb: int, num_steps: int, use_cached_window: bool,
                     has_penalties: bool = False, logprobs_k: int = 0,
                     spec_on: bool = True):
        """One fused K-step decode dispatch.

        kv_ks/kv_vs: the per-(slot, head) dequant scale pools
        [L, Hkv, num_slots] when the KV cache is quantized (int8 payload
        pools; ops/quantization.py), donated and returned rebound like the
        payload pools; [1]-shaped donation dummies otherwise. Each step's
        fresh KV is quantized ON DEVICE inside the scan — the attention
        ring (and the persistent window) carry the DEQUANTIZED values, so
        every later read path (pool gather, window append, Pallas kernel)
        reconstructs bit-identical keys/values.

        packed: int32[b*(NUM_SCALARS+mb)] host buffer laid out as per-row
        scalars (tokens0, pos0, budget, seed_base, gen0, temps, top_k,
        top_p, adapter, presence, frequency — floats bitcast) followed by
        the [b, mb] block tables. Everything else is derived here, on
        device.

        counts0: [b, V] int32 output-token occurrence counts when
        ``has_penalties`` (threaded through the scan carry so mid-scan
        tokens are penalized too); a [1, 1] dummy otherwise. With
        ``logprobs_k`` > 0 the dispatch also returns per-step
        (chosen_logprob [K, b], top_lp [K, b, k], top_ids [K, b, k]) from
        the RAW logits. Both knobs are static so the default serving path
        compiles no penalty/logprob code at all.

        prev_last: [b_max] int32 — the PREVIOUS dispatch's device-resident
        last-token vector. Rows whose packed chain_src (scalar row 11) is
        >= 0 take tokens0 = prev_last[chain_src] instead of the host value,
        so a dispatch can be issued before the previous one's tokens ever
        reach the host (the pipelined engine loop). The dispatch RETURNS
        its own last-token vector [b_max] (each row's final sampled token,
        frozen at its step budget) as the last output.

        win_k_in/win_v_in: the persistent window buffers [L, Hkv, b, mb*bs,
        Dh] (window impl with ``use_cached_window``): they already hold the
        rows' live KV (slot s = absolute position s) and are only appended
        to. Without the flag (first dispatch of a batch, or paged impl)
        they are 1-element donation dummies and a fresh gather builds the
        returned window. The updated window is returned so the caller can
        reuse it next dispatch.

        state_pools: the per-sequence state pools of a model that declares
        some (``()`` otherwise: the program is then the K/V-only one,
        operand for operand). A row's state is gathered from its slot
        (scalar row 12) once, carried through the steps — a step past the
        row's budget delivers nothing and leaves the state as it was
        (``chunk_lens`` 0: the model's forward holds that) — and written
        back in place once, after the loop. Returned rebound, last.
        """
        cfg = self.config
        bs = cfg.block_size
        mc = self.model_config
        scalars = packed[: NUM_SCALARS * b].reshape(NUM_SCALARS, b)
        tokens0 = scalars[0]
        pos0 = scalars[1]
        budget = scalars[2]
        seed_base = jax.lax.bitcast_convert_type(scalars[3], jnp.uint32)
        gen0 = jax.lax.bitcast_convert_type(scalars[4], jnp.uint32)
        temps = jax.lax.bitcast_convert_type(scalars[5], jnp.float32)
        top_k = scalars[6]
        top_p = jax.lax.bitcast_convert_type(scalars[7], jnp.float32)
        # Which sampler picks some row selects: constant over the K steps,
        # so reduced once here and not once a step inside the loop.
        paths = sampler_paths(temps, top_k, top_p)
        adapter_idx = scalars[8]
        presence = jax.lax.bitcast_convert_type(scalars[9], jnp.float32)
        frequency = jax.lax.bitcast_convert_type(scalars[10], jnp.float32)
        chain_src = scalars[11]
        lora = (adapter_idx, self.lora_stacks) if self.lora_stacks else None
        block_tables = packed[NUM_SCALARS * b:].reshape(b, mb)
        b_max = prev_last.shape[0]

        if self.spec_n and spec_on:
            # Speculative draft/verify cycles replace the one-token-per-
            # step scan entirely (docs/PERF.md round 8). Strict pipeline
            # ordering means rows never chain start tokens from an
            # unapplied dispatch here. ``spec_on=False`` (adaptive
            # controller, every row at gamma=0) compiles THIS non-spec
            # body instead: the gamma=0 degradation is the plain scan
            # with zero draft overhead, not a draft loop that drafts
            # nothing (round 10; the dispatch-count-parity test pins it).
            return self._decode_spec(
                params, dparams, kv_k, kv_v, kv_ks, kv_vs, win_k_in,
                win_v_in, counts0, spec_k, spec_v, spec_pos, scalars,
                block_tables, b_max, b=b, mb=mb, num_steps=num_steps,
                use_cached_window=use_cached_window,
                has_penalties=has_penalties, logprobs_k=logprobs_k,
            ) + (state_pools, ())

        # Token chaining: rows continuing from the immediately-previous
        # dispatch read their start token from its device-resident
        # last-token vector (see docstring).
        tokens0 = jnp.where(
            chain_src >= 0,
            prev_last[jnp.clip(chain_src, 0, b_max - 1)],
            tokens0,
        )

        # Per-step seeds [K, b].
        k_iota = jnp.arange(num_steps, dtype=jnp.int32)
        seed_steps = self._derive_seeds(
            seed_base[None, :], gen0[None, :], k_iota[:, None]
        )

        quant = self.kv_quantized
        if self.attn_impl == "paged":
            # Decode attends directly against the stacked HBM pool inside
            # the Pallas kernel — the live KV is never copied (int8 pools
            # dequantize IN-KERNEL as rank-1 score/weight scaling). With
            # tp>1 the pool is kv-head-sharded, so the kernel runs under
            # shard_map over the tp axis (ops/attention.py:attend).
            from production_stack_tpu.parallel.mesh import AXIS_TP

            tp_mesh = self.mesh if self.mesh.shape[AXIS_TP] > 1 else None
            win_k = win_v = None
            view0 = KVView(
                pool_k=kv_k, pool_v=kv_v,
                k_scale=kv_ks if quant else None,
                v_scale=kv_vs if quant else None,
                block_tables=block_tables, kv_lens=pos0, block_size=bs,
                interpret=self._pallas_interpret, tp_mesh=tp_mesh,
            )
        else:
            if use_cached_window:
                win_k, win_v = win_k_in, win_v_in
            else:
                win_k, win_v = gather_window(
                    kv_k, kv_v, block_tables, bs,
                    kv_ks if quant else None, kv_vs if quant else None,
                    out_dtype=self.dtype,
                )
            view0 = KVView(win_k=win_k, win_v=win_v, win_len=pos0)

        nl, hkv, dh = self.kv_spec
        ring_k0 = jnp.zeros((nl, hkv, b, num_steps, dh), self.dtype)
        ring_v0 = jnp.zeros((nl, hkv, b, num_steps, self.kv_v_dim),
                            self.dtype)
        ring_pos0 = jnp.full((b, num_steps), _POS_SENTINEL, jnp.int32)
        if quant:
            # Quantized-KV sidecar rings: the int8 payload + scales each
            # step will write to the pool at the end of the dispatch.
            # Quantizing ONCE per token (here, not at the final write)
            # keeps pool contents and the dequantized attention ring /
            # persistent window derived from the same (q, scale) pair.
            from production_stack_tpu.ops.quantization import SCALE_DTYPE

            qstate0 = (
                jnp.zeros((nl, hkv, b, num_steps, dh), jnp.int8),
                jnp.zeros((nl, hkv, b, num_steps, dh), jnp.int8),
                jnp.zeros((nl, hkv, b, num_steps), SCALE_DTYPE),
                jnp.zeros((nl, hkv, b, num_steps), SCALE_DTYPE),
            )
        else:
            qstate0 = ()
        ones = jnp.ones((b,), jnp.int32)
        max_len = cfg.max_model_len

        iota_rows = jnp.arange(b, dtype=jnp.int32)
        # The loop runs EXACTLY the steps some row still needs — K is only
        # the compiled (buffer-shape) bound. A drain-tail dispatch whose
        # rows all have e.g. 36 steps left executes 36 iterations inside
        # the K=64 family instead of computing 28 discarded steps (22% of
        # the bench round's decode time, r4 dispatch-log profiling).
        n_active = jnp.max(
            jnp.minimum(budget, num_steps)
        ).astype(jnp.int32)

        state_slots = scalars[12]
        rows_state = self._read_state_rows(state_pools, state_slots) \
            if self.state_specs else ()

        def body(carry, j):
            (toks, ring_k, ring_v, ring_pos, counts, qstate, rows_state,
             fwd_stats) = carry
            seeds_j = seed_steps[j]
            positions = jnp.minimum(pos0 + j, max_len - 1)[:, None]
            # One call whatever the module declares: a state it carries
            # (``cache_specs(cfg).state``: handed in as ``state=`` and
            # returned fourth) and counters (``FORWARD_STATS``: returned
            # last) are independent. With either, a row past its budget
            # is handed length 0: it leaves no state and reaches no expert.
            hidden, k_new, v_new, *extra = self._forward(
                params, mc, toks[:, None], positions,
                (j < budget).astype(jnp.int32)
                if self.state_specs or self.fwd_stats else ones,
                view0._replace(ring_k=ring_k, ring_v=ring_v,
                               ring_pos=ring_pos),
                lora=lora,
                **({"state": rows_state} if self.state_specs else {}),
            )
            if self.state_specs:
                rows_state = extra.pop(0)
            if self.fwd_stats:
                fwd_stats = fwd_stats + extra.pop(0)
            if quant:
                # Quantize this step's fresh KV on device; the attention
                # ring carries the DEQUANTIZED values so later steps of
                # this dispatch attend to exactly what later dispatches
                # will reconstruct from the pool.
                from production_stack_tpu.ops.quantization import (
                    dequantize_kv,
                    quantize_kv,
                )

                qk, sk = quantize_kv(k_new)
                qv, sv = quantize_kv(v_new)
                k_new = dequantize_kv(qk, sk, self.dtype)
                v_new = dequantize_kv(qv, sv, self.dtype)
                ring_qk, ring_qv, ring_sk, ring_sv = qstate
                qstate = (
                    jax.lax.dynamic_update_slice(ring_qk, qk, (0, 0, 0, j, 0)),
                    jax.lax.dynamic_update_slice(ring_qv, qv, (0, 0, 0, j, 0)),
                    jax.lax.dynamic_update_slice(ring_sk, sk, (0, 0, 0, j)),
                    jax.lax.dynamic_update_slice(ring_sv, sv, (0, 0, 0, j)),
                )
            logits = self._logits_fn(params, mc, hidden[:, 0])
            if has_penalties:
                from production_stack_tpu.engine.sampling import (
                    apply_penalties,
                )

                eff = apply_penalties(logits, counts, presence, frequency)
            else:
                eff = logits
            nxt = sample_tokens(eff, temps, top_k, top_p, seeds_j, paths)
            if has_penalties:
                counts = counts.at[iota_rows, nxt].add(1)
            if logprobs_k:
                from production_stack_tpu.engine.sampling import (
                    compute_logprobs,
                )

                lp = compute_logprobs(logits, nxt, logprobs_k)
            else:
                lp = None
            # Append this step's KV (+ its position) to the ring at index j.
            with jax.named_scope("kv_write"):
                ring_k = jax.lax.dynamic_update_slice(
                    ring_k, k_new, (0, 0, 0, j, 0)
                )
                ring_v = jax.lax.dynamic_update_slice(
                    ring_v, v_new, (0, 0, 0, j, 0)
                )
                ring_pos = jax.lax.dynamic_update_slice(
                    ring_pos, positions, (0, j)
                )
            # The carried token freezes at each row's step budget, so the
            # final carry is the row's LAST VALID sampled token — the
            # chain vector the next dispatch may start from.
            kept = jnp.where(
                j < budget, nxt.astype(jnp.int32), toks
            )
            return (kept, ring_k, ring_v, ring_pos, counts, qstate,
                    rows_state, fwd_stats), nxt, lp

        def loop_body(state):
            j, carry, toks_all, lp_bufs = state
            carry, nxt, lp = body(carry, j)
            toks_all = toks_all.at[j].set(nxt)
            if logprobs_k:
                lp_bufs = (
                    lp_bufs[0].at[j].set(lp[0]),
                    lp_bufs[1].at[j].set(lp[1]),
                    lp_bufs[2].at[j].set(lp[2]),
                )
            return j + 1, carry, toks_all, lp_bufs

        carry0 = (tokens0, ring_k0, ring_v0, ring_pos0, counts0, qstate0,
                  rows_state, self._fwd_stats_zero())
        if cfg.decode_loop == "scan":
            # A/B alternative: all K steps run unconditionally under
            # lax.scan (more XLA pipelining latitude, no drain-tail skip).
            def scan_body(carry, j):
                carry, nxt, lp = body(carry, j)
                return carry, (nxt, lp if logprobs_k else ())

            (final_toks, ring_k, ring_v, _, _, qstate, rows_state,
             fwd_stats), (toks_all, lp_scan) = jax.lax.scan(
                    scan_body, carry0,
                    jnp.arange(num_steps, dtype=jnp.int32),
                )
            lp_chosen, lp_top, lp_ids = lp_scan if logprobs_k else (
                None, None, None
            )
        else:
            toks_buf0 = jnp.zeros((num_steps, b), jnp.int32)
            lp_bufs0 = (
                jnp.zeros((num_steps, b), jnp.float32),
                jnp.zeros((num_steps, b, logprobs_k), jnp.float32),
                jnp.zeros((num_steps, b, logprobs_k), jnp.int32),
            ) if logprobs_k else ()
            _, (final_toks, ring_k, ring_v, _, _, qstate, rows_state,
                fwd_stats), toks_all, lp_bufs = jax.lax.while_loop(
                    lambda st: st[0] < n_active,
                    loop_body,
                    (jnp.int32(0), carry0, toks_buf0, lp_bufs0),
                )
            if logprobs_k:
                lp_chosen, lp_top, lp_ids = lp_bufs
            else:
                lp_chosen, lp_top, lp_ids = None, None, None
        last_token = jnp.zeros((b_max,), jnp.int32).at[:b].set(final_toks)

        # The dispatch's KV goes back to the paged pool in place, one
        # block-wide slab at a time (ops/kv_write.py; quantized mode: the
        # int8 payload + per-slot scales the scan recorded; the pool never
        # holds compute-dtype KV). Row i's steps j < budget[i] are the
        # consecutive positions pos0[i] + j.
        n_valid = jnp.clip(budget, 0, num_steps)
        with jax.named_scope("kv_write"):
            if quant:
                kv_k, kv_v, kv_ks, kv_vs = write_token_runs(
                    (kv_k, kv_v, kv_ks, kv_vs), qstate, block_tables,
                    pos0, n_valid, bs,
                )
            else:
                kv_k, kv_v = write_token_runs(
                    (kv_k, kv_v), (ring_k, ring_v), block_tables,
                    pos0, n_valid, bs,
                )
            if self.state_specs:
                with jax.named_scope("state_write"):
                    state_pools = write_state_rows(
                        state_pools, rows_state, state_slots)
        if self.attn_impl != "paged":
            # Append the dispatch's KV into the persistent window too (slot
            # s = absolute position s), so the next dispatch over the same
            # rows skips the full re-gather. Out-of-budget steps drop. The
            # quantized path appends the DEQUANTIZED values — identical to
            # what a fresh pool gather would reconstruct.
            with jax.named_scope("kv_write"):
                win_k, win_v = self._append_window(
                    win_k, win_v, ring_k, ring_v, pos0, n_valid
                )
            return (toks_all, kv_k, kv_v, kv_ks, kv_vs, win_k, win_v,
                    lp_chosen, lp_top, lp_ids, last_token,
                    *self._spec_dummy_outs(spec_k, spec_v, spec_pos),
                    state_pools, fwd_stats)
        return (toks_all, kv_k, kv_v, kv_ks, kv_vs, win_k_in, win_v_in,
                lp_chosen, lp_top, lp_ids, last_token,
                *self._spec_dummy_outs(spec_k, spec_v, spec_pos),
                state_pools, fwd_stats)

    def _append_window(self, win_k, win_v, new_k, new_v, start, length):
        """Append row i's tokens j < length[i] ([L, Hkv, b, T, Dh]) to the
        persistent window [L, Hkv, b, S, Dh] at positions start[i] + j;
        positions beyond S drop. The window is a paged pool whose row i
        owns blocks i*mb .. i*mb + mb - 1, written in place like one."""
        nl, hkv, b, s_tot, dh = win_k.shape
        dv = win_v.shape[-1]
        mb = s_tot // self.config.block_size
        own = (jnp.arange(b, dtype=jnp.int32)[:, None] * mb
               + jnp.arange(mb, dtype=jnp.int32)[None, :])
        win_k, win_v = write_token_runs(
            (win_k.reshape(nl, hkv, b * s_tot, dh),
             win_v.reshape(nl, hkv, b * s_tot, dv)),
            (new_k, new_v), own, start, length, self.config.block_size,
        )
        return (win_k.reshape(nl, hkv, b, s_tot, dh),
                win_v.reshape(nl, hkv, b, s_tot, dv))

    @staticmethod
    def _spec_dummy_outs(spec_k, spec_v, spec_pos):
        """Trailing outputs of the non-speculative decode variant, shaped
        to mirror the speculative one: per-cycle emit counts + the [4, b]
        per-row stats block (drafts/accepted/tree-nodes/live-cycles — all
        unused dummies here) and the draft pools passed through."""
        return (jnp.zeros((1, 1), jnp.int32), jnp.zeros((4, 1), jnp.int32),
                spec_k, spec_v, spec_pos)

    def _decode_spec(self, params, dparams, kv_k, kv_v, kv_ks, kv_vs,
                     win_k_in, win_v_in, counts0, spec_k, spec_v, spec_pos,
                     scalars, block_tables, b_max, *, b: int, mb: int,
                     num_steps: int, use_cached_window: bool,
                     has_penalties: bool, logprobs_k: int):
        """Speculative fused decode: draft-ahead N, verify once, accept on
        device (docs/PERF.md round 8; Leviathan et al. 2023 shape, with
        DETERMINISTIC acceptance so spec-on is token-identical to
        spec-off).

        Each cycle of the adaptive loop:
          1. DRAFT — N+1 autoregressive single-token draft-model steps
             starting from the row's last accepted token, each sampled
             with the SAME seed the target will use at that generation
             index (common-random-numbers: with similar distributions the
             proposal matches the target's sample far more often than an
             independent draw would). The extra (N+1)-th step exists only
             to keep the draft ring's KV aligned through fully-accepted
             cycles. Draft KV lives in the per-sequence ring rows gathered
             for this dispatch; rejected positions roll back to sentinel.
          2. VERIFY — ONE batched target forward over the [b, N+1] chunk
             [t0, q_0..q_{N-1}] against window + intra-dispatch ring +
             in-chunk causal attention: the target reads its weights once
             for up to N+1 emitted tokens (the roofline multiplier).
          3. ACCEPT — sampling.speculative_accept: the emitted tokens are
             the TARGET's samples under the accepted-gen-index seed
             schedule, so greedy and seeded output match spec-off exactly;
             only valid entries reach the ring / pool / draft ring.

        Per-row token budget (scalar row 2) counts EMITTED tokens exactly
        as in the non-speculative scan; the loop runs until every row's
        budget is spent (at worst ``num_steps`` cycles — one emitted token
        per cycle at zero acceptance).

        Round 10 adds two legs on the same cycle (both compile away to
        the round-8 graph in fixed/linear mode):
          * per-row draft DEPTH gamma (scalar row 13): the draft ring
            writes and the accept gate honor each row's gamma, so a
            low-acceptance row costs as little as the controller asks
            (gamma=0 rows emit exactly one target token per cycle with
            zero draft-ring traffic; the ALL-gamma=0 case never reaches
            this function — _issue_decode dispatches spec_on=False).
          * token-TREE verify (speculative_tree_width > 1): the verify
            chunk carries n_spec + width nodes — the linear CRN chain
            plus width-1 depth-1 alternates from the draft's own step-0
            top-k — attended under a tree-ancestor attention bias
            (ops/tree_mask.py) through the same window+ring+chunk
            segments, still ONE target forward. The accept walk follows
            the TARGET's samples down the tree (SpecInfer-style
            topology, Leviathan-style deterministic acceptance), and a
            path gather maps the accepted root-to-leaf path back to the
            [b, N+1] layout every downstream commit path already uses.

        Returns the same tuple shape as the non-speculative variant, with
        toks_all = [K, N+1, b] per-cycle verify samples, emits = [K, b]
        per-cycle emit counts, and spec_stats = [4, b] per-row counters
        (drafts, accepted, tree nodes, live cycles).
        """
        cfg = self.config
        mc = self.model_config
        dmc = self.spec_draft_config
        bs = cfg.block_size
        n_spec = self.spec_n
        k_cyc = num_steps                   # cycle bound == token budget
        s_ring = num_steps + n_spec + 1     # intra-dispatch target-KV ring
        r_len = self.spec_ring_len
        nl, hkv, dh = mc.num_layers, mc.num_kv_heads, mc.head_dim_
        dnl, dhkv, ddh = dmc.num_layers, dmc.num_kv_heads, dmc.head_dim_

        tokens0 = scalars[0]
        pos0 = scalars[1]
        budget = scalars[2]
        seed_base = jax.lax.bitcast_convert_type(scalars[3], jnp.uint32)
        gen0 = jax.lax.bitcast_convert_type(scalars[4], jnp.uint32)
        temps = jax.lax.bitcast_convert_type(scalars[5], jnp.float32)
        top_k = scalars[6]
        top_p = jax.lax.bitcast_convert_type(scalars[7], jnp.float32)
        paths = sampler_paths(temps, top_k, top_p)  # once a dispatch
        adapter_idx = scalars[8]
        presence = jax.lax.bitcast_convert_type(scalars[9], jnp.float32)
        frequency = jax.lax.bitcast_convert_type(scalars[10], jnp.float32)
        slot_idx = scalars[12]
        # Per-row draft depth (scalar row 13). The host packs n_spec for
        # every row when the adaptive controller is off, which makes every
        # gamma gate below a no-op — the fixed path stays bit-identical to
        # round 8.
        gamma = jnp.clip(scalars[13], 0, n_spec)
        g_on = gamma > 0
        lora = (adapter_idx, self.lora_stacks) if self.lora_stacks else None

        if use_cached_window:
            win_k, win_v = win_k_in, win_v_in
        else:
            win_k, win_v = gather_window(
                kv_k, kv_v, block_tables, bs, None, None,
                out_dtype=self.dtype,
            )
        win_len = pos0

        # Draft-ring rows for this batch. GATHER clips (padding rows read
        # some live slot harmlessly); the write-back (_write_spec_rows)
        # drops a row whose RAW index is out of range — the host packs
        # such a slot for padding rows, so their stale copies never
        # clobber a live slot.
        slot_c = jnp.clip(slot_idx, 0, spec_pos.shape[0] - 1)
        drk0 = spec_k[:, :, slot_c]            # [Ld, Hd, b, R, Dd]
        drv0 = spec_v[:, :, slot_c]
        drp0 = spec_pos[slot_c]                # [b, R]

        iota_b = jnp.arange(b, dtype=jnp.int32)
        iota_n1 = jnp.arange(n_spec + 1, dtype=jnp.int32)
        ones = jnp.ones((b,), jnp.int32)
        max_len = cfg.max_model_len
        d_max_pos = self._spec_draft_max_pos
        tw = self.spec_tree_width
        t_v = n_spec + tw          # verify-chunk nodes per row (tree adds
        #                            tw-1 depth-1 alternates; tw=1 -> N+1)
        full_lens = jnp.full((b,), t_v, jnp.int32)
        if tw > 1:
            tree_depths = jnp.asarray(self._spec_tree_depths)    # [t_v]
            main_chain = jnp.asarray(self._spec_main_chain)      # [N+1]

        ring_k0 = jnp.zeros((nl, hkv, b, s_ring, dh), self.dtype)
        ring_v0 = jnp.zeros((nl, hkv, b, s_ring, dh), self.dtype)
        ring_pos0 = jnp.full((b, s_ring), _POS_SENTINEL, jnp.int32)
        toks_buf0 = jnp.zeros((k_cyc, n_spec + 1, b), jnp.int32)
        emit_buf0 = jnp.zeros((k_cyc, b), jnp.int32)
        lp_bufs0 = (
            jnp.zeros((k_cyc, n_spec + 1, b), jnp.float32),
            jnp.zeros((k_cyc, n_spec + 1, b, logprobs_k), jnp.float32),
            jnp.zeros((k_cyc, n_spec + 1, b, logprobs_k), jnp.int32),
        ) if logprobs_k else ()

        from production_stack_tpu.engine.sampling import (
            apply_penalties,
            compute_logprobs,
            speculative_accept,
            speculative_tree_accept,
        )

        def cycle(state):
            (j, toks, pos, gen_off, rem, base, ring_k, ring_v, ring_pos,
             drk, drv, drp, counts, drafts, accepted, tree_cnt, cycles,
             toks_buf, emit_buf, lp_bufs) = state
            live = rem > 0

            # -- 1. draft N+1 autoregressive steps ----------------------
            def dstep(dc, i):
                if tw > 1:
                    dtok, drk, drv, drp, props, l1 = dc
                else:
                    dtok, drk, drv, drp, props = dc
                dpos = pos + i
                dpos_c = jnp.clip(dpos, 0, d_max_pos - 1)
                hid, dk, dv = self._draft_forward(
                    dparams, dmc, dtok[:, None], dpos_c[:, None], ones,
                    KVView(ring_k=drk, ring_v=drv, ring_pos=drp),
                )
                # gamma=0 rows draft nothing this dispatch: no ring
                # writes (the forward itself is batched and unavoidable,
                # but the row's draft state is untouched).
                widx = jnp.where(live & g_on,
                                 iota_b * r_len + dpos % r_len,
                                 b * r_len)
                drk = drk.reshape(dnl, dhkv, b * r_len, ddh).at[
                    :, :, widx
                ].set(dk[:, :, :, 0], mode="drop").reshape(
                    dnl, dhkv, b, r_len, ddh
                )
                drv = drv.reshape(dnl, dhkv, b * r_len, ddh).at[
                    :, :, widx
                ].set(dv[:, :, :, 0], mode="drop").reshape(
                    dnl, dhkv, b, r_len, ddh
                )
                drp = drp.reshape(-1).at[widx].set(
                    dpos, mode="drop"
                ).reshape(b, r_len)
                logits_d = self._draft_logits(dparams, dmc, hid[:, 0])
                seeds_i = self._derive_seeds(
                    seed_base, gen0 + gen_off, i.astype(jnp.uint32)
                )
                prop = sample_tokens(
                    logits_d, temps, top_k, top_p, seeds_i, paths
                ).astype(jnp.int32)
                props = props.at[i].set(prop)
                if tw > 1:
                    # Keep the STEP-0 draft SAMPLING scores (not raw
                    # logits): the tree's depth-1 alternates must be the
                    # runner-ups of the field the sampler argmaxes —
                    # logits/T + Gumbel under the shared CRN seed — or
                    # seeded-row divergences land outside the alternate
                    # set and the tree never salvages anything. Carried,
                    # not stacked: a [N+1, b, V] ys would be HBM waste.
                    l1 = jnp.where(
                        i == 0,
                        sampling_scores(logits_d, temps, seeds_i, paths[0]),
                        l1,
                    )
                    return (prop, drk, drv, drp, props, l1), None
                return (prop, drk, drv, drp, props), None

            props0 = jnp.zeros((n_spec + 1, b), jnp.int32)
            if tw > 1:
                l10 = jnp.zeros((b, self.model_config.vocab_size),
                                jnp.float32)
                (_, drk, drv, drp, props, l1), _ = jax.lax.scan(
                    dstep, (toks, drk, drv, drp, props0, l10), iota_n1
                )
            else:
                (_, drk, drv, drp, props), _ = jax.lax.scan(
                    dstep, (toks, drk, drv, drp, props0), iota_n1
                )

            # -- 2. one batched target verify ---------------------------
            # Linear: the chunk is [t0, q_0..q_{N-1}] under plain causal
            # attention. Tree: the chunk is the NODE list [t0, q_0,
            # alt_1..alt_{tw-1}, q_1..q_{N-1}] — the linear chain plus
            # the draft's top-(tw-1) step-0 alternates — attended under
            # the tree-ancestor bias; node positions are pos + depth, so
            # depth-1 siblings SHARE a position (and a seed: the CRN
            # schedule is per generation index, not per node).
            if tw > 1:
                p1 = props[0]                               # [b]
                alt_idx = jax.lax.top_k(
                    l1.at[iota_b, p1].set(jnp.float32(-jnp.inf)), tw - 1
                )[1].astype(jnp.int32)                      # [b, tw-1]
                v_toks = jnp.concatenate(
                    [toks[:, None], props[0][:, None], alt_idx,
                     props[1:n_spec].T], axis=1,
                )                                           # [b, T_v]
                v_pos = pos[:, None] + tree_depths[None, :]
                chunk_bias = self._spec_tree_bias
                node_gen = tree_depths.astype(jnp.uint32)   # [T_v]
            else:
                v_toks = jnp.concatenate(
                    [toks[:, None], props[:n_spec].T], axis=1
                )                                           # [b, N+1]
                v_pos = pos[:, None] + iota_n1[None, :]
                chunk_bias = None
                node_gen = iota_n1.astype(jnp.uint32)
            v_pos_c = jnp.minimum(v_pos, max_len - 1)
            hid, k_new, v_new = self._forward(
                params, mc, v_toks, v_pos_c, full_lens,
                KVView(win_k, win_v, win_len, ring_k, ring_v, ring_pos,
                       chunk_bias=chunk_bias),
                lora=lora,
            )
            logits = self._logits_fn(params, mc, hid)       # [b, T_v, V]
            vocab = logits.shape[-1]
            seeds = (
                seed_base[:, None] * _SEED_MULT
                + (gen0[:, None] + gen_off[:, None] + node_gen[None, :])
            ).astype(jnp.uint32)                            # [b, T_v]
            if has_penalties:
                # Sequential over MAIN-CHAIN positions: position i's
                # penalties must include this cycle's earlier samples,
                # exactly as the one-token-per-step scan would have
                # counted them. (tw=1: main chain == all positions.)
                mci = main_chain if tw > 1 else iota_n1     # [N+1]
                logits_m = logits[:, mci]
                seeds_m = seeds[:, mci]

                def vstep(c, i):
                    cnt, zm = c
                    eff = apply_penalties(
                        logits_m[:, i], cnt, presence, frequency
                    )
                    zi = sample_tokens(
                        eff, temps, top_k, top_p, seeds_m[:, i], paths
                    ).astype(jnp.int32)
                    cnt = cnt.at[iota_b, zi].add(1)
                    zm = zm.at[:, i].set(zi)
                    return (cnt, zm), None

                (_, z_main), _ = jax.lax.scan(
                    vstep, (counts, jnp.zeros((b, n_spec + 1), jnp.int32)),
                    iota_n1,
                )
                if tw > 1:
                    # Alternate nodes sample EXACTLY what the linear
                    # semantics would: conditioned on the walk reaching
                    # alternate a, the depth-0 emission was v_toks[:, a]
                    # itself, so that one count is the only penalty
                    # delta vs. the pre-cycle counts.
                    z = jnp.zeros((b, t_v), jnp.int32)
                    z = z.at[:, mci].set(z_main)
                    for a in range(2, tw + 1):
                        cnt_a = counts.at[iota_b, v_toks[:, a]].add(1)
                        eff_a = apply_penalties(
                            logits[:, a], cnt_a, presence, frequency
                        )
                        za = sample_tokens(
                            eff_a, temps, top_k, top_p, seeds[:, a], paths
                        ).astype(jnp.int32)
                        z = z.at[:, a].set(za)
                else:
                    z = z_main
            else:
                z = sample_tokens(
                    logits.reshape(b * t_v, vocab),
                    jnp.repeat(temps, t_v),
                    jnp.repeat(top_k, t_v),
                    jnp.repeat(top_p, t_v),
                    seeds.reshape(-1),
                    paths,
                ).reshape(b, t_v).astype(jnp.int32)

            # -- 3. accept/emit -----------------------------------------
            if tw > 1:
                emit, acc, path_idx, main_len = speculative_tree_accept(
                    v_toks, z, self._spec_tree_parents,
                    self._spec_tree_depths, rem, gamma,
                )
                z_path = jnp.take_along_axis(z, path_idx, axis=1)
                k_path = jnp.take_along_axis(
                    k_new, path_idx[None, None, :, :, None], axis=3
                )
                v_path = jnp.take_along_axis(
                    v_new, path_idx[None, None, :, :, None], axis=3
                )
            else:
                emit, acc = speculative_accept(
                    props[:n_spec].T, z, rem, gamma=gamma
                )
                path_idx = jnp.broadcast_to(
                    iota_n1[None, :], (b, n_spec + 1)
                )
                main_len = emit
                z_path, k_path, v_path = z, k_new, v_new
            # Accepted-path positions are pos + step regardless of tree
            # shape (the walk advances one depth per emitted token).
            c_pos = pos[:, None] + iota_n1[None, :]          # [b, N+1]
            valid_i = iota_n1[None, :] < emit[:, None]       # [b, N+1]
            if has_penalties:
                # Carry forward counts for EMITTED tokens only (the
                # sequential vstep's temp counts included discarded tail
                # positions).
                zi_m = jnp.where(valid_i, z_path, vocab)     # OOB -> drop
                counts = counts.at[
                    jnp.broadcast_to(iota_b[:, None], (b, n_spec + 1)),
                    zi_m,
                ].add(1, mode="drop")
            if logprobs_k:
                # Logprobs over the ACCEPTED path's nodes only (tw=1:
                # path == chunk). Gathering logits first keeps the
                # softmax at [b*(N+1), V] regardless of tree width.
                logits_path = jnp.take_along_axis(
                    logits, path_idx[:, :, None], axis=1
                ) if tw > 1 else logits
                lp = compute_logprobs(
                    logits_path.reshape(b * (n_spec + 1), vocab),
                    z_path.reshape(-1), logprobs_k,
                )
                lp_c = lp[0].reshape(b, n_spec + 1).T          # [N+1, b]
                lp_t = lp[1].reshape(
                    b, n_spec + 1, logprobs_k
                ).transpose(1, 0, 2)
                lp_i = lp[2].reshape(
                    b, n_spec + 1, logprobs_k
                ).transpose(1, 0, 2)

            # Commit valid target KV into the intra-dispatch ring at
            # [base, base+emit); rejected tail entries land at the drop
            # index and are overwritten by the next cycle. Tree mode
            # commits the PATH-gathered KV — the accepted root-to-leaf
            # chain in [b, N+1] layout, exactly what linear mode commits.
            flat_r = jnp.where(
                valid_i,
                iota_b[:, None] * s_ring + base[:, None] + iota_n1[None, :],
                b * s_ring,
            ).reshape(-1)
            k_chunk = k_path.reshape(nl, hkv, b * (n_spec + 1), dh)
            v_chunk = v_path.reshape(nl, hkv, b * (n_spec + 1), dh)
            ring_k = ring_k.reshape(nl, hkv, b * s_ring, dh).at[
                :, :, flat_r
            ].set(k_chunk, mode="drop").reshape(nl, hkv, b, s_ring, dh)
            ring_v = ring_v.reshape(nl, hkv, b * s_ring, dh).at[
                :, :, flat_r
            ].set(v_chunk, mode="drop").reshape(nl, hkv, b, s_ring, dh)
            ring_pos = ring_pos.reshape(-1).at[flat_r].set(
                c_pos.reshape(-1), mode="drop"
            ).reshape(b, s_ring)

            # Draft-ring rollback: entries the draft wrote this cycle
            # whose input token diverged from what the target emitted
            # must never be attended; the sentinel masks them and the
            # next cycle's draft rewrites the position with the
            # corrected token. main_len counts the draft-ring entries
            # that are still right: emit for linear acceptance, but only
            # t0's entry when a tree walk salvaged a depth-1 SIBLING
            # (the draft's chain continued from its own rejected q_0).
            # gamma=0 rows wrote nothing, so nothing rolls back.
            inval = (
                (iota_n1[None, :] >= main_len[:, None])
                & live[:, None] & g_on[:, None]
            )
            rb_idx = jnp.where(
                inval, iota_b[:, None] * r_len + c_pos % r_len, b * r_len
            ).reshape(-1)
            drp = drp.reshape(-1).at[rb_idx].set(
                _POS_SENTINEL, mode="drop"
            ).reshape(b, r_len)

            new_tok = jnp.take_along_axis(
                z_path, jnp.clip(emit - 1, 0, n_spec)[:, None], axis=1
            )[:, 0]
            toks = jnp.where(emit > 0, new_tok, toks)
            pos = pos + emit
            gen_off = gen_off + emit.astype(jnp.uint32)
            base = base + emit
            rem = rem - emit
            drafts = drafts + jnp.where(live, gamma, 0)
            # Telemetry numerator is the PRE-budget-clip acceptance (the
            # draft's predictive quality — speculative_accept's contract);
            # emission may be clipped below it on a row's last tokens.
            accepted = accepted + jnp.where(live, acc, 0)
            # Tree nodes the verify pass considered for the row: the tw
            # depth-1 nodes plus the gamma-1 deeper chain nodes (tw=1
            # degrades to gamma — the linear chain itself).
            tree_cnt = tree_cnt + jnp.where(
                live & g_on, tw - 1 + gamma, 0
            )
            cycles = cycles + jnp.where(live, 1, 0)
            toks_buf = toks_buf.at[j].set(z_path.T)
            emit_buf = emit_buf.at[j].set(emit)
            if logprobs_k:
                lp_bufs = (
                    lp_bufs[0].at[j].set(lp_c),
                    lp_bufs[1].at[j].set(lp_t),
                    lp_bufs[2].at[j].set(lp_i),
                )
            return (j + 1, toks, pos, gen_off, rem, base, ring_k, ring_v,
                    ring_pos, drk, drv, drp, counts, drafts, accepted,
                    tree_cnt, cycles, toks_buf, emit_buf, lp_bufs)

        zero_b = jnp.zeros((b,), jnp.int32)
        state0 = (
            jnp.int32(0), tokens0, pos0, jnp.zeros((b,), jnp.uint32),
            budget, zero_b, ring_k0, ring_v0, ring_pos0, drk0, drv0, drp0,
            counts0, zero_b, zero_b, zero_b, zero_b, toks_buf0, emit_buf0,
            lp_bufs0,
        )
        final = jax.lax.while_loop(
            lambda st: (st[0] < k_cyc) & jnp.any(st[4] > 0),
            cycle, state0,
        )
        (_, final_toks, _, _, _, _, ring_k, ring_v, ring_pos, drk, drv,
         drp, _, drafts, accepted, tree_cnt, cycles, toks_buf, emit_buf,
         lp_bufs) = final

        # The dispatch's committed ring entries go to the pool and to the
        # persistent window in place (ops/kv_write.py): ring entry r of
        # row i holds position pos0[i] + r, and the committed entries are
        # the first n_commit[i] (the rest still hold the sentinel).
        with jax.named_scope("kv_write"):
            n_commit = jnp.sum(ring_pos < _POS_SENTINEL, axis=1,
                               dtype=jnp.int32)
            kv_k, kv_v = write_token_runs(
                (kv_k, kv_v), (ring_k, ring_v), block_tables, pos0,
                n_commit, bs,
            )
            win_k, win_v = self._append_window(
                win_k, win_v, ring_k, ring_v, pos0, n_commit
            )
            spec_k, spec_v, spec_pos = self._write_spec_rows(
                spec_k, spec_v, spec_pos, slot_idx, drk, drv, drp
            )

        last_token = jnp.zeros((b_max,), jnp.int32).at[:b].set(final_toks)
        lp_c_buf, lp_t_buf, lp_i_buf = lp_bufs if logprobs_k else (
            None, None, None
        )
        spec_stats = jnp.stack([drafts, accepted, tree_cnt, cycles])
        return (toks_buf, kv_k, kv_v, kv_ks, kv_vs, win_k, win_v,
                lp_c_buf, lp_t_buf, lp_i_buf, last_token, emit_buf,
                spec_stats, spec_k, spec_v, spec_pos)

    def decode_bucket(self, rows: int) -> int:
        """Rows of the decode program that ``rows`` sequences run in."""
        return _bucket(rows, 1, max(1, self.config.max_num_seqs))

    # -------------------------------------------------------- stored programs
    def _jitted(self, kind: str):
        """``_decode`` or ``_prefill``, by a program's kind."""
        return self._decode if kind == "decode" else self._prefill

    def _load_program(self, key: str):
        """The stored program ``key``, loaded into the table; None where
        its file is missing, short or refused by the backend: the program
        then takes the traced path in this process, and the manifest goes,
        so that the next boot stores everything again."""
        t0 = time.monotonic()
        try:
            loaded = self._store.load(key, list(self.mesh.devices.flat))
        except Exception:  # noqa: BLE001 — whatever refuses it: trace it
            logger.warning(
                "Stored program %s did not load: it is traced, and the "
                "warm-up manifest is dropped", key, exc_info=True)
            self._forget_program(key)
            return None
        self._programs[key] = loaded
        compile_clock().loaded(time.monotonic() - t0)
        return loaded

    def _forget_program(self, key: str) -> None:
        """The store failed ``key``: out of the table, traced from now on
        in this process, and no manifest for the next boot to trust."""
        self._programs.pop(key, None)
        self._stored -= {key}
        self._store.drop_manifest()

    def _dispatch(self, kind: str, program: Dict, args, static):
        """Enqueue one dispatch program: through its loaded executable
        where this process holds one or the store has one to load (a warm
        boot's deferred variant, at its first use), else through the jitted
        function, which traces, lowers and compiles it. Never both, so a
        program is resident once."""
        key = program["key"]
        loaded = self._programs.get(key)
        if loaded is None and key in self._stored:
            loaded = self._load_program(key)
        if loaded is not None:
            return loaded(*args)
        return self._jitted(kind)(*args, **static)

    def _issue_decode(self, batch: ScheduledBatch) -> "DispatchHandle":
        cfg = self.config
        seqs = batch.seqs
        k = batch.num_steps
        b = self.decode_bucket(len(seqs))
        mb = self._decode_mb(max(len(s.block_ids) for s in seqs))

        packed = np.zeros((NUM_SCALARS * b + b * mb,), np.int32)
        sc = packed[: NUM_SCALARS * b].reshape(NUM_SCALARS, b)
        bt = packed[NUM_SCALARS * b:].reshape(b, mb)
        f32 = sc.view(np.float32)
        u32 = sc.view(np.uint32)
        has_penalties = any(
            s.sampling.presence_penalty or s.sampling.frequency_penalty
            for s in seqs
        )
        logprobs_k = max(
            (logprobs_bucket(s.sampling.logprobs) for s in seqs
             if s.sampling.logprobs is not None),
            default=0,
        )
        sc[11, :] = -1
        spec_on = True
        gammas: Optional[List[int]] = None
        if self.spec_n:
            # Padding rows get an out-of-range slot: their scatter-back
            # drops instead of clobbering slot 0 (see _decode_spec).
            sc[12, :] = self.spec_num_slots
            if self._spec_controller is not None:
                gammas = [
                    self._spec_controller.gamma(s.request_id) for s in seqs
                ]
                if not any(gammas):
                    # Every row's controller says gamma=0: dispatch the
                    # PLAIN decode body (spec_on=False static variant) —
                    # no draft steps, no ring traffic, no slot churn.
                    # This is the measured degradation bar: an all-cold
                    # batch must cost exactly what spec-off costs.
                    spec_on = False
                    self.spec_gamma0_dispatches_total += 1
            batch.spec_mode = (
                "off-degrade" if not spec_on
                else "adaptive" if self.spec_adaptive
                else "tree" if self.spec_tree_width > 1
                else "linear"
            )
        chain_entry = None  # the ONE device vector this dispatch chains from
        for i, s in enumerate(seqs):
            if self.spec_n and spec_on:
                g = gammas[i] if gammas is not None else self.spec_n
                sc[13, i] = g
                if g > 0:
                    # Disagg decode hops / restores join decode without a
                    # local prefill; give the draft its context first.
                    # gamma=0 rows skip BOTH (no draft work this
                    # dispatch; a later probe's catch-up replays the gap
                    # from the warmed ledger).
                    self._spec_catch_up(s, s.num_computed_tokens)
                    sc[12, i] = self.spec_slot(s.request_id)
            pos = s.num_computed_tokens
            # Token chaining: a row whose last sampled token still sits in
            # an in-flight dispatch's device buffer (unapplied — the
            # pipelined engine issues before fetching) reads it ON DEVICE
            # from that dispatch's last-token vector; rows with
            # fully-applied host tokens take the packed tokens0. The
            # source may be a decode (the row rode it) or a prefill (the
            # row's last prompt chunk: it joins this train straight behind
            # it). All chained rows must resolve to the SAME source
            # dispatch — the engine loop's depth guarantees it (at most
            # two dispatches in flight, so exactly one is unapplied when
            # this one is issued: engine._run_loop); the two errors below
            # are the check.
            if pos < len(s.all_token_ids):
                sc[0, i] = s.all_token_ids[pos]
            else:
                src, src_entry = -1, None
                for entry in self._chains:  # newest first
                    r = entry["row"].get(s.request_id, -1)
                    if r >= 0 and entry["epoch"][s.request_id] == \
                            s.num_preemptions:
                        src, src_entry = r, entry
                        break
                if src < 0:
                    raise RuntimeError(
                        f"row {s.request_id}: token at pos {pos} neither "
                        f"applied on host nor chainable from a recent "
                        f"dispatch (pipeline invariant breach)"
                    )
                if chain_entry is None:
                    chain_entry = src_entry
                elif chain_entry is not src_entry:
                    raise RuntimeError(
                        f"row {s.request_id}: decode batch chains start "
                        f"tokens from two different in-flight dispatches "
                        f"(overlap single-source invariant breach)"
                    )
                sc[11, i] = src
            sc[1, i] = pos
            sc[2, i] = batch.decode_steps[i]
            u32[3, i] = _seed_base(s)
            u32[4, i] = len(s.output_token_ids) + s.inflight_steps
            sc[8, i] = s.adapter_idx
            sp = s.sampling
            f32[5, i] = sp.temperature
            sc[6, i] = sp.top_k
            f32[7, i] = sp.top_p
            f32[9, i] = sp.presence_penalty
            f32[10, i] = sp.frequency_penalty
            bt[i, :len(s.block_ids)] = s.block_ids
            if self.state_specs:
                sc[12, i] = s.state_slot
        self._count_sample_dispatch(f32[5], sc[6], f32[7])
        if has_penalties:
            vocab = self.model_config.vocab_size
            counts = np.zeros((b, vocab), np.int32)
            for i, s in enumerate(seqs):
                if s.output_token_ids:
                    np.add.at(
                        counts[i],
                        np.asarray(s.output_token_ids, np.int64) % vocab, 1,
                    )
        else:
            counts = np.zeros((1, 1), np.int32)

        ids = tuple(s.request_id for s in seqs)
        cache = self._win_cache
        # The cached window is valid when the SAME ordered rows decode again
        # at positions its content covers: the original gather ([0, old
        # pos)) plus the appended accepted tokens. Truncated/rolled-back
        # rows (pos below the covered end) are fine — entries past win_len
        # are masked, and determinism regenerates identical KV beneath it.
        use_cached = (
            self.attn_impl != "paged"
            and cache is not None
            and cache["ids"] == ids
            and cache["b"] == b and cache["mb"] == mb
            and all(
                seqs[i].num_computed_tokens <= cache["end"][i]
                for i in range(len(seqs))
            )
        )
        if use_cached:
            wk, wv = cache["win"]
            self._win_cache = None  # buffers are donated to the dispatch
        else:
            # paged impl AND the fresh-gather window variant never read the
            # input buffers — donation fodder only, so dummies suffice (the
            # fresh variant returns the gathered windows it builds itself).
            self._win_cache = None  # drop any stale buffers now
            wk = jnp.zeros((1, 1, 1, 1, 1), self.dtype)
            wv = jnp.zeros((1, 1, 1, 1, 1), self.dtype)

        prev_last = (
            chain_entry["last"] if chain_entry is not None else self._zero_last
        )
        kv_ks, kv_vs = self._scale_pool_args()
        dparams, sp_k, sp_v, sp_p = self._spec_pool_args()
        program = self.program("decode", (b, mb, k, use_cached),
                               has_penalties, logprobs_k, spec_on)
        (toks_all, self.kv_k, self.kv_v, kv_ks2, kv_vs2, wk2, wv2, lp_c,
         lp_t, lp_i, last_token, emits, spec_stats_dev, sp_k2,
         sp_v2, sp_p2, self.state_pools, fwd_stats) = self._dispatch(
            "decode", program,
            (self.params, jnp.asarray(packed), self.kv_k, self.kv_v,
             kv_ks, kv_vs, wk, wv, jnp.asarray(counts), prev_last,
             dparams, sp_k, sp_v, sp_p, self.state_pools),
            dict(b=b, mb=mb, num_steps=k, use_cached_window=use_cached,
                 has_penalties=has_penalties, logprobs_k=logprobs_k,
                 spec_on=spec_on),
        )
        self._rebind_scale_pools(kv_ks2, kv_vs2)
        self._rebind_spec_pools(sp_k2, sp_v2, sp_p2)
        if self.kv_quantized:
            self.kv_quant_tokens_written += sum(batch.decode_steps)
        noted = self._note_fwd_stats("decode", fwd_stats) \
            if self.fwd_stats else 0
        cache = None
        if self.attn_impl != "paged":
            cache = {
                "ids": ids, "b": b, "mb": mb,
                # Speculative dispatches emit a VARIABLE token count; the
                # fetch closure below advances "end" by the actual emits
                # (strict pipeline ordering: the next schedule pass runs
                # only after that fetch applies).
                "end": [
                    seqs[i].num_computed_tokens
                    + (0 if (self.spec_n and spec_on)
                       else batch.decode_steps[i])
                    for i in range(len(seqs))
                ],
                "win": (wk2, wv2),
            }
            self._win_cache = cache
        self._push_chain({
            "last": last_token,
            "row": {s.request_id: i for i, s in enumerate(seqs)},
            "epoch": {s.request_id: s.num_preemptions for s in seqs},
        })
        steps = list(batch.decode_steps)
        n = len(seqs)

        if self.spec_n and spec_on:
            # Issue-time positions (advance_at_issue runs after this
            # call returns, so num_computed_tokens is still pos0 here).
            poss = [s.num_computed_tokens for s in seqs]
            row_gammas = gammas if gammas is not None else [self.spec_n] * n

            def fetch():
                out = np.asarray(toks_all)          # [K, N+1, b]
                em = np.asarray(emits)              # [K, b]
                stats = np.asarray(spec_stats_dev)  # [4, b]
                drafts_cnt, accepted_cnt = stats[0], stats[1]
                tokens = []
                for i in range(n):
                    row = []
                    for c in range(out.shape[0]):
                        row.extend(
                            int(out[c, t, i]) for t in range(em[c, i])
                        )
                    tokens.append(row)
                    rid = seqs[i].request_id
                    if row_gammas[i] > 0:
                        # Ring-warm ledger: the dispatch wrote draft KV
                        # for the emitted tokens. (Tree mode: a cycle
                        # that salvaged a depth-1 SIBLING leaves that
                        # one position's entry rolled back — an
                        # acceptance-only pinhole the sentinel masks;
                        # not worth a per-cycle host fetch to track.)
                        # gamma=0 rows wrote nothing: their ledger
                        # stays put so the next probe's catch-up
                        # replays the gap.
                        self._spec_warmed[rid] = poss[i] + len(row)
                    if self._spec_controller is not None:
                        self._spec_controller.update(
                            rid, int(drafts_cnt[i]), int(accepted_cnt[i])
                        )
                # Acceptance telemetry accumulates at fetch (GIL-safe
                # int adds; the engine loop serializes runner calls).
                d_tot = int(drafts_cnt.sum())
                a_tot = int(accepted_cnt.sum())
                self.spec_draft_tokens_total += d_tot
                self.spec_accepted_tokens_total += a_tot
                self._spec_window.append((d_tot, a_tot))
                # stats row 0 is the sum of per-row gammas over live
                # cycles — exactly the served-depth numerator.
                self.spec_draft_depth_sum += d_tot
                self.spec_tree_nodes_total += int(stats[2].sum())
                self.spec_live_cycles_total += int(stats[3].sum())
                if cache is not None and self._win_cache is cache:
                    for i in range(n):
                        cache["end"][i] += len(tokens[i])
                if not logprobs_k:
                    return tokens, None
                lpc = np.asarray(lp_c)              # [K, N+1, b]
                lpt = np.asarray(lp_t)
                lpi = np.asarray(lp_i)
                lps = []
                for i, s in enumerate(seqs):
                    want = s.sampling.logprobs
                    if want is None:
                        lps.append(None)
                        continue
                    entries = []
                    for c in range(out.shape[0]):
                        for t in range(em[c, i]):
                            top = [
                                (int(lpi[c, t, i, r]), float(lpt[c, t, i, r]))
                                for r in range(min(want, lpi.shape[-1]))
                            ]
                            entries.append((float(lpc[c, t, i]), top))
                    lps.append(entries)
                return tokens, lps

            return DispatchHandle(fetch, program, n)

        def fetch():
            out = np.asarray(toks_all)  # ONE [K, B] fetch per K*B tokens
            self._drain_fwd_stats(noted)
            tokens = [
                [int(out[j, i]) for j in range(steps[i])] for i in range(n)
            ]
            if not logprobs_k:
                return tokens, None
            return tokens, self._gather_logprobs(
                seqs, steps, np.asarray(lp_c), np.asarray(lp_t),
                np.asarray(lp_i),
            )

        return DispatchHandle(fetch, program, n)

    @staticmethod
    def _gather_logprobs(seqs, steps, lp_c, lp_t, lp_i):
        """Per-seq aligned logprob entries from the dispatch arrays
        ([K, b], [K, b, k], [K, b, k]): rows that asked for logprobs get
        one (chosen_lp, [(token_id, lp), ...top-k-requested]) per accepted
        token; others get None."""
        out = []
        for i, s in enumerate(seqs):
            want = s.sampling.logprobs
            if want is None:
                out.append(None)
                continue
            entries = []
            for j in range(steps[i]):
                top = [
                    (int(lp_i[j, i, r]), float(lp_t[j, i, r]))
                    for r in range(min(want, lp_i.shape[-1]))
                ]
                entries.append((float(lp_c[j, i]), top))
            out.append(entries)
        return out

    # ----------------------------------------------------------------- prefill
    def _prefill_impl(self, params, packed, kv_k, kv_v, kv_ks, kv_vs,
                      counts0, dparams, spec_k, spec_v, spec_pos,
                      state_pools, *,
                      b: int, t: int, mb: int, has_window: bool,
                      b_max: int, has_penalties: bool = False,
                      logprobs_k: int = 0, segs: int = 0):
        """One (multi-sequence) prefill chunk dispatch.

        Two forms (``prefill_packs``). ``segs`` 0: a ``[b, t]`` rectangle,
        sequence i the row i. ``segs`` > 0 (and b == 1): ONE packed row of
        t tokens holding up to ``segs`` sequences' chunks end to end from
        token 0 (``_forward_packed_row``). Either way ``packed`` carries a
        set of scalars and a block-table row a SEQUENCE, and what is
        sampled, chained and fetched is indexed by sequence.

        kv_ks/kv_vs: per-(slot, head) dequant scale pools when the KV cache
        is quantized (donated + returned rebound, like _decode_impl); the
        chunk's fresh KV is quantized on device at the end of the dispatch
        — no extra host round-trip — and the history window gather
        dequantizes inline.

        packed: int32[n*(NUM_SCALARS+mb) + b*t], n = segs or b: per-
        sequence scalars (chunk_start, chunk_len, seed_base, gen0, temps,
        top_k, top_p, pad, adapter, presence, frequency), the [n, mb]
        block tables, then the [b, t] chunk token ids. Positions and the
        KV write slots are derived on device.

        counts0/has_penalties/logprobs_k: see _decode_impl — they shape the
        FINAL sampled token (non-final chunks never fetch it). Penalties
        matter here only for preempted sequences re-prefilling with prior
        output tokens; fresh prompts have zero counts (output-only
        penalties, vLLM semantics).

        state_pools: see _decode_impl. A chunk starts from its row's slot
        — from zeros where chunk_start is 0: a slot is cleared when a
        sequence starts in it, not when one leaves it — and leaves the
        state after its last valid token, so a prompt longer than the
        token budget crosses chunks through the slot.
        """
        cfg = self.config
        bs = cfg.block_size
        mc = self.model_config
        n = segs or b       # sequences: segments of the row, or rows
        scalars = packed[: NUM_SCALARS * n].reshape(NUM_SCALARS, n)
        chunk_start = scalars[0]
        chunk_lens = scalars[1]
        seed_base = jax.lax.bitcast_convert_type(scalars[2], jnp.uint32)
        gen0 = jax.lax.bitcast_convert_type(scalars[3], jnp.uint32)
        temps = jax.lax.bitcast_convert_type(scalars[4], jnp.float32)
        top_k = scalars[5]
        top_p = jax.lax.bitcast_convert_type(scalars[6], jnp.float32)
        adapter_idx = scalars[8]
        presence = jax.lax.bitcast_convert_type(scalars[9], jnp.float32)
        frequency = jax.lax.bitcast_convert_type(scalars[10], jnp.float32)
        lora = (adapter_idx, self.lora_stacks) if self.lora_stacks else None
        block_tables = packed[NUM_SCALARS * n: NUM_SCALARS * n + n * mb].reshape(n, mb)
        token_ids = packed[NUM_SCALARS * n + n * mb:].reshape(b, t)
        if segs:
            (last_hidden, kv_k, kv_v, state_pools,
             fwd_stats) = self._forward_packed_row(
                params, token_ids, block_tables, chunk_start, chunk_lens,
                kv_k, kv_v, state_pools,
                scalars[12] if self.state_specs else None)
            next_tokens, lp = self._sample_first_tokens(
                params, last_hidden, counts0, temps, top_k, top_p,
                seed_base, gen0, presence, frequency, has_penalties,
                logprobs_k)
            last_token = jnp.zeros((b_max,), jnp.int32).at[:n].set(
                next_tokens.astype(jnp.int32))
            return (next_tokens, kv_k, kv_v, kv_ks, kv_vs, *lp, last_token,
                    spec_k, spec_v, spec_pos, state_pools, fwd_stats)

        t_iota = jnp.arange(t, dtype=jnp.int32)
        positions = jnp.minimum(
            chunk_start[:, None] + t_iota[None, :], cfg.max_model_len - 1
        )                                                        # [b, t]
        in_chunk = t_iota[None, :] < chunk_lens[:, None]

        quant = self.kv_quantized
        if self.prefill_reads_pool:
            # The rows' history is read in place, up to chunk_start, by
            # ``attend`` (a first chunk's is empty): no window, whatever
            # ``has_window`` says (the families carry it False).
            view = KVView(
                pool_k=kv_k, pool_v=kv_v, block_tables=block_tables,
                kv_lens=chunk_start, block_size=bs,
            )
        elif has_window:
            win_k, win_v = gather_window(
                kv_k, kv_v, block_tables, bs,
                kv_ks if quant else None, kv_vs if quant else None,
                out_dtype=self.dtype,
            )
            view = KVView(win_k, win_v, chunk_start)
        else:
            view = KVView()

        # Sequence-parallel prefill rides ring attention over the sp mesh
        # axis (ops/attention.py:attend) — first chunks ring the chunk itself;
        # continuation chunks ring the combined (history window ++ chunk)
        # sequence, so EVERY chunk of a long prefill sequence-shards
        # (VERDICT r4 weak #5). Both the chunk and the combined KV length
        # must divide by sp (shard_map even-sharding requirement).
        from production_stack_tpu.parallel.mesh import AXIS_SP

        sp = self.mesh.shape[AXIS_SP]
        rings = (
            t > 1 and sp > 1 and t % sp == 0
            and (not has_window or (mb * bs + t) % sp == 0)
        )
        # The decode loop's one call shape: state in and out where the
        # module declares any (such a module is refused sequence
        # parallelism, so ``rings`` is false), counters last where it
        # declares those.
        state_in = {}
        if self.state_specs:
            state_slots = scalars[12]
            state_in["state"] = self._read_state_rows(
                state_pools, state_slots, fresh=chunk_start == 0)
        hidden, k_new, v_new, *extra = self._forward(
            params, mc, token_ids, positions, chunk_lens,
            view._replace(sp_mesh=self.mesh if rings else None),
            act_sharding=self._act_sharding, lora=lora, **state_in,
        )
        if self.state_specs:
            rows_state = extra.pop(0)
        fwd_stats = extra.pop(0) if self.fwd_stats else ()
        logit_idx = jnp.maximum(chunk_lens - 1, 0)
        last_hidden = hidden[jnp.arange(b), logit_idx]            # [b, D]
        next_tokens, lp = self._sample_first_tokens(
            params, last_hidden, counts0, temps, top_k, top_p, seed_base,
            gen0, presence, frequency, has_penalties, logprobs_k)

        # The chunk's KV goes to the pool in place, one block-wide slab
        # at a time (ops/kv_write.py): row i's tokens j < chunk_lens[i]
        # are the consecutive positions chunk_start[i] + j.
        with jax.named_scope("kv_write"):
            if quant:
                # Quantize the chunk's KV on device before the write —
                # compute-dtype KV never lands in the pool.
                from production_stack_tpu.ops.quantization import quantize_kv

                kq, ks = quantize_kv(k_new)
                vq, vs = quantize_kv(v_new)
                kv_k, kv_v, kv_ks, kv_vs = write_token_runs(
                    (kv_k, kv_v, kv_ks, kv_vs), (kq, vq, ks, vs),
                    block_tables, chunk_start, chunk_lens, bs,
                )
            else:
                kv_k, kv_v = write_token_runs(
                    (kv_k, kv_v), (k_new, v_new), block_tables,
                    chunk_start, chunk_lens, bs,
                )
            if self.state_specs:
                with jax.named_scope("state_write"):
                    state_pools = write_state_rows(
                        state_pools, rows_state, state_slots)
        # Speculative draft warm-up (docs/PERF.md round 8): run the DRAFT
        # model over the same chunk so its per-sequence KV ring holds the
        # prompt context before decode starts — a cold draft ring proposes
        # from near-zero context and acceptance collapses. Rows starting a
        # fresh (re)prefill at chunk_start 0 reset their ring first, so a
        # preempted/resumed sequence never attends stale entries.
        if self.spec_n:
            dmc = self.spec_draft_config
            r_len = self.spec_ring_len
            dnl, dhkv, ddh = (dmc.num_layers, dmc.num_kv_heads,
                              dmc.head_dim_)
            slot_idx = scalars[12]
            # Clipped gather / raw-index dropping write-back: see
            # _decode_spec (padding rows must never write slot 0).
            slot_c = jnp.clip(slot_idx, 0, spec_pos.shape[0] - 1)
            drk = spec_k[:, :, slot_c]
            drv = spec_v[:, :, slot_c]
            drp = spec_pos[slot_c]                       # [b, R]
            drp = jnp.where(
                (chunk_start == 0)[:, None], _POS_SENTINEL, drp
            )
            d_max_pos = self._spec_draft_max_pos
            d_positions = jnp.minimum(positions, d_max_pos - 1)
            _, dk, dv = self._draft_forward(
                dparams, dmc, token_ids, d_positions, chunk_lens,
                KVView(ring_k=drk, ring_v=drv, ring_pos=drp),
            )                                  # dk: [Ld, Hd, b, t, Dd]
            # Keep only the last min(t, R) chunk tokens per row: their
            # ring indices (pos % R) are then collision-free, so the
            # scatter stays deterministic; older tokens fall out of the
            # ring window exactly as they would during decode.
            chunk_end = chunk_start + chunk_lens
            iota_b2 = jnp.arange(b, dtype=jnp.int32)[:, None]
            keep = in_chunk & (positions >= (chunk_end[:, None] - r_len))
            widx = jnp.where(
                keep, iota_b2 * r_len + positions % r_len, b * r_len
            ).reshape(-1)
            drk = drk.reshape(dnl, dhkv, b * r_len, ddh).at[
                :, :, widx
            ].set(
                dk.reshape(dnl, dhkv, b * t, ddh), mode="drop"
            ).reshape(dnl, dhkv, b, r_len, ddh)
            drv = drv.reshape(dnl, dhkv, b * r_len, ddh).at[
                :, :, widx
            ].set(
                dv.reshape(dnl, dhkv, b * t, ddh), mode="drop"
            ).reshape(dnl, dhkv, b, r_len, ddh)
            drp = drp.reshape(-1).at[widx].set(
                positions.reshape(-1), mode="drop"
            ).reshape(b, r_len)
            with jax.named_scope("kv_write"):
                spec_k, spec_v, spec_pos = self._write_spec_rows(
                    spec_k, spec_v, spec_pos, slot_idx, drk, drv, drp
                )
        # Device-resident last-token vector (final rows' sampled tokens):
        # the first decode dispatch after this prefill may chain from it
        # without a host roundtrip (see _decode_impl).
        last_token = jnp.zeros((b_max,), jnp.int32).at[:b].set(
            next_tokens.astype(jnp.int32)
        )
        return (next_tokens, kv_k, kv_v, kv_ks, kv_vs, lp[0], lp[1], lp[2],
                last_token, spec_k, spec_v, spec_pos, state_pools, fwd_stats)

    def _sample_first_tokens(self, params, last_hidden, counts0, temps,
                             top_k, top_p, seed_base, gen0, presence,
                             frequency, has_penalties: bool,
                             logprobs_k: int):
        """The token each sequence of a prefill dispatch would emit after
        its chunk, from the hidden state of the chunk's last token ([n,
        D]), and its log-probabilities ((None,) * 3 where none are asked
        for): what both forms of ``_prefill_impl`` end in."""
        logits = self._logits_fn(params, self.model_config, last_hidden)
        seeds = self._derive_seeds(seed_base, gen0, jnp.uint32(0))
        if has_penalties:
            from production_stack_tpu.engine.sampling import apply_penalties

            eff = apply_penalties(logits, counts0, presence, frequency)
        else:
            eff = logits
        next_tokens = sample_tokens(eff, temps, top_k, top_p, seeds)
        if logprobs_k:
            from production_stack_tpu.engine.sampling import compute_logprobs

            lp = compute_logprobs(logits, next_tokens, logprobs_k)
        else:
            lp = (None, None, None)
        return next_tokens, lp

    def _forward_packed_row(self, params, token_ids, block_tables,
                            chunk_start, chunk_lens, kv_k, kv_v,
                            state_pools, state_slots):
        """The forward of a PACKED prefill row (``prefill_packs``):
        ``token_ids`` [1, t] holds the sequences' chunks end to end from
        token 0, segment i ``chunk_lens[i]`` tokens at positions
        ``chunk_start[i]`` on, its history the pool's slots below
        ``chunk_start[i]`` by ``block_tables[i]``. Everything but
        attention and a state the module keeps is a function of a token,
        so the model runs the row as it runs any row; ``attend`` is told
        where each segment begins (``KVView.seg_lens``), and so is whatever
        keeps a state: the module takes and hands back a row of state a
        SEGMENT (its ``STATES_CROSSING_SEGMENTS``), read here from the
        segments' slots (``state_slots``; from zeros where a segment is its
        sequence's first chunk) and written back to them, as a rectangle's
        rows are. Returns (the hidden state of each segment's last token
        [n, D], the pools with the row's K/V written to each segment's
        slots, the state pools, the forward's counters)."""
        cfg = self.config
        seg_end = jnp.cumsum(chunk_lens)
        seg, within = segment_of_token(chunk_lens, token_ids.shape[1])
        positions = jnp.minimum(
            chunk_start[seg] + within, cfg.max_model_len - 1)[None]  # [1, t]
        view = KVView(
            pool_k=kv_k, pool_v=kv_v, block_tables=block_tables,
            kv_lens=chunk_start, seg_lens=chunk_lens,
            block_size=cfg.block_size,
        )
        state_in = {}
        if self.state_specs:
            state_in["state"] = self._read_state_rows(
                state_pools, state_slots, fresh=chunk_start == 0)
        hidden, k_new, v_new, *extra = self._forward(
            params, self.model_config, token_ids, positions, seg_end[-1:],
            view, act_sharding=self._act_sharding, lora=None, **state_in,
        )
        if self.state_specs:
            segs_state = extra.pop(0)
        fwd_stats = extra.pop(0) if self.fwd_stats else ()
        last_hidden = hidden[0, jnp.maximum(seg_end - 1, 0)]      # [n, D]
        with jax.named_scope("kv_write"):
            kv_k, kv_v = write_token_runs(
                (kv_k, kv_v), (k_new, v_new), block_tables, chunk_start,
                chunk_lens, cfg.block_size,
                source_start=seg_end - chunk_lens,
            )
            if self.state_specs:
                with jax.named_scope("state_write"):
                    state_pools = write_state_rows(
                        state_pools, segs_state, state_slots)
        return last_hidden, kv_k, kv_v, state_pools, fwd_stats

    def _issue_prefill(self, batch: ScheduledBatch) -> "DispatchHandle":
        cfg = self.config
        seqs = batch.seqs
        n = len(seqs)
        b, t = prefill_rectangle(
            n, max(batch.chunk_lens), cfg,
            sum(batch.chunk_lens) if batch.packed else None)
        # A packed row's scalars and block tables are a SEGMENT each.
        segs = self._prefill_segs if batch.packed else 0
        rows = segs or b
        has_window = not self.prefill_reads_pool and \
            any(st > 0 for st in batch.chunk_starts)
        mb = self._prefill_mb(max(len(s.block_ids) for s in seqs),
                              has_window, b)

        finals = [
            batch.chunk_starts[i] + batch.chunk_lens[i] >= seqs[i].num_tokens
            for i in range(n)
        ]
        # Penalty/logprob variants only matter for the FINAL chunk's sampled
        # token; non-final chunks stay on the default variant.
        has_penalties = any(finals) and any(
            s.sampling.presence_penalty or s.sampling.frequency_penalty
            for s in seqs
        )
        logprobs_k = 0
        if any(finals):
            logprobs_k = max(
                (logprobs_bucket(s.sampling.logprobs) for s in seqs
                 if s.sampling.logprobs is not None),
                default=0,
            )

        packed = np.zeros((NUM_SCALARS * rows + rows * mb + b * t,),
                          np.int32)
        sc = packed[: NUM_SCALARS * rows].reshape(NUM_SCALARS, rows)
        bt = packed[NUM_SCALARS * rows:
                    NUM_SCALARS * rows + rows * mb].reshape(rows, mb)
        toks = packed[NUM_SCALARS * rows + rows * mb:].reshape(b, t)
        f32 = sc.view(np.float32)
        u32 = sc.view(np.uint32)
        if self.spec_n:
            # Padding rows: out-of-range slot -> scatter-back drops.
            sc[12, :] = self.spec_num_slots
        for i, s in enumerate(seqs):
            start, ln = batch.chunk_starts[i], batch.chunk_lens[i]
            sc[0, i] = start
            sc[1, i] = ln
            if self.spec_n:
                # Cache-hit/restored prefixes never prefill on this
                # engine, so replay them through the draft first — an
                # un-warmed ring collapses acceptance on exactly the
                # cache-friendly workloads speculation should help.
                self._spec_catch_up(s, start)
                sc[12, i] = self.spec_slot(s.request_id)
                self._spec_warmed[s.request_id] = start + ln
            u32[2, i] = _seed_base(s)
            u32[3, i] = len(s.output_token_ids)
            sc[8, i] = s.adapter_idx
            sp = s.sampling
            f32[4, i] = sp.temperature
            sc[5, i] = sp.top_k
            f32[6, i] = sp.top_p
            f32[9, i] = sp.presence_penalty
            f32[10, i] = sp.frequency_penalty
            bt[i, :len(s.block_ids)] = s.block_ids
            if self.state_specs:
                sc[12, i] = s.state_slot
            # A packed row's chunks lie end to end; a rectangle's a row each.
            row, at = (0, sum(batch.chunk_lens[:i])) if segs else (i, 0)
            toks[row, at:at + ln] = s.all_token_ids[start:start + ln]
        self._count_sample_dispatch(f32[4], sc[5], f32[6])
        if has_penalties:
            vocab = self.model_config.vocab_size
            counts = np.zeros((rows, vocab), np.int32)
            for i, s in enumerate(seqs):
                if s.output_token_ids:
                    np.add.at(
                        counts[i],
                        np.asarray(s.output_token_ids, np.int64) % vocab, 1,
                    )
        else:
            counts = np.zeros((1, 1), np.int32)

        kv_ks, kv_vs = self._scale_pool_args()
        dparams, sp_k, sp_v, sp_p = self._spec_pool_args()
        program = self.program(
            "prefill", (b, t, mb, has_window), has_penalties, logprobs_k)
        (next_tokens, self.kv_k, self.kv_v, kv_ks2, kv_vs2, lp_c, lp_t,
         lp_i, last_token, sp_k2, sp_v2, sp_p2,
         self.state_pools, fwd_stats) = self._dispatch(
            "prefill", program,
            (self.params, jnp.asarray(packed), self.kv_k, self.kv_v,
             kv_ks, kv_vs, jnp.asarray(counts), dparams, sp_k, sp_v, sp_p,
             self.state_pools),
            dict(b=b, t=t, mb=mb, has_window=has_window, b_max=self._b_max,
                 has_penalties=has_penalties, logprobs_k=logprobs_k,
                 segs=segs),
        )
        self._rebind_scale_pools(kv_ks2, kv_vs2)
        self._rebind_spec_pools(sp_k2, sp_v2, sp_p2)
        if self.kv_quantized:
            self.kv_quant_tokens_written += sum(batch.chunk_lens)
        noted = self._note_fwd_stats("prefill", fwd_stats) \
            if self.fwd_stats else 0
        # Final rows' sampled tokens are chainable by the next decode
        # dispatch without a host roundtrip. Non-final chunks produce no
        # tokens — no entry, so they never evict a live decode chain.
        if any(finals):
            self._push_chain({
                "last": last_token,
                "row": {
                    s.request_id: i for i, s in enumerate(seqs) if finals[i]
                },
                "epoch": {
                    s.request_id: s.num_preemptions
                    for i, s in enumerate(seqs) if finals[i]
                },
            })

        def fetch():
            if not any(finals):
                # No row finished its prompt: no blocking fetch at all.
                return [[] for _ in range(n)], None
            out = np.asarray(next_tokens)
            self._drain_fwd_stats(noted)
            tokens = [[int(out[i])] if finals[i] else [] for i in range(n)]
            if not logprobs_k:
                return tokens, None
            lp = self._gather_logprobs(
                seqs, [1 if f else 0 for f in finals],
                np.asarray(lp_c)[None], np.asarray(lp_t)[None],
                np.asarray(lp_i)[None],
            )
            return tokens, lp

        return DispatchHandle(fetch, program, n)

    # ------------------------------------------------------------ token chain
    def _push_chain(self, entry: Dict) -> None:
        """Record a token-producing dispatch's device-resident last-token
        vector (newest first, bounded): later decodes chain start tokens
        from it until the dispatch's results reach the host."""
        self._chains.insert(0, entry)
        del self._chains[self._max_chains:]

    # ---------------------------------------------------------------- execute
    def _count_sample_dispatch(self, temps: np.ndarray, top_k: np.ndarray,
                               top_p: np.ndarray) -> None:
        """What the device's conds will see, from the float32 vectors a
        dispatch has just packed (padding rows included: they are greedy)."""
        any_sampled, any_filtered = sampler_paths(temps, top_k, top_p)
        self.sample_dispatches_total += 1
        self.sample_dispatches_greedy_total += not any_sampled
        self.sample_dispatches_filtered_total += bool(any_filtered)

    def execute_async(self, batch: ScheduledBatch,
                      step_counter: int) -> "DispatchHandle":
        """ISSUE one dispatch (async — returns before any device->host
        sync). The returned handle's fetch() blocks on the results; the
        pipelined engine loop issues the next dispatch first so that sync
        overlaps device execution."""
        if batch.kind == "decode":
            return self._issue_decode(batch)
        return self._issue_prefill(batch)

    def execute(self, batch: ScheduledBatch, step_counter: int):
        """Synchronous issue+fetch; returns (token_lists, logprob_lists):
        per-sequence NEW token lists (empty for a non-final prefill chunk,
        whose sampled token is never fetched) and, when any row requested
        logprobs, per-sequence aligned (chosen_lp, top-k) entry lists
        (None otherwise — the default path fetches nothing extra)."""
        return self.execute_async(batch, step_counter).fetch()

    # -------------------------------------------------------------- embedding
    @functools.cached_property
    def _embed_jit(self):
        """Mean-pooled, L2-normalized final hidden states (no KV pool touch).

        Serves /v1/embeddings and /v1/rerank (the reference router proxies
        both — src/vllm_router/app.py routes — to engines; here the engine
        itself provides them from the causal LM trunk)."""

        def embed(params, token_ids, lens):
            b, t = token_ids.shape
            positions = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None, :], (b, t)
            )
            hidden, _, _ = self._forward(
                params, self.model_config, token_ids, positions, lens,
            )
            mask = (jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None])
            maskf = mask.astype(jnp.float32)[:, :, None]
            denom = jnp.maximum(lens[:, None].astype(jnp.float32), 1.0)
            pooled = (hidden.astype(jnp.float32) * maskf).sum(1) / denom
            norm = jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
            )
            return pooled / norm

        return jax.jit(embed)

    def embed(self, token_lists: List[List[int]]) -> np.ndarray:
        """[n, hidden] float32 embeddings for tokenized inputs. Inputs beyond
        max_num_seqs are processed in chunks."""
        cap = max(1, self.config.max_num_seqs)
        outs = []
        for ofs in range(0, len(token_lists), cap):
            chunk = token_lists[ofs:ofs + cap]
            n = len(chunk)
            b = _bucket(n, 1, cap)
            # hi must itself be a power of two: a non-pow2 max_model_len
            # (e.g. 3000) would clamp t to a non-multiple of QBLOCK and trip
            # window_attention's chunking assert.
            hi = 16
            while hi < self.config.max_model_len:
                hi *= 2
            t = _bucket(max((len(x) for x in chunk), default=1), 16, hi)
            token_ids = np.zeros((b, t), np.int32)
            lens = np.zeros((b,), np.int32)
            for i, toks in enumerate(chunk):
                toks = toks[:t]
                token_ids[i, :len(toks)] = toks
                lens[i] = len(toks)
            out = self._embed_jit(
                self.params, jnp.asarray(token_ids), jnp.asarray(lens)
            )
            outs.append(np.asarray(out)[:n])
        return np.concatenate(outs, axis=0)

    # ------------------------------------------------------------ KV offload
    @functools.cached_property
    def _gather_blocks_jit(self):
        bs = self.config.block_size

        def gather(kv_k, kv_v, blocks):
            # Block-indexed: each gathered element is a contiguous bs*Dh run
            # (slot-row gathers measured ~2 GB/s on a v5e — r3 profiling).
            nl, hkv, ns, dh = kv_k.shape
            kr = kv_k.reshape(nl, hkv, ns // bs, bs, dh)
            vr = kv_v.reshape(nl, hkv, ns // bs, bs, dh)
            return kr[:, :, blocks], vr[:, :, blocks]  # [L, Hkv, n, bs, Dh]
        return jax.jit(gather)

    @functools.cached_property
    def _gather_scales_jit(self):
        bs = self.config.block_size

        def gather(kv_ks, kv_vs, blocks):
            nl, hkv, ns = kv_ks.shape
            kr = kv_ks.reshape(nl, hkv, ns // bs, bs)
            vr = kv_vs.reshape(nl, hkv, ns // bs, bs)
            return kr[:, :, blocks], vr[:, :, blocks]    # [L, Hkv, n, bs]
        return jax.jit(gather)

    @functools.cached_property
    def _scatter_blocks_jit(self):
        bs = self.config.block_size

        def scatter(kv_k, kv_v, blocks, k_new, v_new):
            # Whole blocks ([L, Hkv, n, bs, ...] payload or scales), in
            # place (ops/kv_write.py); padding lands in the null block.
            n = blocks.shape[0]
            return write_slabs(
                (kv_k, kv_v), (k_new, v_new),
                dst_start=blocks * bs,
                src_row=jnp.arange(n, dtype=jnp.int32),
                src_start=jnp.zeros((n,), jnp.int32),
                width=bs,
            )
        return jax.jit(scatter, donate_argnums=(0, 1))

    def read_blocks(self, block_ids: List[int]):
        """Device->host read of whole KV blocks.

        Returns (k, v, k_scale, v_scale) numpy arrays: payload
        [n, L, Hkv, bs, Dh] in the pool's storage dtype, plus per-slot
        scales [n, L, Hkv, bs] when the KV cache is quantized (None
        otherwise) — offloaded/handed-off blocks stay int8 on the wire.
        May raise RuntimeError if a concurrent step donated the pool
        buffers mid-read (the offload spiller retries against the rebound
        arrays).
        """
        n = len(block_ids)
        nb = _bucket(n, 1, max(1, self.num_kv_blocks))
        blocks = np.zeros((nb,), np.int32)  # padding -> null block
        blocks[:n] = block_ids
        k_g, v_g = self._gather_blocks_jit(
            self.kv_k, self.kv_v, jnp.asarray(blocks)
        )
        k_np = np.asarray(k_g).transpose(2, 0, 1, 3, 4)[:n]  # [n,L,Hkv,bs,Dh]
        v_np = np.asarray(v_g).transpose(2, 0, 1, 3, 4)[:n]
        if not self.kv_quantized:
            return k_np, v_np, None, None
        ks_g, vs_g = self._gather_scales_jit(
            self.kv_k_scale, self.kv_v_scale, jnp.asarray(blocks)
        )
        ks_np = np.asarray(ks_g).transpose(2, 0, 1, 3)[:n]   # [n,L,Hkv,bs]
        vs_np = np.asarray(vs_g).transpose(2, 0, 1, 3)[:n]
        return k_np, v_np, ks_np, vs_np

    def read_blocks_retry(self, block_ids: List[int], attempts: int = 3):
        """read_blocks with retry against donation races: an engine step may
        donate the pool buffers mid-read (RuntimeError on TPU, ValueError
        INVALID_ARGUMENT on the CPU backend); the retry re-reads the
        rebound arrays. The ONE helper shared by the offload spiller and
        the disagg handoff publisher."""
        for attempt in range(attempts):
            try:
                return self.read_blocks(block_ids)
            except (RuntimeError, ValueError):
                if attempt == attempts - 1:
                    raise
                time.sleep(0.01)

    def write_blocks(self, block_ids: List[int], k_np, v_np,
                     k_scale=None, v_scale=None) -> None:
        """Host->device restore of whole KV blocks.

        k_np/v_np: [n, L, Hkv, bs, Dh] in the pool's storage dtype;
        quantized pools additionally require the per-slot scales
        [n, L, Hkv, bs] (an offloaded/handed-off int8 block restores
        bit-identically — no requantization). Runs on the engine loop
        between steps, so the donated update is ordered with model
        dispatches.
        """
        if self.kv_quantized and k_scale is None:
            raise ValueError(
                "restoring into an int8 KV pool requires per-slot scales "
                "(blob written by a kv_cache_dtype=bfloat16 engine?)"
            )
        n = len(block_ids)
        nb = _bucket(n, 1, max(1, self.num_kv_blocks))
        if nb != n:
            pad = np.zeros((nb - n,) + k_np.shape[1:], k_np.dtype)
            k_np = np.concatenate([k_np, pad])
            v_np = np.concatenate([v_np, pad])
        blocks = np.zeros((nb,), np.int32)  # padding -> null block
        blocks[:n] = block_ids
        # [nb, L, Hkv, bs, Dh] -> [L, Hkv, nb, bs, Dh]
        k_blk = k_np.transpose(1, 2, 0, 3, 4)
        v_blk = v_np.transpose(1, 2, 0, 3, 4)
        self.kv_k, self.kv_v = self._scatter_blocks_jit(
            self.kv_k, self.kv_v, jnp.asarray(blocks), jnp.asarray(k_blk),
            jnp.asarray(v_blk),
        )
        if self.kv_quantized:
            if nb != n:
                spad = np.zeros((nb - n,) + k_scale.shape[1:], k_scale.dtype)
                k_scale = np.concatenate([k_scale, spad])
                v_scale = np.concatenate([v_scale, spad])
            ks_blk = k_scale.transpose(1, 2, 0, 3)   # [L, Hkv, nb, bs]
            vs_blk = v_scale.transpose(1, 2, 0, 3)
            self.kv_k_scale, self.kv_v_scale = self._scatter_blocks_jit(
                self.kv_k_scale, self.kv_v_scale, jnp.asarray(blocks),
                jnp.asarray(ks_blk), jnp.asarray(vs_blk),
            )
            self.kv_quant_tokens_written += n * self.config.block_size
        self._win_cache = None  # pool changed outside a decode dispatch

    # ------------------------------------------------------------- maintenance
    def reachable_decode_families(self):
        """Every (b, mb, K, use_cached_window) decode family the scheduler
        can dispatch under this config. The quantized shape rules
        (_decode_mb, scheduler.decode_step_cap + the interactive-first-
        dispatch cap, pinned num_steps) exist precisely so this set is
        small enough to enumerate — warmup compiles it EXACTLY, and the
        zero-compile-after-warmup test (tests/test_warmup_coverage.py)
        fails if a dispatch ever escapes it (VERDICT r4 weak #1/#7)."""
        from production_stack_tpu.engine.scheduler import (
            INTERACTIVE_DECODE_STEPS,
            decode_step_cap,
        )

        cfg = self.config
        b_max = _bucket(cfg.max_num_seqs, 1, max(1, cfg.max_num_seqs))
        full_mb = _bucket(cfg.max_blocks_per_seq, 1,
                          max(1, cfg.max_blocks_per_seq))
        if self.attn_impl == "paged":
            mbs = [full_mb]
            cached_variants = (False,)
        else:
            mbs = sorted({
                window_mb_bucket(m, cfg.max_blocks_per_seq)
                for m in (1, full_mb // 4, full_mb // 2, full_mb)
            })
            cached_variants = (False, True)
        fams = set()
        # The row buckets ``_bucket`` can give: the powers of two below
        # ``b_max`` and ``b_max`` itself, which need not be one
        # (``--max-num-seqs 48``: a train of 33 to 48 rows runs in the
        # bucket of 48).
        buckets = [1 << i for i in range(b_max.bit_length())
                   if 1 << i < b_max] + [b_max]
        for nb in buckets:
            # Tier bounds can land mid-bucket (counts 1..nb share bucket
            # nb), so both endpoints' caps are warmed; the interactive cap
            # makes (nb, INTERACTIVE) reachable at every row bucket.
            ks = {
                decode_step_cap(nb, cfg.num_decode_steps),
                decode_step_cap(nb // 2 + 1, cfg.num_decode_steps),
                min(INTERACTIVE_DECODE_STEPS,
                    decode_step_cap(nb, cfg.num_decode_steps)),
            }
            for mb in mbs:
                if self.attn_impl != "paged" and \
                        nb * mb > self.decode_window_blocks:
                    continue  # scheduler's window budget never emits it
                for dk in ks:
                    for cached in cached_variants:
                        fams.add((nb, mb, dk, cached))
        return sorted(fams)

    def reachable_prefill_families(self):
        """Every (b, t, mb, has_window) prefill family reachable under this
        config (see reachable_decode_families). Where the history is read
        in place (``prefill_reads_pool``) a window is no property of the
        program: ONE family a (rows, t), and where the dispatches are
        packed rows (``prefill_packs``) rows is 1; where it is gathered,
        the family without a window and the windowed ladder (or its pinned
        width)."""
        cfg = self.config
        full_mb = _bucket(cfg.max_blocks_per_seq, 1,
                          max(1, cfg.max_blocks_per_seq))
        win_mbs = sorted({
            window_mb_bucket(m, cfg.max_blocks_per_seq)
            for m in (1, full_mb // 4, full_mb // 2, full_mb)
        })

        def windowed(pb):
            if self.prefill_reads_pool:
                return ()
            return [full_mb] if self._pins_prefill_window(pb, full_mb) \
                else win_mbs

        fams = set()
        # Exactly the rectangles a dispatch can run: admission chooses
        # among them and the runner issues what prefill_rectangle says.
        for pb, t in prefill_rectangles(cfg, self.prefill_packs):
            fams.add((pb, t, full_mb, False))
            for mb in windowed(pb):
                if pb * mb <= self.prefill_window_blocks:
                    fams.add((pb, t, mb, True))
        return sorted(fams)

    def _abstract_params(self):
        """The weights as ShapeDtypeStructs with their shardings: what a
        dispatch program is lowered against when nothing may run."""
        mc = self.model_config
        abstract = jax.eval_shape(
            lambda: self._init_fn(mc, jax.random.PRNGKey(0), self.dtype)
        )
        return jax.tree.map(
            lambda leaf, sh: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sh
            ),
            abstract, param_shardings(mc, self.mesh, abstract),
        )

    def _lower_decode(self, aparams, db, mb, dk, cached, *,
                      has_penalties=False, logprobs_k=0):
        """One decode family lowered against abstract weights, argument
        for argument what _issue_decode passes (the pools only lend their
        shape and sharding: lowering reads no buffer, so a dispatch in
        flight may have donated them)."""
        mc, bs = self.model_config, self.config.block_size
        nl, hkv, dh = self.kv_spec
        sds = jax.ShapeDtypeStruct
        if cached:
            # Cached-window variants receive windows that are COMMITTED
            # outputs of the previous dispatch; an unsharded abstract
            # window lowers to a different module (the committed/
            # uncommitted cache-key split) and would compile artifacts
            # the execute pass never loads — measured: 27/63 mismatches
            # on CPU without this. At tp>1 the real window sharding may
            # differ from replicated; the prepass is opportunistic there
            # (a mismatch costs extra compiles, never correctness).
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self.mesh, PartitionSpec())
            wk = sds((nl, hkv, db, mb * bs, dh), self.dtype, sharding=rep)
            wv = sds((nl, hkv, db, mb * bs, self.kv_v_dim), self.dtype,
                     sharding=rep)
        else:
            wk = wv = sds((1, 1, 1, 1, 1), self.dtype)
        counts = sds(
            (db, mc.vocab_size) if has_penalties else (1, 1), jnp.int32
        )
        return self._decode.lower(
            aparams, sds((NUM_SCALARS * db + db * mb,), jnp.int32),
            self.kv_k, self.kv_v, *self._scale_pool_args(), wk, wv,
            counts, self._zero_last, *self._spec_pool_args(),
            self.state_pools,
            b=db, mb=mb, num_steps=dk, use_cached_window=cached,
            has_penalties=has_penalties, logprobs_k=logprobs_k,
        )

    def _lower_prefill(self, aparams, pb, t, mb, has_window, *,
                       has_penalties=False, logprobs_k=0):
        """One prefill family lowered like _lower_decode."""
        mc = self.model_config
        sds = jax.ShapeDtypeStruct
        length, seqs, shape = self._prefill_program_shape(
            pb, t, mb, has_window)
        counts = sds(
            (seqs, mc.vocab_size) if has_penalties else (1, 1), jnp.int32
        )
        return self._prefill.lower(
            aparams, sds((length,), jnp.int32),
            self.kv_k, self.kv_v, *self._scale_pool_args(), counts,
            *self._spec_pool_args(), self.state_pools,
            **shape, has_penalties=has_penalties, logprobs_k=logprobs_k,
        )

    def _prefill_program_shape(self, pb, t, mb, has_window):
        """Of one prefill family: (the length of its ``packed`` operand,
        the sequences it has scalars for, its static shape arguments): as
        ``_issue_prefill`` builds them."""
        segs = self._prefill_segs
        seqs = segs or pb
        return NUM_SCALARS * seqs + seqs * mb + pb * t, seqs, dict(
            b=pb, t=t, mb=mb, has_window=has_window, b_max=self._b_max,
            segs=segs)

    def audit_pool_programs(self) -> List[Dict]:
        """Compile one program of each kind this engine dispatches (the
        widest decode family, cached-window too where that exists, and
        the widest prefill family with and without a history window) and
        report what each does to the KV pools: ``pool_copies`` — ``copy``
        operations whose result has a pool's shape, or the shape of the
        rows' state a decode program carries through its loops (0: the
        donated pools are updated in place, ops/kv_write.py, and so is the
        carried state, ops/gated_delta.py) — and the program's temporaries
        beside one payload pool's bytes; for a decode program of a model
        with recurrent state, ``gdn_step`` / ``ssd_step``: which execution
        of that recurrence's step it holds (``"pallas"`` / ``"xla"``), for
        one of a model with window rings ``ring_step`` likewise, and
        for a prefill program of a Gated DeltaNet model ``gdn_chunk``
        likewise (the chunkwise form), and ``short_conv`` on every line of
        a model of gated short convolutions; for a
        prefill program, ``prefill_attn``: which execution of the chunk's
        attention (``"pallas"``: the flash kernel over the pool /
        ``"xla"``: ``window_attention`` over gathered keys); for every
        program, ``hc_mult`` and ``hc_mix`` (``residual_report``). Nothing
        runs; with a compile cache the programs are the ones warmup left
        there."""
        pools = [self.kv_k] + [
            x for x in (*self._scale_pool_args(),
                        *self._spec_pool_args()[1:3]) if x.size > 1
        ] + list(self.state_pools)
        aparams = self._abstract_params()
        programs = []
        decode = self.reachable_decode_families()
        for cached in sorted({f[3] for f in decode}):
            fam = [f for f in decode if f[3] == cached][-1]
            programs.append(("decode", fam,
                             self._lower_decode(aparams, *fam)))
        prefill = self.reachable_prefill_families()
        for has_window in sorted({f[3] for f in prefill}):
            fam = [f for f in prefill if f[3] == has_window][-1]
            programs.append(("prefill", fam,
                             self._lower_prefill(aparams, *fam)))
        out = []
        for kind, fam, lowered in programs:
            compiled = lowered.compile()
            analysis = analysis_of(compiled)
            program = self.program(kind, fam)
            self.memory.analysed(program, analysis)
            text = compiled.as_text()
            carried = [jax.ShapeDtypeStruct((fam[0], *x.shape[1:]), x.dtype)
                       for x in self.state_pools] if kind == "decode" else []
            out.append({
                "program": kind, "family": list(fam),
                "pool_copies": len(pool_copies(text, pools + carried)),
                # temp_bytes, alias_bytes, ... (memory_analysis), and
                # beside them what the allocator gave the family's
                # dispatch where the memory ledger has measured it.
                **analysis,
                **{k: v for k, v in self.memory.programs[program["key"]]
                   .items() if k in ("held_bytes", "in_company")},
                "pool_bytes": int(self.kv_k.size * self.kv_k.dtype.itemsize),
                "state_pool_bytes": self.state_pool_bytes,
            })
            for name, path in (("gdn_step", gated_delta.step_path(text)),
                               ("gdn_chunk", gated_delta.chunk_path(text)),
                               ("ssd_step", ssd.step_path(text)),
                               ("ring_step", ring_step_path(text)),
                               ("s6_chunk",
                                selective_scan.chunk_path(text)),
                               ("short_conv",
                                gated_delta.short_conv_path(text))):
                if path:
                    out[-1][name] = path
            out[-1].update(self.residual_report())
            out[-1].update(self.span_report())
            out[-1].update(self.ring_report())
            if kind == "prefill":
                out[-1]["prefill_attn"] = prefill_attn_path(text)
                out[-1]["prefill_reads_pool"] = self.prefill_reads_pool
        return out

    def analyse_programs(self, keys) -> None:
        """Attach ``memory_analysis()`` to these programs of the memory
        ledger that lack it (``GET /debug/memory?analyze=1``: the families
        its events name). Lowers and compiles each in the caller's thread;
        with a compile cache the program is the one warm-up left there. A
        program of the plain decode body under speculation
        (``spec_on`` false) has no lowering of its own here and is left."""
        keys = set(keys)
        wanted = [(k, p) for k, p in self.memory.snapshot()["programs"].items()
                  if k in keys and "temp_bytes" not in p and p["spec_on"]]
        if not wanted:
            return
        aparams = self._abstract_params()
        for key, program in wanted:
            *shape, flag = program["family"]
            compiled = self._lowering(program["kind"])(
                aparams, *shape, bool(flag),
                has_penalties=program["has_penalties"],
                logprobs_k=program["logprobs_k"]).compile()
            self.memory.analysed({"key": key}, analysis_of(compiled))

    def _lowering(self, kind: str):
        """``_lower_decode`` or ``_lower_prefill``, by a program's kind."""
        return self._lower_decode if kind == "decode" else self._lower_prefill

    def residual_report(self) -> Dict:
        """How many streams the model's residual is (``hc_mult``) and,
        where more than one, what computes their mix (``hc_mix``:
        ops/hyper_connections.py has one execution, ``xla``)."""
        from production_stack_tpu.ops.hyper_connections import EXECUTION

        streams = self.model_config.hc_mult
        return {"hc_mult": streams,
                **({"hc_mix": EXECUTION} if streams > 1 else {})}

    @functools.cached_property
    def layer_spans(self):
        """Per layer, the keys a query sees up to itself (ops/attention.py:
        a span; NO_SPAN where the layer has none) of a model that bounds
        some layer's attention; None for every other."""
        model = get_model(self.model_config)
        bounded = getattr(model, "bounded_layers", None)
        return model.spans(self.model_config) \
            if bounded is not None and bounded(self.model_config) else None

    def span_report(self) -> Dict:
        """Of a model that bounds some layer's attention: which layers
        (``span_layers``) and by how many keys (``span``); else nothing."""
        if self.layer_spans is None:
            return {}
        bounded = [i for i, s in enumerate(self.layer_spans) if s != NO_SPAN]
        return {"span_layers": bounded,
                "span": int(self.layer_spans[bounded[0]])}

    def ring_report(self) -> Dict:
        """Of a model that keeps some layers' keys and values as a
        per-sequence window ring in its state slots, or holds a share of
        its experts: what its module says of them (``ring_report``: the
        window layers, the ring's shape, the experts held); else nothing."""
        report = getattr(get_model(self.model_config), "ring_report", None)
        return report(self.model_config) if report is not None else {}

    @functools.cached_property
    def ring_layers(self) -> int:
        """Layers that keep a window ring (0: the model has none)."""
        return len(self.ring_report().get("window_layers", ()))

    def _warmup_compile_prepass(self) -> int:
        """Compile-only AOT pass over every reachable shape family using
        ABSTRACT weights (jax.ShapeDtypeStruct), so XLA compilation — the
        CPU-bound half of startup — overlaps the background checkpoint
        read (docs/ELASTIC.md). Fills the persistent cache on a cold boot
        (classifying each variant as cache hit/miss); the execute pass in
        warmup() then pays only a retrace + persistent-cache load per
        family. Never runs with speculative decoding (weight deferral is
        disabled there).

        ADAPTIVE: the prepass only pays for itself while there is idle
        host time to fill, so it stops early (a) the moment the weight
        loader finishes — the execute pass compiles the rest with nothing
        left to overlap — and (b) after a few consecutive persistent-cache
        hits, which means a previous boot already populated the cache and
        the execute pass will deserialize everything anyway (measured: a
        full prepass on a warm cache DOUBLED warm-boot time). Returns the
        number of variants covered, in enumeration order, so warmup()'s
        execute pass counts hit/miss only for the variants this pass did
        not."""
        from production_stack_tpu.utils import prefill_t_floor as _t_floor

        cfg = self.config
        count_dir = self.compilation_cache_path
        aparams = self._abstract_params()

        n = 0
        consecutive_hits = 0
        # A warm cache makes the prepass pure overhead: after this many
        # consecutive hits, trust the cache and let the execute pass
        # deserialize directly.
        warm_bail = 4

        class _PrepassDone(Exception):
            pass

        # Progress is mirrored onto the runner as it happens: if the
        # prepass dies mid-way, warmup() must still know how many
        # variants were classified (and persistently cached) so the
        # execute pass neither double-counts them nor mistakes the
        # prepass's own fresh artifacts for warm-boot hits.
        self._prepass_progress = 0

        def compile_counted(kind, *family, **variant):
            nonlocal n, consecutive_hits
            if self.weights_ready or consecutive_hits >= warm_bail:
                raise _PrepassDone()
            before = _cache_entries(count_dir)
            compiled = self._lowering(kind)(
                aparams, *family, **variant).compile()
            # A compiled program is at hand: the memory ledger takes its
            # analysis (the execute pass holds none).
            self.memory.analysed(self.program(kind, family, **variant),
                                 analysis_of(compiled))
            after = _cache_entries(count_dir)
            if before is not None and after is not None:
                if after - before:
                    self.startup_cache_miss_families += 1
                    consecutive_hits = 0
                else:
                    self.startup_cache_hit_families += 1
                    consecutive_hits += 1
            n += 1
            self._prepass_progress = n

        variants = ((False, 0), (False, LOGPROB_BUCKETS[0]), (True, 0))
        try:
            for db, mb, dk, cached in self.reachable_decode_families():
                dvariants = variants if db == 1 else variants[:2]
                for pen, lpk in dvariants:
                    compile_counted(
                        "decode", db, mb, dk, cached,
                        has_penalties=pen, logprobs_k=lpk,
                    )
            t_floor = _t_floor(cfg.max_num_batched_tokens)
            for pb, t, mb, has_window in self.reachable_prefill_families():
                if pb == 1:
                    pvariants = (
                        variants if t == t_floor and not has_window
                        else (variants[0], variants[1])
                    )
                else:
                    pvariants = variants[:1]
                for pen, lpk in pvariants:
                    compile_counted(
                        "prefill", pb, t, mb, has_window,
                        has_penalties=pen, logprobs_k=lpk,
                    )
        except _PrepassDone:
            logger.info(
                "AOT compile prepass stopping early after %d variants "
                "(%s)", n,
                "weights ready" if self.weights_ready
                else "persistent cache is warm",
            )
        logger.info(
            "AOT compile prepass: %d variants lowered+compiled while "
            "weights load (persistent cache: %d hit / %d miss)",
            n, self.startup_cache_hit_families,
            self.startup_cache_miss_families,
        )
        return n

    def _program_store(self) -> Optional[ProgramStore]:
        """The program store of THIS exact boot (None without a persistent
        cache): its key names the warm-up manifest and every stored
        program (engine/program_store.py). The manifest is written only
        after a FULLY successful warm-up of every variant, and a warm boot
        under it LOADS its programs and traces none, so the key holds all
        that a trace would have noticed: what shapes the lowered modules
        (model and its configuration, dtypes, mesh and its devices, pool
        geometry, loop construct, the complete reachable family
        enumeration) and what turns them into executables (a digest of
        this package's source, the jax, jaxlib and backend versions, the
        latter libtpu's build on a TPU, the device kind, the XLA and
        libtpu flags in the environment). A key that differs in anything
        names another manifest, and the boot warms cold."""
        if not self.compilation_cache_path:
            return None
        import os

        import jaxlib

        cfg = self.config
        device = self.mesh.devices.flat[0]
        return ProgramStore(self.compilation_cache_path, {
            "model": cfg.model, "dtype": cfg.dtype,
            "model_config": repr(self.model_config),
            "kv_cache_dtype": cfg.kv_cache_dtype,
            "block_size": cfg.block_size,
            "num_kv_blocks": self.num_kv_blocks,
            "attn": self.attn_impl, "decode_loop": cfg.decode_loop,
            "mesh": sorted(dict(self.mesh.shape).items()),
            "devices": [d.id for d in self.mesh.devices.flat],
            "b_max": self._b_max,
            "max_model_len": cfg.max_model_len,
            "max_num_batched_tokens": cfg.max_num_batched_tokens,
            "max_prefill_seqs": prefill_row_cap(cfg),
            "spec": cfg.speculative_num_tokens,
            "spec_model": cfg.speculative_model,
            "spec_ring": self.spec_ring_len,
            "spec_adaptive": cfg.speculative_adaptive,
            "spec_tree": cfg.speculative_tree_width,
            "lora": sorted(cfg.lora_modules),
            "logprob_buckets": LOGPROB_BUCKETS,
            "decode_families": self.reachable_decode_families(),
            "prefill_families": self.reachable_prefill_families(),
            "source": source_digest(),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": device.client.platform_version,
            "device_kind": device.device_kind,
            "flags": [os.environ.get(k, "")
                      for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")],
        })

    def warmup(self) -> None:
        """Compile (or load) AND execute every reachable shape family before
        serving.

        Each family is run once through the very callable serving will
        dispatch it through (``_dispatch``): a loaded executable of the
        program table, or the jitted function itself (not a
        jit.lower().compile() thrown away, which fills the persistent XLA
        cache but NOT the in-process pjit dispatch cache — the first real
        call would still pay a full retrace + cache load inside the
        serving path). The dummy
        inputs are all-zero: a decode with per-row budget 0 and a prefill
        with chunk_lens 0 keep no token, so their trailing pool write
        (ops/kv_write.py) rewrites the null block with its own content.
        The donated KV pool buffers are rebound from the dispatch outputs,
        so pool contents survive warmup untouched.

        Sampling-variant coverage contract (a mid-serving compile stalls
        the single dispatch executor, so the variants co-batched traffic
        can pull in are warmed; the rest pay a ONE-TIME persistent-cached
        compile on first use — advisor r4 low #4, r5 review):
          * default (no logprobs/penalties): every family;
          * logprobs: every decode family and every single-row prefill
            family (any chat+logprobs request reaches these);
          * penalties: the interactive families only (b=1 decode, the
            floor-width single-row prefill);
          * multi-row prefill with variants, penalty+logprobs combos:
            first-use compile, persistent-cached thereafter.
        With the persistent compilation cache
        (config.compilation_cache_dir) all of this is paid once per
        machine, not once per process, and what a boot finds on disk
        chooses its path (docs/ELASTIC.md fast-start; no option):
          * COLD (no manifest under this boot's key, ``_program_store``):
            every variant above is lowered from the very arguments it is
            then run on, compiled (a persistent-cache HIT where no new
            cache artifact appeared, else a MISS), STORED as its
            serialized executable beside the cache, and run and served
            through that one ``Compiled``. When all ran, the manifest is
            written: the list of the programs stored.
          * WARM (manifest-verified): the default variant of every family
            is LOADED from its stored executable and run once on the zero
            inputs; nothing of it is traced or lowered. The logprobs and
            penalty variants are deferred: their first use loads the
            stored executable (``_dispatch``). A loaded program counts as
            a hit and in ``startup_loaded_families``.
          * A stored file that is missing, short or refused by the backend
            (or a loaded program that refuses its arguments) sends THAT
            program down the traced path, counts as a miss, and drops the
            manifest, so that the next boot stores everything again; so
            does a manifest-verified boot that compiled anything.
        A program the store cannot hold (one that closes over device
        arrays: LoRA stacks) and every program warm-up does not enumerate
        go through the jitted function as they always did, and so does
        every program of a process without a cache directory.
        The phase durations land in startup_{compile,warmup}_seconds, and
        warm-up's own seconds split into loading, tracing + lowering +
        compiling, and executing in its log line.

        With overlapped weight loading (config.overlap_weight_load) a
        compile-only PREPASS lowers+compiles every family against abstract
        weights while the loader thread reads the checkpoint — the
        IO-bound and CPU-bound halves of startup pipeline instead of
        serializing — and the execute pass below then pays only a retrace
        + persistent-cache load per family.

        Cost note: under the default decode_loop="scan" (engine/config.py)
        each dummy decode family executes its full K forwards (~K * one
        decode step, a few hundred ms per family on large models) — a
        startup-time cost only. Under "while" the dummy executions run ZERO
        loop iterations (budget 0).
        """
        import os as _os
        import time as _time

        cfg = self.config
        mc = self.model_config
        # Warmup manifest (docs/ELASTIC.md): a previous FULLY successful
        # warmup under this exact key stored every program it names, so
        # this boot LOADS the DEFAULT (no-logprobs/no-penalties) variants
        # and defers the others to a first-use load. A key that differs in
        # anything names another manifest and warms cold.
        store = self._program_store()
        # Programs that close over device arrays bake them in: no file
        # could stand for them (the LoRA stacks; ``serialize`` refuses).
        self._store = store if not self.lora_stacks else None
        stored = store.manifest() if store is not None else None
        warm_verified = stored is not None
        self._stored = stored if warm_verified and self._store else frozenset()
        # Stored once and gone since (a pruned directory): each takes the
        # traced path and counts as a miss, a deferred variant too, whose
        # first use would else find it out inside serving.
        lost = {k for k in self._stored
                if not _os.path.exists(store.path(k))}
        self._stored -= lost
        ran = set()
        self.startup_deferred_families = 0
        prepassed = 0
        if warm_verified:
            logger.info(
                "Warmup manifest present (%s): loading %d stored programs, "
                "non-default sampling variants at their first use",
                _os.path.basename(store.manifest_path), len(self._stored),
            )
        elif self._params is None and self._param_thread is not None:
            tc = _time.monotonic()
            try:
                prepassed = self._warmup_compile_prepass()
            except Exception:  # noqa: BLE001 — prepass is opportunistic
                self.startup_warmup_failures += 1
                logger.exception(
                    "AOT compile prepass failed; the execute pass below "
                    "compiles serially (startup still correct, just slower)"
                )
                # The variants the prepass DID cover are already
                # classified (and their artifacts written): the execute
                # pass must skip counting exactly those, or a cold boot's
                # prepass-written artifacts would re-count as hits.
                prepassed = getattr(self, "_prepass_progress", 0)
            self.startup_compile_seconds = _time.monotonic() - tc
        # Join the weight loader OUTSIDE the warmup try: a broken
        # checkpoint must fail startup exactly like the serial path did,
        # not degrade into "warmup failed (continuing)".
        self.wait_for_weights()
        t0 = _time.monotonic()
        count_dir = self.compilation_cache_path
        call_idx = 0
        saved = set()
        spent = {"load": 0.0, "trace": 0.0, "execute": 0.0}

        def timed(phase, fn, *args, **kwargs):
            t = _time.monotonic()
            try:
                return fn(*args, **kwargs)
            finally:
                spent[phase] += _time.monotonic() - t

        def counted(fn, *args, **kwargs):
            """Run one compiling call, classifying it as a persistent-cache
            hit or miss by whether a new cache artifact appeared. The
            first ``prepassed`` calls were already classified by the
            prepass (same enumeration order) — re-counting them here
            would double-book, and its freshly written artifacts would
            masquerade as hits."""
            if count_dir is None or call_idx <= prepassed:
                return fn(*args, **kwargs)
            before = _cache_entries(count_dir)
            out = fn(*args, **kwargs)
            after = _cache_entries(count_dir)
            if before is not None and after is not None:
                if after - before:
                    self.startup_cache_miss_families += 1
                else:
                    self.startup_cache_hit_families += 1
            return out

        def traced(kind, args, static):
            # One call traces, lowers, compiles (or finds) and enqueues.
            return timed("trace", counted, self._jitted(kind), *args,
                         **static)

        def compile_and_store(kind, key, args, static):
            # Lowered from the very arguments it is then run on: what a
            # jitted call would have traced, committed shardings and all.
            before = _cache_entries(count_dir)
            compiled = timed(
                "trace",
                lambda: self._jitted(kind).lower(*args, **static).compile())
            after = _cache_entries(count_dir)
            fresh = before is None or after is None or bool(after - before)
            if call_idx > prepassed:   # the prepass classified the first
                if fresh:
                    self.startup_cache_miss_families += 1
                else:
                    self.startup_cache_hit_families += 1
            if fresh or self._stores_loaded:
                try:
                    self._store.save(key, compiled)
                    saved.add(key)
                except Exception:  # noqa: BLE001 — the next boot traces it
                    logger.warning("Program %s was not stored", key,
                                   exc_info=True)
            self._programs[key] = compiled
            return timed("execute", compiled, *args)

        def run(kind, program, args, static):
            """One variant, once, on its zero inputs. Cold: compiled here,
            stored, and run through that ``Compiled``. Warm: through its
            stored executable. Through the jitted function where there is
            no store, where no boot could store this program, or where
            the store fails it (a miss)."""
            nonlocal call_idx
            call_idx += 1
            key = program["key"]
            ran.add(key)
            if self._store is None:
                return traced(kind, args, static)
            if not warm_verified:
                return compile_and_store(kind, key, args, static)
            if key not in stored:
                return traced(kind, args, static)
            loaded = timed("load", self._load_program, key) \
                if key in self._stored else None
            if loaded is not None:
                try:
                    out = timed("execute", loaded, *args)
                    self.startup_cache_hit_families += 1
                    self.startup_loaded_families += 1
                    return out
                except Exception:  # noqa: BLE001 — refused: trace it
                    # Arguments are checked before anything runs: the
                    # donated pools are whole.
                    logger.exception(
                        "Stored program %s refused its arguments: it is "
                        "traced, and the manifest dropped", key)
                    self._forget_program(key)
            # Stored once and lost since: a miss, whatever JAX's cache
            # still holds of it.
            self.startup_cache_miss_families += 1
            return timed("trace", self._jitted(kind), *args, **static)

        # Where the devices report their memory the ledger reads the
        # allocator right before and right after each family's enqueue:
        # the difference is what the count shows of the program (its code
        # and outputs). No sync: a family still running changes neither
        # read (its temporaries are in no count; PERF.md section 6, PR 49),
        # and a sync a family cost a warm boot 2-3 s.
        reads = bool(self.memory.reading())
        clock = compile_clock()

        def measured(kind, program, rows, args, static):
            if not reads:
                return run(kind, program, args, static)
            self.memory.quiet()
            before = clock.reading()
            out = run(kind, program, args, static)
            self.memory.issued(n_warmed, program, rows, clock.since(before))
            self.memory.fetched(n_warmed)
            return out

        variants = ((False, 0), (False, LOGPROB_BUCKETS[0]), (True, 0))
        n_warmed = 0
        # Serving's cached-window dispatches receive window buffers that are
        # OUTPUTS of the previous dispatch (committed, concretely sharded);
        # fresh jnp.zeros are uncommitted and key a DIFFERENT pjit cache
        # entry. Warm the cached variants by chaining each family's fresh
        # variant's returned windows — the same producer/consumer shape as
        # serving. Keyed by (b, mb): the window shape depends on nothing
        # else.
        wins = {}
        try:
            for db, mb, dk, cached in self.reachable_decode_families():
                dvariants = variants if db == 1 else variants[:2]
                if warm_verified:
                    self.startup_deferred_families += len(dvariants) - 1
                    dvariants = variants[:1]
                # The adaptive controller's all-gamma=0 degrade dispatches
                # the spec_on=False static variant of every decode family
                # — warm it too or the first cold batch pays a mid-serving
                # compile (zero-compile-after-warmup contract).
                spec_modes = (
                    (True, False) if (self.spec_n and self.spec_adaptive)
                    else (True,)
                )
                for pen, lpk in dvariants:
                    for sp_on in spec_modes:
                        if cached:
                            wk, wv = wins[(db, mb)]
                        else:
                            wk = jnp.zeros((1, 1, 1, 1, 1), self.dtype)
                            wv = jnp.zeros((1, 1, 1, 1, 1), self.dtype)
                        counts = jnp.zeros(
                            (db, mc.vocab_size) if pen else (1, 1),
                            jnp.int32
                        )
                        kv_ks, kv_vs = self._scale_pool_args()
                        dparams, sp_k, sp_v, sp_p = self._spec_pool_args()
                        out = measured(
                            "decode",
                            self.program("decode", (db, mb, dk, cached),
                                         pen, lpk, sp_on), db,
                            (self.params,
                             jnp.zeros((NUM_SCALARS * db + db * mb,),
                                       jnp.int32),
                             self.kv_k, self.kv_v, kv_ks, kv_vs, wk, wv,
                             counts, self._zero_last, dparams, sp_k, sp_v,
                             sp_p, self.state_pools),
                            dict(b=db, mb=mb, num_steps=dk,
                                 use_cached_window=cached,
                                 has_penalties=pen, logprobs_k=lpk,
                                 spec_on=sp_on),
                        )
                        _, self.kv_k, self.kv_v = out[0], out[1], out[2]
                        self._rebind_scale_pools(out[3], out[4])
                        self._rebind_spec_pools(out[13], out[14], out[15])
                        self.state_pools = out[16]
                        if self.attn_impl != "paged":
                            # Both variants return the (appended/gathered)
                            # windows; the inputs were donated, so rebind.
                            wins[(db, mb)] = (out[5], out[6])
                        n_warmed += 1
            t_floor = prefill_t_floor(cfg.max_num_batched_tokens)
            for pb, t, mb, has_window in self.reachable_prefill_families():
                # Coverage contract (mirrors the docstring): logprobs
                # variants warm for every single-row prefill family (any
                # chat+logprobs prompt length/history hits one); penalties
                # only at the interactive floor family — they engage on
                # prefill only for preempted re-prefills, a rare path
                # whose other combinations pay a one-time
                # persistent-cached compile.
                if pb == 1:
                    pvariants = (
                        variants if t == t_floor and not has_window
                        else (variants[0], variants[1])
                    )
                else:
                    pvariants = variants[:1]
                if warm_verified:
                    self.startup_deferred_families += len(pvariants) - 1
                    pvariants = variants[:1]
                length, seqs, shape = self._prefill_program_shape(
                    pb, t, mb, has_window)
                for pen, lpk in pvariants:
                    counts = jnp.zeros(
                        (seqs, mc.vocab_size) if pen else (1, 1), jnp.int32
                    )
                    kv_ks, kv_vs = self._scale_pool_args()
                    dparams, sp_k, sp_v, sp_p = self._spec_pool_args()
                    out = measured(
                        "prefill",
                        self.program("prefill", (pb, t, mb, has_window),
                                     pen, lpk), seqs,
                        (self.params,
                         jnp.zeros((length,), jnp.int32),
                         self.kv_k, self.kv_v, kv_ks, kv_vs, counts,
                         dparams, sp_k, sp_v, sp_p, self.state_pools),
                        dict(**shape, has_penalties=pen, logprobs_k=lpk),
                    )
                    self.kv_k, self.kv_v = out[1], out[2]
                    self._rebind_scale_pools(out[3], out[4])
                    self._rebind_spec_pools(out[9], out[10], out[11])
                    self.state_pools = out[12]
                    n_warmed += 1
            if self.spec_n:
                # Draft catch-up (ingest) families: one per T bucket, so
                # a mid-serving cache-hit prompt never pays the compile.
                t_ing = 16
                t_max = max(16, 1 << (self.spec_ring_len - 1).bit_length())
                while t_ing <= t_max:
                    call_idx += 1
                    self.spec_k, self.spec_v, self.spec_pos = timed(
                        "trace", counted, self._spec_ingest_jit,
                        self.spec_params, self.spec_k, self.spec_v,
                        self.spec_pos, jnp.int32(0),
                        jnp.zeros((t_ing,), jnp.int32), jnp.int32(0),
                        jnp.int32(0), t=t_ing,
                    )
                    n_warmed += 1
                    t_ing *= 2
            # Warmup dispatches block-wait on the last output so compile
            # failures surface here, not mid-serving.
            timed("execute", jax.block_until_ready, self.kv_k)
            self.startup_cache_miss_families += len(lost - ran)
            if count_dir is None:
                # No persistent cache configured: every variant compiled
                # from scratch — an all-miss boot by definition.
                self.startup_cache_hit_families = 0
                self.startup_cache_miss_families = n_warmed
            logger.info(
                "Warmup: %d shape families run (attn=%s) in %.1fs: %d "
                "loaded from stored executables (load %.1fs), trace + "
                "lower + compile %.1fs, execute %.1fs (persistent cache: "
                "%d hit / %d miss; %d variants deferred to first-use "
                "loads)",
                n_warmed, self.attn_impl, _time.monotonic() - t0,
                self.startup_loaded_families, spent["load"],
                spent["trace"], spent["execute"],
                self.startup_cache_hit_families,
                self.startup_cache_miss_families,
                self.startup_deferred_families,
            )
            self.startup_warmup_seconds = _time.monotonic() - t0
            self.startup_warmed_families = n_warmed
            if store is not None:
                if not warm_verified and \
                        self.startup_cache_hit_families \
                        + self.startup_cache_miss_families > 0:
                    # Every variant is now persistently cached, and those
                    # the manifest lists are stored: later boots under
                    # this key load them and defer the non-default ones.
                    try:
                        store.write_manifest(saved)
                    except OSError:
                        logger.warning("Could not write warmup manifest",
                                       exc_info=True)
                elif warm_verified and self.startup_cache_miss_families:
                    # The cache or the store was pruned under the
                    # manifest: its proof no longer holds — drop it so the
                    # next boot re-warms (and stores) everything.
                    logger.warning(
                        "Warmup manifest was stale (%d misses on a "
                        "verified-warm boot); removing it",
                        self.startup_cache_miss_families,
                    )
                    store.drop_manifest()
        except Exception:  # noqa: BLE001 — warmup must never kill serving
            self.startup_warmup_failures += 1
            logger.exception("Warmup compilation failed (continuing)")
            self.startup_warmup_seconds = _time.monotonic() - t0
            self.startup_warmed_families = n_warmed
            # The dispatches DONATE the pool buffers (donate_argnums): a
            # failure between donation and rebinding would leave
            # self.kv_k/kv_v deleted and poison every later real dispatch.
            # Warmup runs before any KV exists, so rebuilding zeroed pools
            # loses nothing.
            try:
                deleted = self.kv_k.is_deleted() or self.kv_v.is_deleted()
                if self.kv_quantized and not deleted:
                    deleted = (self.kv_k_scale.is_deleted()
                               or self.kv_v_scale.is_deleted())
            except (RuntimeError, ValueError):  # donation race mid-probe
                # The observed donation-race pair (TPU RuntimeError / CPU
                # ValueError); an unprobeable pool is treated as consumed
                # and rebuilt — strictly safe, warmup runs before any KV.
                deleted = True
            if deleted:
                logger.warning(
                    "Rebuilding KV pool consumed by failed warmup"
                )
                self._alloc_kv_pools()
                self._alloc_state_pools()
            if self.spec_n:
                try:
                    spec_gone = (self.spec_k.is_deleted()
                                 or self.spec_pos.is_deleted())
                except (RuntimeError, ValueError):  # donation race mid-probe
                    spec_gone = True
                if spec_gone:
                    self._alloc_spec_pools()
