"""Compile the main path's kernels for a DESCRIBED TPU v5e (no chip attached):
the dense and shared family. The paged decode and prefill kernels, the pool
write, the sampler, the sharded decode kernel, the dense deployments'
programs, the pinned entry points of every Pallas kernel of the serving path,
and every configuration's decode program by its instruction count.
tests/chip_compile_helpers.py says how and why.
"""

import functools
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models.config import resolve_model_config
from production_stack_tpu.ops.pallas.paged_attention import (
    paged_flash_decode_stats,
    paged_flash_decode_stats_tp,
)
from production_stack_tpu.ops.quantization import SCALE_DTYPE
from production_stack_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP
from tests.chip_compile_helpers import (
    BATCH,
    BLOCK_SIZE,
    HYBRID_DIR,
    LAYERS,
    MAX_BLOCKS,
    NUM_SLOTS,
    _deployment_runner,
    _digest,
    _prefill_text_digest,
)
from tests.chip_compile_helpers import (  # noqa: F401  (fixtures)
    v5e,
)


def _kernel_args(model: str, pool: str, sharding_for):
    """ShapeDtypeStructs of one decode call at ``model``'s head shapes.
    ``sharding_for(kind)`` places each argument on described devices."""
    mc = resolve_model_config(model)
    h, hkv, dh = mc.num_heads, mc.num_kv_heads, mc.head_dim_

    def sds(shape, dtype, kind):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sharding_for(kind))

    store = jnp.int8 if pool == "int8" else jnp.bfloat16
    args = [
        sds((BATCH, h, dh), jnp.bfloat16, "q"),
        sds((LAYERS, hkv, NUM_SLOTS, dh), store, "pool"),
        sds((LAYERS, hkv, NUM_SLOTS, dh), store, "pool"),
        sds((BATCH, MAX_BLOCKS), jnp.int32, "rep"),
        sds((BATCH,), jnp.int32, "rep"),
        sds((1,), jnp.int32, "rep"),
    ]
    scales = {}
    if pool == "int8":
        scales = {
            "k_scale": sds((LAYERS, hkv, NUM_SLOTS), SCALE_DTYPE, "scale"),
            "v_scale": sds((LAYERS, hkv, NUM_SLOTS), SCALE_DTYPE, "scale"),
        }
    return args, scales, (BATCH, h, dh)


# llama-3b is the smoke's model (head_dim 128); llama-1b packs two tokens per
# 128-lane row (head_dim 64); llama-3-8b is the reference's headline shape;
# and the hybrid configuration's full layers: 30 query and 30 KV heads of
# 128, not a multiple of 8 (the superpage shrinks to fit VMEM: 256 keys).
@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
@pytest.mark.parametrize(
    "model", ["llama-3b", "llama-1b", "llama-3-8b", HYBRID_DIR],
    ids=["llama-3b", "llama-1b", "llama-3-8b", "olmo-hybrid-30-heads"])
def test_paged_decode_kernel_compiles_for_v5e(v5e, model, pool):
    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    args, scales, out_shape = _kernel_args(model, pool, lambda _: one_chip)
    compiled = paged_flash_decode_stats.lower(
        *args, block_size=BLOCK_SIZE, interpret=False, **scales
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    out, m, l = compiled.out_info
    assert out.shape == out_shape
    assert m.shape == l.shape == out_shape[:2]


@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
def test_sharded_paged_decode_kernel_compiles_for_four_v5e(v5e, pool):
    """The tp=4 serving path: the kernel under shard_map over the kv-head
    axis of a 4-device mesh of described chips, with no collective and no
    gather of the pool around it."""
    import numpy as np

    mesh = Mesh(np.array(v5e.devices[:4]).reshape(1, 1, 4),
                (AXIS_DP, AXIS_SP, AXIS_TP))
    spec = {
        "q": P(None, AXIS_TP, None),
        "pool": P(None, AXIS_TP, None, None),
        "scale": P(None, AXIS_TP, None),
        "rep": P(),
    }
    args, scales, _ = _kernel_args(
        "llama-3b", pool, lambda kind: NamedSharding(mesh, spec[kind])
    )

    def step(q, kp, vp, bt, lens, layer, *sc):
        kw = dict(zip(("k_scale", "v_scale"), sc))
        return paged_flash_decode_stats_tp(
            q, kp, vp, bt, lens, layer, mesh, block_size=BLOCK_SIZE,
            interpret=False, **kw,
        )

    compiled = jax.jit(step).lower(*args, *scales.values()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert f" {collective}(" not in text, collective
    # Each device holds a quarter of the kv heads: the pool argument's
    # per-device bytes are a quarter of the whole.
    mc = resolve_model_config("llama-3b")
    pool_bytes = (LAYERS * mc.num_kv_heads * NUM_SLOTS * mc.head_dim_
                  * (1 if pool == "int8" else 2))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 2 * pool_bytes / 4 * 1.2, (per_device, pool_bytes)


@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
@pytest.mark.parametrize("run", ["decode-8x32", "prefill-1x512"])
def test_pool_write_is_in_place_on_v5e(v5e, pool, run):
    """The KV write of a dispatch (ops/kv_write.py) on donated pools at
    llama-3b's widths: the compiled program copies no pool and holds no
    pool-sized temporary. The form it replaced, ``pool.at[:, :,
    slots].set(new)``, cost two whole-pool copies a pool here (PERF.md §6,
    PR 25) — and the CPU compiler cannot show it (it has no tiled layouts
    to change, and widens bf16 updates instead)."""
    from production_stack_tpu.ops.kv_write import (
        pool_copies,
        write_token_runs,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    mc = resolve_model_config("llama-3b")
    hkv, dh = mc.num_kv_heads, mc.head_dim_
    b, t = (8, 32) if run.startswith("decode") else (1, 512)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    store = jnp.int8 if pool == "int8" else jnp.bfloat16
    pools = [sds((LAYERS, hkv, NUM_SLOTS, dh), store)] * 2
    news = [sds((LAYERS, hkv, b, t, dh), store)] * 2
    if pool == "int8":
        pools += [sds((LAYERS, hkv, NUM_SLOTS), SCALE_DTYPE)] * 2
        news += [sds((LAYERS, hkv, b, t), SCALE_DTYPE)] * 2

    def write(pools, news, tables, start, length):
        return write_token_runs(pools, news, tables, start, length,
                                BLOCK_SIZE)

    compiled = jax.jit(write, donate_argnums=0).lower(
        pools, news, sds((b, MAX_BLOCKS), jnp.int32),
        sds((b,), jnp.int32), sds((b,), jnp.int32),
    ).compile()
    assert not pool_copies(compiled.as_text(), pools)
    mem = compiled.memory_analysis()
    payload = LAYERS * hkv * NUM_SLOTS * dh * jnp.dtype(store).itemsize
    assert mem.temp_size_in_bytes < payload / 4, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * payload


def _unguarded_instructions(hlo: str):
    """Instruction lines of every computation the entry reaches WITHOUT
    passing through a ``conditional``'s branch: the entry itself, ``while``
    bodies and conditions, fusions and reducers called from those."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            line = re.sub(r"(branch_computations=\{[^}]*\}"
                          r"|(true|false)_computation=%?[\w.\-]+)", "", line)
            for group in re.findall(
                    r"(?:calls|to_apply|body|condition|called_computations)"
                    r"=\{?((?:%[\w.\-]+(?:,\s*)?)+)", line):
                todo.extend(re.findall(r"%([\w.\-]+)", group))
    return [line for comp in seen for line in comps[comp]], len(comps)


def test_sampler_branches_sit_inside_conditionals_on_v5e(v5e):
    """The decode program's sampler at qwen2.5-3b's width, 32 rows x 151936,
    as the step loop calls it (predicates reduced once, outside the
    ``while``): the TPU compiler keeps both ``conditional``s, and every
    ``TopK`` custom call and every operation of the Gumbel field lies in a
    branch computation — none in the entry or the ``while`` body, where an
    all-greedy dispatch would pay for it (0.41 s of 4 s in
    qwen2.5-3b.chat-saturated before PR 28, PERF.md §6). A refactor that
    makes XLA flatten a cond into selects fails here, at no chip time."""
    from production_stack_tpu.engine.sampling import (
        sample_tokens,
        sampler_paths,
        sampling_scores,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    rows, vocab, steps = 32, 151936, 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def train(logits, temps, top_k, top_p, seeds):
        paths = sampler_paths(temps, top_k, top_p)

        def body(state):
            j, toks, best = state
            step_logits = logits + j.astype(jnp.float32)
            step_seeds = seeds + j.astype(jnp.uint32)
            nxt = sample_tokens(step_logits, temps, top_k, top_p,
                                step_seeds, paths)
            scores = sampling_scores(step_logits, temps, step_seeds,
                                     paths[0])
            return (j + 1, toks.at[j].set(nxt.astype(jnp.int32)),
                    jnp.maximum(best, scores.max(axis=-1)))

        return jax.lax.while_loop(
            lambda s: s[0] < steps, body,
            (jnp.int32(0), jnp.zeros((steps, rows), jnp.int32),
             jnp.zeros((rows,), jnp.float32)))

    compiled = jax.jit(train).lower(
        sds((rows, vocab), jnp.float32), sds((rows,), jnp.float32),
        sds((rows,), jnp.int32), sds((rows,), jnp.float32),
        sds((rows,), jnp.uint32),
    ).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") >= 3, text.count(" conditional(")
    assert 'custom_call_target="TopK"' in text
    assert "_gumbel" in text
    assert " while(" in text
    unguarded, n_comps = _unguarded_instructions(text)
    assert n_comps > 10 and unguarded
    for line in unguarded:
        assert 'custom_call_target="TopK"' not in line, line[:300]
        assert "_gumbel" not in line, line[:300]
    # The skipped picks need no buffer of the field's size kept alive
    # outside the branches: temporaries stay a few fields' worth.
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * rows * vocab * 4


# ---- prefill attention: the flash kernel over the paged pool (PR 35)
@pytest.mark.parametrize("rows,t,heads,kv_heads", [
    (8, 256, 16, 2), (1, 512, 32, 8), (1, 2048, 30, 30), (1, 128, 16, 2)],
    ids=["qwen-8x256", "mistral-1x512", "olmo-1x2048", "qwen-1x128"])
def test_paged_prefill_kernel_compiles_for_v5e(v5e, rows, t, heads, kv_heads):
    """The prefill flash kernel alone, at the benchmark's head layouts and
    chunk widths: Mosaic takes it (VMEM, tiling, the page copies), and its
    device operation does not carry the decode kernels' name (the
    benchmark counts decode steps by the prefix ``paged_flash_decode``)."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_prefill,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((LAYERS, kv_heads, NUM_SLOTS, 128), jnp.bfloat16)
    chunk = sds((rows, t, kv_heads, 128), jnp.bfloat16)
    compiled = paged_flash_prefill.lower(
        sds((rows, t, heads, 128), jnp.bfloat16), chunk, chunk,
        sds((rows, t), jnp.int32), sds((rows,), jnp.int32), pool, pool,
        sds((rows, MAX_BLOCKS), jnp.int32), sds((rows,), jnp.int32),
        sds((1,), jnp.int32), block_size=BLOCK_SIZE).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%paged_flash_prefill" in text
    assert "%paged_flash_decode" not in text      # an operation's name
    assert compiled.out_info.shape == (rows, t, heads, 128)


# (deployment, rows, t, the parent's and this tree's temp_size_in_bytes of
# that program: PR 33's tree gathered a window of every row at the widest
# step of its ladder and held the float32 scores; measured at PR 35.)
PREFILL_PROGRAMS = {
    # The dense deployments' dispatches are packed rows since PR 46: the
    # 2048 tokens that were 8 x 256 are one row (162.7 MB at PR 46).
    "qwen2.5-3b-1x2048":
        ("qwen2.5-3b", 1, 2048, 1_444_768_256, 162_667_008),
    "mistral-7b-d16-1x512":
        ("mistral-7b-d16", 1, 512, 844_797_440, 3_024_896),
    "olmo-hybrid-7b-d16-1x2048":
        ("olmo-hybrid-7b-d16", 1, 2048, 913_192_448, 661_928_960),
}


@pytest.mark.parametrize("program", list(PREFILL_PROGRAMS))
def test_prefill_programs_hold_the_flash_kernel_on_v5e(v5e, program):
    """A prefill program of the three K/V deployments, lowered for a v5e
    as the engine lowers it: its chunk attends through the flash kernel
    over the pool (``prefill_attn`` "pallas"), the pools are written in
    place, nothing of a window's shape is gathered, no float32 tensor of
    the scores' shape exists, and its temporaries are below the parent's.
    There is ONE such program a (rows, t): a window is no property of it."""
    import re

    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.attention import prefill_attn_path
    from production_stack_tpu.ops.kv_write import pool_copies

    name, rows, t, parent_temp, temp = PREFILL_PROGRAMS[program]
    r = _deployment_runner(v5e, name)
    assert r.prefill_reads_pool
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    fams = [f for f in r.reachable_prefill_families()
            if f[:2] == (rows, t)]
    assert fams == [(rows, t, full_mb, False)]
    compiled = r._lower_prefill(r._abstract_params(), *fams[0]).compile()
    text = compiled.as_text()
    assert prefill_attn_path(text) == "pallas"
    assert "%paged_flash_prefill" in text
    assert "%paged_flash_decode" not in text      # an operation's name
    assert pool_copies(text, [r.kv_k, *r.state_pools]) == []
    nl, hkv, dh = r.kv_spec
    heads = r.model_config.num_heads
    # A gathered window [.., Hkv, rows, keys, Dh] at any step of the
    # parent's ladder, and a float32 tensor with the scores' leading shape
    # [Hkv, rows, G x queries, ..] (the scores and the value product of
    # window_attention): neither is there.
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"[a-z]\w*\[([\d,]+)\]", text)}
    ladder = {full_mb * 16 // d for d in (1, 2, 4)}
    for shape in shapes:
        assert not (len(shape) >= 4 and shape[-4:-2] == (hkv, rows)
                    and shape[-2] in ladder and shape[-1] == dh
                    and shape != tuple(r.kv_k.shape)), shape
    score_rows = heads // hkv * min(t, 256)
    lead = tuple(x for x in (hkv, rows, score_rows) if x != 1)
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        shape = tuple(int(x) for x in dims.split(",") if x != "1")
        assert not (shape[:-1] == lead and shape[-1] in ladder | {t}), shape
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < parent_temp / 1.3
    assert mem.temp_size_in_bytes <= temp * 1.05


@pytest.mark.parametrize("name,families,in_place,packs", [
    ("qwen2.5-3b", 5, True, True), ("mistral-7b-d16", 5, True, True),
    ("olmo-hybrid-7b-d16", 8, True, False),
    ("kanana-2-30b-a3b-d8", 4, True, True),
    ("xing4.0-29b-a4b-d7", 4, True, True),
    ("granite-4.0-h-micro", 8, True, False),
    ("lfm2-8b-a1b-d16", 4, True, True),
    ("trinity-mini-d8", 5, True, True)])
def test_prefill_family_counts_of_the_deployments(v5e, name, families,
                                                  in_place, packs):
    """One prefill family a (rows, t) where the history is read in place
    (36 -> 9 at qwen2.5-3b's envelope, 32 -> 9, 18 -> 9, and 8 since PR
    37's ladder: 1 x {128..2048}, 8 x {128, 256}, 16 x 128); so it is for
    latent rows since PR 39 (14, with and without the pinned window, -> 7:
    1 x {128..1024}, 4 x {128, 256}, 8 x 128), and no program is larger
    than the token budget. The dense deployments' dispatches are packed
    rows since PR 46 (``prefill_packs``): the one-row column alone, 8 -> 5;
    whoever keeps a state a row keeps the rectangles, and trinity-mini-d8
    (K/V rows only, sparse experts) is the first sparse model through the
    packed row. The two latent deployments follow in PR 48 (the packed
    kernel's body over one page stream): 7 -> 4, 1 x {128..1024}; their
    4 prefill programs, compiled here for a described v5e into an empty
    cache one variant each, are 17.2 and 21.5 MB where the 7 were 28.1
    and 35.8 (PERF.md section 6, PR 48), a boot holds three prefill
    families fewer, and a configuration's cache is capped at 192 MiB.
    lfm2-8b-a1b-d16 follows in PR 50, 7 -> 4: its one state is the short
    convolution's last two inputs, which cross a segment boundary inside
    the row (``STATES_CROSSING_SEGMENTS``); the two deployments whose
    states are scans' keep their rectangles."""
    r = _deployment_runner(v5e, name)
    assert r.prefill_reads_pool is in_place
    assert r.prefill_packs is packs
    fams = r.reachable_prefill_families()
    assert len(fams) == families
    assert ({f[0] for f in fams} == {1}) is packs
    assert all(rows * t <= r.config.max_num_batched_tokens
               for rows, t, _, _ in fams)
    assert {f[3] for f in fams} == ({False} if in_place else {False, True})
    assert r.prefill_window_blocks == (
        1 << 30 if in_place else r.num_kv_blocks)
_PARENT_KERNEL_JAXPR = {"kv": "46493c11e2bc8df7", "latent": "08ff37ac91730342"}


@pytest.mark.parametrize("kernel", sorted(_PARENT_KERNEL_JAXPR))
def test_rectangle_prefill_kernels_trace_to_the_parents_jaxpr(kernel):
    """... and the jaxprs of the two rectangle entry points (what a Mosaic
    body is made from), at the hybrid's full layers' and the latent
    configurations' shapes: over K/V rows the rectangle kernel's, PR 45's
    still; over latent rows, since PR 56, the packed body's over the
    rectangle laid as a row (re-pinned there on purpose: PR 45's was
    578dfb29646ddfdd)."""
    from production_stack_tpu.ops.pallas import paged_attention as pa

    sds = jax.ShapeDtypeStruct
    b, t, mb, slots = 8, 256, 192, 3072 * 16
    tail = (sds((b, mb), jnp.int32), sds((b,), jnp.int32),
            sds((), jnp.int32))
    if kernel == "kv":
        h = hkv = 30
        jaxpr = jax.make_jaxpr(
            lambda *a: pa.paged_flash_prefill(*a, block_size=16))(
            sds((b, t, h, 128), jnp.bfloat16),
            sds((b, t, hkv, 128), jnp.bfloat16),
            sds((b, t, hkv, 128), jnp.bfloat16), sds((b, t), jnp.int32),
            sds((b,), jnp.int32), sds((4, hkv, slots, 128), jnp.bfloat16),
            sds((4, hkv, slots, 128), jnp.bfloat16), *tail)
    else:
        jaxpr = jax.make_jaxpr(
            lambda *a: pa.paged_flash_prefill_latent(
                *a, block_size=16, value_dim=512, scale=0.1))(
            sds((b, t, 32, 640), jnp.bfloat16),
            sds((b, t, 1, 640), jnp.bfloat16),
            sds((b,), jnp.int32), sds((8, 1, slots, 640), jnp.bfloat16),
            *tail)
    assert _digest(str(jaxpr)) == _PARENT_KERNEL_JAXPR[kernel]


# What a packed prefill program of a deployment WITHOUT state lowered to for
# a described v5e at PR 49 (the parent of PR 50, which gave the packed row's
# forward a state to read and write where the module keeps one): digests as
# ``_PARENT_PREFILL_TEXT``'s. The added reads and writes hang on
# ``state_specs``, a Python value, so these programs hold none of them.
_PARENT_PACKED_TEXT = {
    ("qwen2.5-3b", 2048): "6400a8983d1865ae",
    ("kanana-2-30b-a3b-d8", 1024): "7f5e64a67ccf7a51",
    ("trinity-mini-d8", 2048): "c518ae71e225d241",
}


@pytest.mark.parametrize("name,t", sorted(_PARENT_PACKED_TEXT))
def test_stateless_packed_prefill_programs_lower_to_the_parents_text(v5e,
                                                                     name, t):
    """Dense K/V rows, latent rows and a bounded span: the fullest packed
    program of each lowers for a v5e to the text it lowered to before a
    packed row could carry a state."""
    from production_stack_tpu.engine.runner import _bucket

    r = _deployment_runner(v5e, name)
    assert r.prefill_packs and not r.state_specs
    fam = (1, t, _bucket(r.config.max_blocks_per_seq, 1,
                         r.config.max_blocks_per_seq), False)
    assert fam in r.reachable_prefill_families()
    assert _prefill_text_digest(r, fam) == _PARENT_PACKED_TEXT[name, t]


@pytest.mark.parametrize("name,t", [("qwen2.5-3b", 2048),
                                    ("mistral-7b-d16", 512),
                                    ("qwen2.5-3b", 128),
                                    ("kanana-2-30b-a3b-d8", 1024),
                                    ("xing4.0-29b-a4b-d7", 1024),
                                    ("lfm2-8b-a1b-d16", 1024),
                                    ("lfm2-8b-a1b-d16", 128)])
def test_packed_prefill_programs_compile_in_place_for_v5e(v5e, name, t):
    """A deployment's packed prefill program (one row of ``t`` tokens, up
    to 16 segments at a 2048-token budget, 8 at 1024) compiles for a v5e,
    holds the packed flash kernel and no other execution of the chunk's
    attention, copies no pool (the segments' K/V, or latent rows since PR
    48, go to their slots slab by slab out of the one row; since PR 50 the
    segments' conv state of lfm2-8b-a1b-d16 from and to its slot pool
    likewise) and keeps the temporaries of the rectangle it replaces."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.attention import prefill_attn_path
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, name)
    latent = r.kv_pools == 1
    experts = bool(r.fwd_stats)
    assert r.prefill_packs and r._prefill_segs == \
        r.config.max_num_batched_tokens // 128
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    assert (1, t, full_mb, False) in r.reachable_prefill_families()
    compiled = r._lower_prefill(
        r._abstract_params(), 1, t, full_mb, False).compile()
    text = compiled.as_text()
    assert pool_copies(text, [r.kv_k, *r.state_pools]) == []
    assert "paged_flash_prefill_packed" in text
    assert ("%paged_flash_prefill_packed_latent" in text) is latent
    assert "%paged_flash_prefill_latent" not in text
    assert prefill_attn_path(text) == "pallas"
    # The one kernel; where experts are routed, the sparse scan's call of
    # it and the scan's two grouped matmuls, and the dense layers' call
    # where those hold attention (lfm2's two are convolutions).
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (4 if latent else 3 if experts else 1)
    for scope in ("embed", "attn_proj", "attn_core", "ffn", "logits",
                  "kv_write", "sample") + (
                      ("short_conv", "state_read", "state_write")
                      if r.state_specs else ()):
        assert f"/{scope}/" in text, scope
    # 2048 tokens of a 3B model's activations: 163 MB at PR 46.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


def _kernel_entry_points():
    """name -> (entry point, ShapeDtypeStructs at one cell's shape, the sha1
    of its jaxpr's text): the ten Pallas kernels of the serving path, the
    ring's step at both configurations' shapes."""
    from production_stack_tpu.ops.pallas import gated_delta, ssd, window_ring
    from production_stack_tpu.ops.pallas import paged_attention as pa

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    bf16, i32 = jnp.bfloat16, jnp.int32
    mb = 3072 // BLOCK_SIZE                 # --max-model-len 3072
    slots = (64 * mb + 1) * BLOCK_SIZE

    def tables(rows):
        return (sds(rows, mb, dtype=i32), sds(rows, dtype=i32),
                sds(1, dtype=i32))

    kv = sds(36, 2, slots, 128, dtype=bf16)         # qwen2.5-3b: 16 / 2 x 128
    latent = sds(8, 1, slots, 640, dtype=bf16)      # kanana: 512 + 64 -> 640
    latent_kw = dict(block_size=BLOCK_SIZE, value_dim=512, scale=192 ** -0.5)
    return {
        "paged_flash_decode_stats-qwen-32": (
            functools.partial(pa.paged_flash_decode_stats,
                              block_size=BLOCK_SIZE),
            (sds(32, 16, 128, dtype=bf16), kv, kv, *tables(32)),
            "dea4f5850f07a2ea3ae541363a15e50eb5e23fc1"),
        "paged_flash_decode_latent_stats-kanana-64": (
            functools.partial(pa.paged_flash_decode_latent_stats,
                              **latent_kw),
            (sds(64, 32, 640, dtype=bf16), latent, *tables(64)),
            "6307294aa962f5dd45b9b96ee08e563d6f3f45a8"),
        "paged_flash_prefill-qwen-8x256": (
            functools.partial(pa.paged_flash_prefill, block_size=BLOCK_SIZE),
            (sds(8, 256, 16, 128, dtype=bf16), sds(8, 256, 2, 128, dtype=bf16),
             sds(8, 256, 2, 128, dtype=bf16), sds(8, 256, dtype=i32),
             sds(8, dtype=i32), kv, kv, *tables(8)),
            "a8e076241545956b243979e09031bc2abcd4d9af"),
        "paged_flash_prefill_latent-kanana-8x128": (
            functools.partial(pa.paged_flash_prefill_latent, **latent_kw),
            # Since PR 56 the packed body over the rectangle laid as a row
            # (re-pinned there on purpose: e990b7f0.. was the latent
            # rectangle kernel's).
            (sds(8, 128, 32, 640, dtype=bf16), sds(8, 128, 1, 640, dtype=bf16),
             sds(8, dtype=i32), latent, *tables(8)),
            "d2d052a3b19686cea433d6b05fe83ae9b9e20141"),
        # The packed row's kernel (PR 46), ONE body for both pools since PR
        # 48: over K/V rows it is the program PR 47's tree traced (this
        # hash is that tree's: it moves only if the dense cells' prefill
        # programs do), over latent rows it is PR 48's. PR 56 left both.
        "paged_flash_prefill_packed-qwen-1x2048": (
            functools.partial(pa.paged_flash_prefill_packed,
                              block_size=BLOCK_SIZE),
            (sds(1, 2048, 16, 128, dtype=bf16),
             sds(1, 2048, 2, 128, dtype=bf16),
             sds(1, 2048, 2, 128, dtype=bf16), sds(16, dtype=i32), kv, kv,
             *tables(16)),
            "dced19ac85561731751ec092dcbe267047bbe01b"),
        "paged_flash_prefill_packed_latent-kanana-1x1024": (
            functools.partial(pa.paged_flash_prefill_packed_latent,
                              **latent_kw),
            (sds(1, 1024, 32, 640, dtype=bf16),
             sds(1, 1024, 1, 640, dtype=bf16), sds(8, dtype=i32), latent,
             *tables(8)),
            "84a56b7ee25fe7b1b36640220b609e2058c0e0fb"),
        "gdn_step_in_place-olmo-32": (      # 12 layers of 30 x 96 x 192
            gated_delta.gdn_step_in_place,
            (sds(32, 12, 15, 96, 384), sds(dtype=i32), sds(32, 30, 96),
             sds(32, 30, 96), sds(32, 30, 192), sds(32, 30), sds(32, 30),
             sds(32, dtype=jnp.bool_)),
            "83e332bc883e10ac630d06f1d9d7de6491d5866e"),
        "gdn_chunk_in_place-olmo-8x256": (
            gated_delta.gdn_chunk_in_place,
            (sds(8, 15, 96, 384), sds(8, 256, 30, 96), sds(8, 256, 30, 96),
             sds(8, 256, 30, 192), sds(8, 256, 30), sds(8, 256, 30),
             sds(8, dtype=i32)),
            "02c1bc0ffbae0858902a9830ce28cb4b2a7b1d79"),
        "ssd_step_in_place-granite-32": (   # 36 layers of 64 x 64 x 128
            ssd.ssd_step_in_place,
            (sds(32, 36, 64, 64, 128), sds(dtype=i32), sds(32, 64, 64),
             sds(32, 128), sds(32, 128), sds(32, 64), sds(32, 64), sds(64),
             sds(32, dtype=jnp.bool_)),
            "de3586aacc14ccaf97b2d31521467be0726a83ef"),
        # The third user of ops/pallas/live_blocks.py (PR 53), which left
        # the two above the programs they were: 9 window layers' rings of
        # 8 x 128 slots, keys of 192 lanes in rows of 256, values of 128.
        "ring_step_in_place-mimo-32": (
            functools.partial(window_ring.ring_step_in_place,
                              scale=192 ** -0.5),
            (sds(32, 9, 8, 128, 256, dtype=bf16),
             sds(32, 9, 8, 128, 128, dtype=bf16), sds(dtype=i32),
             sds(32, 64, 192, dtype=bf16), sds(32, 8, 192, dtype=bf16),
             sds(32, 8, 128, dtype=bf16), sds(32, dtype=i32),
             sds(32, dtype=jnp.bool_), sds(64)),
            "5ec3311ea8adefa49dcebfc44664b1f16a74767f"),
        # The same kernel at phi-4-mini-flash's rings (PR 55), which left
        # the one above the program it was: 8 window layers of 10 packed
        # KV rows x 512 slots x 128 lanes, 4 queries a KV row (8 sublanes a
        # head in the float32 scratch), a 48-row bucket, no sink.
        "ring_step_in_place-phi4flash-48": (
            functools.partial(window_ring.ring_step_in_place,
                              scale=64 ** -0.5),
            (sds(48, 8, 10, 512, 128, dtype=bf16),
             sds(48, 8, 10, 512, 128, dtype=bf16), sds(dtype=i32),
             sds(48, 40, 128, dtype=bf16), sds(48, 10, 128, dtype=bf16),
             sds(48, 10, 128, dtype=bf16), sds(48, dtype=i32),
             sds(48, dtype=i32), sds(40)),
            "14fc57344db0f8b35b9129d44b18fd98e978dc1b"),
    }


KERNEL_ENTRY_POINTS = _kernel_entry_points()


@pytest.mark.parametrize("name", list(KERNEL_ENTRY_POINTS))
def test_kernel_entry_point_is_the_program_it_was(name):
    """Each Pallas kernel of the serving path, at one cell's shape, is
    pinned by the hash of its jaxpr's text (the kernel's body, its
    ``dma_start`` / ``dma_wait`` equations and the grid mapping; no file
    name and no line number, so it moves only when the program does, and it
    needs no described chip). PR 43 moved the kernels' data movement into
    shared code (``_PageFetch`` and the two sequences of
    ops/pallas/paged_attention.py, ops/pallas/live_blocks.py) and wrote
    these: six are the hashes of PR 42's tree, ``gdn_step_in_place``'s is
    new (its first fetch took the guarded form of ``ssd_step_in_place``'s).
    A PR that changes a kernel on purpose writes the new hash here."""
    import hashlib

    fn, args, want = KERNEL_ENTRY_POINTS[name]
    text = str(jax.make_jaxpr(fn)(*args))
    assert "pallas_call" in text
    assert hashlib.sha1(text.encode()).hexdigest() == want


# The decode program of every configuration at its deployment's widest
# bucket, compiled for a described v5e: (rows, instructions, the state-space
# step it holds). A change to one recurrence's operations leaves every
# program that does not run them as it was, to the instruction: PR 41
# (ops/ssd.py, ops/pallas/ssd.py) the five without a state-space layer, PR 42
# (the chunkwise form of ops/gated_delta.py, which no decode program runs)
# all six.
DECODE_PROGRAMS = {
    "qwen2.5-3b": (64, 2047, None),
    "mistral-7b-d16": (16, 2007, None),
    "olmo-hybrid-7b-d16": (32, 2749, None),
    "kanana-2-30b-a3b-d8": (64, 4003, None),
    "xing4.0-29b-a4b-d7": (64, 8260, None),
    "granite-4.0-h-micro": (32, 2730, "pallas"),
}


@pytest.mark.parametrize("name", list(DECODE_PROGRAMS))
def test_decode_programs_without_the_scan_are_unchanged_on_v5e(v5e, name):
    """A configuration with no state-space layer holds no step of the scan,
    no decode program holds a chunk of the gated delta rule, and each counts
    the instructions it did."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops import gated_delta, ssd

    rows, instructions, scan_step = DECODE_PROGRAMS[name]
    r = _deployment_runner(v5e, name)
    assert r._b_max == rows
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    text = r._lower_decode(
        r._abstract_params(), rows, full_mb, 32, False).compile().as_text()
    assert ssd.step_path(text) == scan_step
    assert gated_delta.chunk_path(text) is None
    assert sum(1 for ln in text.splitlines() if " = " in ln) == instructions
