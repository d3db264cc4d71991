"""Compile for a DESCRIBED TPU v5e (no chip attached): the recurrences. The
gated delta rule (olmo-hybrid-7b-d16), the state-space scan
(granite-4.0-h-micro) and the short convolution (lfm2-8b-a1b-d16): their
kernels and dispatch programs. tests/chip_compile_helpers.py says how and
why.
"""

import re

import pytest
import jax
import jax.numpy as jnp

from tests.chip_compile_helpers import (
    HYBRID_DIR,
    _deployment_runner,
    _described_runner,
    reads_its_pool_in_place,
)
from tests.chip_compile_helpers import (  # noqa: F401  (fixtures)
    v5e,
)


@pytest.mark.parametrize("program", ["decode-32x32", "prefill-1x2048"])
def test_hybrid_dispatch_programs_compile_in_place_for_v5e(v5e, program):
    """The decode and prefill programs of olmo-hybrid-7b-d16's envelope
    (deployment.json's flags, published widths) compile for a v5e, fit its
    HBM beside their arguments, and copy no pool: K/V and the recurrent
    state are gathered by row and written back in place. The decode
    program steps the recurrence in place in its loops' carried state
    (ops/pallas/gated_delta.py): no copy of the carry either."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.gated_delta import chunk_path, step_path
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _described_runner(
        v5e, HYBRID_DIR, max_model_len=3072, max_num_seqs=32,
        max_num_batched_tokens=2048, num_kv_blocks=3072)
    assert [p.shape for p in r.state_pools] == \
        [(33, 12, 15, 96, 384), (33, 12, 3 * 11520 // 128, 128)]
    assert r.kv_k.shape == (4, 30, 3072 * 16, 128)
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    decode = program.startswith("decode")
    if decode:
        lowered = r._lower_decode(aparams, 32, full_mb, 32, False)
    else:
        lowered = r._lower_prefill(aparams, 1, 2048, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    # The 32 rows' recurrent state as the decode loops carry it.
    carry = jax.ShapeDtypeStruct((32, 12, 15, 96, 384), jnp.float32)
    assert pool_copies(text, [r.kv_k, *r.state_pools, carry]) == []
    # The Mosaic kernels: the full layers' paged decode and the linear
    # layers' step; of prefill, the full layers' flash kernel over the pool
    # and the linear layers' chunkwise form.
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert step_path(text) == ("pallas" if decode else None)
    assert chunk_path(text) == (None if decode else "pallas")
    mem = compiled.memory_analysis()
    # The rows' state is ONE loop carry (0.85 GB at 32 rows), not one a
    # layer, and the step kernel is aliased to it: the decode program's
    # temporaries stay under 1.5 GB (1.246 GB, as before the kernel).
    assert mem.temp_size_in_bytes < 1.5 * (1 << 30)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# The shapes a prefill dispatch of olmo-hybrid-7b-d16's deployment has: its
# eight families (1 x {128..2048}, 8 x {128, 256}, 16 x 128; T is always whole
# chunks of 64).
HYBRID_PREFILL_FAMILIES = [(1, 128), (1, 256), (1, 512), (1, 1024),
                           (1, 2048), (8, 128), (8, 256), (16, 128)]


@pytest.mark.parametrize("rows,t", [(16, 128), (8, 256), (8, 128), (1, 2048)])
def test_gdn_chunk_kernel_compiles_for_v5e(v5e, rows, t):
    """The chunkwise kernel alone (ops/pallas/gated_delta.py) at
    Olmo-Hybrid-7B's published head shapes, 30 x 96 x 192: it compiles for
    a v5e (VMEM: a row's 2.2 MB state in and out beside a chunk's blocks),
    and the state it returns is the buffer it was given."""
    from production_stack_tpu.ops.pallas.gated_delta import (
        gdn_chunk_in_place,
        supports_chunk_kernel,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    h, dk, dv = 30, 96, 192
    assert supports_chunk_kernel(t, h, (15, 96, 384))
    text = gdn_chunk_in_place.lower(
        sds(rows, 15, 96, 384), sds(rows, t, h, dk), sds(rows, t, h, dk),
        sds(rows, t, h, dv), sds(rows, t, h), sds(rows, t, h),
        sds(rows, dtype=jnp.int32)).compile().as_text()
    call = [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(call) == 1 and "gdn_chunk_in_place" in call[0]
    assert "output_to_operand_aliasing={{1}: (5, {})}" in call[0]


def test_the_smoke_times_the_whole_jnp_chunk_form_on_v5e(v5e):
    """``chip_smoke.py --gdn`` chains calls of a form of ``gdn_chunk``
    through the state with the same q, k, v and gates every call. Compiled
    for a v5e, the program of the ``jnp`` form holds its three loops (the
    calls, the 63-trip substitution, the scan over chunks) with only the
    first in the entry computation: nothing the form computes is lifted out
    of the timed loop and done once for all the calls."""
    import chip_smoke
    from production_stack_tpu.ops.gated_delta import gdn_chunk_jnp

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    t, h, dk, dv = 128, 30, 96, 192
    text = jax.jit(chip_smoke.chained_chunks(
        gdn_chunk_jnp, jnp.array([t], jnp.int32), 4)).lower(
            sds(1, 15, 96, 384), sds(1, t, h, dk), sds(1, t, h, dk),
            sds(1, t, h, dv), sds(1, t, h), sds(1, t, h)).compile().as_text()
    entry = next(c for c in text.split("\n\n") if c.startswith("ENTRY"))
    assert text.count(" while(") == 3 and entry.count(" while(") == 1


@pytest.mark.parametrize("rows,t", HYBRID_PREFILL_FAMILIES)
def test_hybrid_prefill_programs_hold_the_chunk_kernel_on_v5e(v5e, rows, t):
    """Every prefill family of olmo-hybrid-7b-d16's deployment holds the
    chunkwise kernel: under the recurrence's scope no loop is left (the
    63-trip substitution, the scan over chunks) and nothing is copied or
    transposed (q, k and v reach the kernel from the fusions that make
    them, the state as the slice of the rows' carried state), and the state
    pools are updated in place. Its full layers read their pool in place
    whatever the rectangle."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.gated_delta import chunk_path

    r = _deployment_runner(v5e, "olmo-hybrid-7b-d16")
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    assert [f[:2] for f in r.reachable_prefill_families()] == \
        HYBRID_PREFILL_FAMILIES
    text = r._lower_prefill(
        r._abstract_params(), rows, t, full_mb, False).compile().as_text()
    assert chunk_path(text) == "pallas"
    reads_its_pool_in_place(text, r, rows)
    # Operations under the recurrence's scope (a result's type, a tuple's
    # too, ends at the last "} " or ") " before the operation's name).
    ops = {m.group(1) for m in (
        re.search(r" = (?:\(.*?\)|\S+) ([a-z][\w\-]*)\(", ln)
        for ln in text.splitlines() if "/gdn_chunk/" in ln) if m}
    assert "custom-call" in ops
    assert not ops & {"while", "copy", "copy-start", "transpose"}, ops


# ---- granite-4.0-h-micro: state-space layers beside 64-lane attention heads
# Instructions of a compiled dispatch program (2730 and 2673 at the time of
# writing; the decode program was 2796 while XLA packed the step kernel's
# small operands, PR 40: ONE state-space layer's code and ONE attention
# layer's, whatever the depth; a second traced copy of either shows here).
STATE_SPACE_INSTRUCTIONS = 3600
# The decode program's temporaries with the step kernel's first form (PR 40):
# the 32 rows' carried state is 2.45 GB of them. The kernel's operands (the
# decays in SMEM among them) may pin no layout that costs more.
STATE_SPACE_DECODE_TEMP = 2_606_885_376


@pytest.mark.parametrize("program", ["decode-32x32", "prefill-8x256"])
def test_state_space_dispatch_programs_compile_in_place_for_v5e(v5e, program):
    """The decode program at the 32-row bucket and the [8, 256] prefill
    program of granite-4.0-h-micro's envelope (deployment.json's flags,
    published widths, all 40 layers) compile for a v5e, fit its HBM beside
    their arguments, and copy no pool: K/V, the scan's state and the conv
    state are gathered by row and written back in place. The decode program
    steps the scan in place in its loops' carried state
    (ops/pallas/ssd.py): no copy of the carry either, and no more
    temporaries than with the kernel's first form (2.6 GB, of which the 32
    rows' carried state is 2.45: a head's decay is an operand of its own, in
    SMEM, and pins no projection's layout). The attention layers' 64-lane KV heads lie
    two to a row of 128 lanes (models/granite_hybrid.py:kv_pack), so both
    paged kernels take them as they are: with a pool whose minor axis was
    64 the compiler kept it slots-minor, copied both pools whole into
    every dispatch and reshaped them whole a layer a step for the decode
    kernel's two-tokens-a-row view (8.7 GB of temporaries: it did not
    fit), and with dt's 64 columns beside z | xBC it copied the in_proj
    stack (1.25 GB)."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops import ssd
    from production_stack_tpu.ops.attention import prefill_attn_path
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "granite-4.0-h-micro")
    assert [p.shape for p in r.state_pools] == \
        [(33, 36, 64, 64, 128), (33, 36, 3 * 4352 // 128, 128)]
    assert [str(p.dtype) for p in r.state_pools] == ["float32", "bfloat16"]
    assert r.kv_k.shape == (4, 4, 6144 * 16, 128)
    assert r.prefill_reads_pool
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    decode = program.startswith("decode")
    if decode:
        lowered = r._lower_decode(aparams, 32, full_mb, 32, False)
    else:
        assert (8, 256, full_mb, False) in r.reachable_prefill_families()
        lowered = r._lower_prefill(aparams, 8, 256, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    # The rows' state as the decode loops carry it (2.42 GB + 30 MB), and
    # the weights' largest stacks.
    carried = [jax.ShapeDtypeStruct((32, 36, 64, 64, 128), jnp.float32),
               jax.ShapeDtypeStruct((32, 36, 102, 128), jnp.bfloat16)]
    stacks = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
        (36, 2048, 8448), (36, 4096, 2048), (36, 2048, 16384),
        (36, 8192, 2048))]
    assert pool_copies(
        text, [r.kv_k, *r.state_pools, *carried, *stacks]) == []
    # The Mosaic kernels: the attention layers' paged decode and the
    # state-space layers' step; of prefill, the flash kernel over the pool.
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (2 if decode else 1)
    assert ssd.step_path(text) == ("pallas" if decode else None)
    assert ("/ssd_chunk/" in text) == (not decode)
    if not decode:
        assert prefill_attn_path(text) == "pallas"
        reads_its_pool_in_place(text, r, 8)
    for scope in ("embed", "attn_proj", "attn_core", "ffn", "logits",
                  "kv_write", "sample"):
        assert f"/{scope}/" in text, scope
    instructions = sum(1 for ln in text.splitlines() if " = " in ln)
    assert instructions < STATE_SPACE_INSTRUCTIONS, instructions
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= (
        STATE_SPACE_DECODE_TEMP if decode else 2.2e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# ---- lfm2-8b-a1b-d16: gated short convolutions around routed experts (PR 44)
# Instructions of a compiled dispatch program (3908 and 4291 at the time of
# writing, kanana's 4003 and 4257 beside them: ONE attention operator, ONE
# sparse FFN and one convolution a scan, whatever the depth and wherever the
# attention layers stand; a second traced copy of the experts shows here).
# The packed [1, 1024] prefill program (PR 50) counts 5626 where the rectangle
# it replaces counted 4291: the segments' bookkeeping (which slot a token's
# K/V goes to out of the one row, the packed kernel's tiles), as kanana's
# packed program of the same row counts 5310 where its rectangle counted 4257.
LFM_INSTRUCTIONS = 4900
LFM_PACKED_INSTRUCTIONS = 6200


@pytest.mark.parametrize("program", ["decode-64x32", "decode-16x32",
                                     "prefill-1x128", "prefill-1x1024"])
def test_short_conv_expert_dispatch_programs_compile_in_place_for_v5e(
        v5e, program):
    """The decode program at the widest and at the window's 16-row bucket
    and the shortest and the longest prefill program of lfm2-8b-a1b-d16's
    envelope (deployment.json's flags, published widths, all 32 experts of
    14 sparse layers, 12 conv layers' state in 65 slots) compile for a v5e,
    fit its HBM beside 10.80 GB of weights and the 1.61 GB K/V pool, and
    copy neither a pool, nor the conv state a decode loop carries or a
    prefill row's segments read and write, nor an expert stack. They hold
    the Mosaic kernels: the attention layers' paged kernel (decode, or the
    PACKED prefill flash since PR 50: a prefill program is one row of up to
    8 segments, the [1, 1024] row what the [8, 128] rectangle was; over
    64-lane KV heads two to a row) and the two grouped matmuls of the
    sparse scan; the convolution is plain XLA under its own scope."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops import gated_delta, ssd
    from production_stack_tpu.ops.attention import prefill_attn_path
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "lfm2-8b-a1b-d16")
    assert [p.shape for p in r.state_pools] == [(65, 12, 32, 128)]
    assert [str(p.dtype) for p in r.state_pools] == ["bfloat16"]
    assert r.kv_k.shape == r.kv_v.shape == (4, 4, 12288 * 16, 128)
    assert r.prefill_reads_pool and r.fwd_stats
    assert r.prefill_packs and r._prefill_segs == 8
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    sparse = aparams["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (14, 32, 2048, 3584)
    assert sparse["w_router"].dtype == jnp.float32
    decode = program.startswith("decode")
    rows, t = (int(x) for x in program.split("-")[1].split("x"))
    if decode:
        lowered = r._lower_decode(aparams, rows, full_mb, t, False)
    else:
        assert (rows, t, full_mb, False) in r.reachable_prefill_families()
        lowered = r._lower_prefill(aparams, rows, t, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    # The state a decode loop carries of its rows, every step. (A packed
    # prefill row's, a segment each, [8, 12, 32, 128], is read once and
    # written once a dispatch; between the dense layers' scan and the
    # sparse layers' the compiler lays its 0.8 MB out anew in VMEM, once.)
    carried = [jax.ShapeDtypeStruct((rows, 12, 32, 128), jnp.bfloat16)] \
        if decode else []
    experts = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for k in (
        "w_gate_up", "we_down") for shape in (
            sparse[k].shape, (14 * 32, *sparse[k].shape[2:]))]
    assert pool_copies(
        text, [r.kv_k, *r.state_pools, *carried, *experts]) == []
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert gated_delta.short_conv_path(text) == "xla"
    assert ssd.step_path(text) is None
    assert gated_delta.step_path(text) is None
    if not decode:
        assert prefill_attn_path(text) == "pallas"
        assert "%paged_flash_prefill_packed" in text
    for scope in ("embed", "attn_proj", "attn_core", "short_conv", "ffn",
                  "moe_route", "moe_experts", "moe_gmm", "logits",
                  "kv_write", "state_write", "sample"):
        assert f"/{scope}/" in text, scope
    instructions = sum(1 for ln in text.splitlines() if " = " in ln)
    assert instructions < (
        LFM_INSTRUCTIONS if decode else LFM_PACKED_INSTRUCTIONS), instructions
    mem = compiled.memory_analysis()
    # Weights 10.80 GB, K/V 1.61 GB and 6.4 MB of slots are arguments; a
    # program's temporaries (0.11 GB at the widest) fit beside them.
    assert 12.4e9 < mem.argument_size_in_bytes < 12.45e9
    assert mem.temp_size_in_bytes < 0.4e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_prefill_family_count_of_the_short_conv_deployment(v5e):
    """4 prefill families of one row (1 x {128..1024}; 7 with 4 x {128,
    256} and 8 x 128 before PR 50), as kanana's under the same token
    budget since PR 48, none with a window."""
    r = _deployment_runner(v5e, "lfm2-8b-a1b-d16")
    fams = r.reachable_prefill_families()
    assert [f[:2] for f in fams] == [(1, 128), (1, 256), (1, 512), (1, 1024)]
    assert {f[3] for f in fams} == {False}
