"""Compile for a DESCRIBED TPU v5e (no chip attached): latent rows and the
four-stream residual. kanana-2-30b-a3b-d8's and xing4.0-29b-a4b-d7's kernels
(the latent decode and prefill kernels, the grouped matmul) and dispatch
programs, and the pinned RECTANGLE prefill programs of whoever still builds
one (a state a row; latent rows beside an adapter).
tests/chip_compile_helpers.py says how and why.
"""

import re

import pytest
import jax
import jax.numpy as jnp

from tests.chip_compile_helpers import (
    BLOCK_SIZE,
    NUM_SLOTS,
    _deployment_runner,
    _prefill_text_digest,
    reads_its_pool_in_place,
)
from tests.chip_compile_helpers import (  # noqa: F401  (fixtures)
    v5e,
)


@pytest.mark.parametrize("rows", [8, 64])
def test_latent_decode_kernel_compiles_for_v5e(v5e, rows):
    """32 query heads over ONE row a token, 640 lanes wide, values its
    first 512: the published widths of kanana-2-30b-a3b."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_decode_latent_stats,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = paged_flash_decode_latent_stats.lower(
        sds((rows, 32, 640), jnp.bfloat16),
        sds((8, 1, NUM_SLOTS, 640), jnp.bfloat16),
        sds((rows, 192), jnp.int32), sds((rows,), jnp.int32),
        sds((1,), jnp.int32),
        block_size=BLOCK_SIZE, value_dim=512, scale=192 ** -0.5,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out, m, l = compiled.out_info
    assert out.shape == (rows, 32, 512)
    assert m.shape == l.shape == (rows, 32)


@pytest.mark.parametrize("pairs,k,n", [
    (32 * 6, 2048, 1536), (32 * 6, 768, 2048),         # a decode step
    (1024 * 6, 2048, 1536), (1024 * 6, 768, 2048),     # a prefill chunk
], ids=["decode-gate-up", "decode-down", "prefill-gate-up", "prefill-down"])
def test_grouped_matmul_compiles_for_v5e(v5e, pairs, k, n):
    """The experts' two products over the WHOLE stack of 7 x 128 experts
    (a layer's groups sit at layer x 128: no slice of 1.2 GB is cut out)."""
    from production_stack_tpu.ops.pallas.grouped_matmul import moe_gmm

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(moe_gmm).lower(
        sds((pairs, k), jnp.bfloat16), sds((7 * 128, k, n), jnp.bfloat16),
        sds((7 * 128,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled.out_info.shape == (pairs, n)
    # The stack goes to the kernel as it lies: no copy of its shape.
    assert f"bf16[{7 * 128},{k},{n}]" in text
    assert not [ln for ln in text.splitlines()
                if f"bf16[{7 * 128},{k},{n}]" in ln.split(" = ")[0]
                and " copy(" in ln]


@pytest.mark.parametrize("program", ["decode-32x32", "prefill-1x1024"])
def test_latent_dispatch_programs_compile_in_place_for_v5e(v5e, program):
    """The decode and the fullest prefill program of kanana-2-30b-a3b-d8's
    envelope (deployment.json's flags, published widths, all 128 experts of
    7 sparse layers) compile for a v5e, fit its HBM beside 10.14 GB of
    weights and the 2.68 GB latent pool, copy neither the pool nor the
    experts' stacks, and hold the Mosaic kernels: the latent decode or
    prefill kernel (the dense layer's call and the sparse scan's) and the
    two grouped matmuls of the scan."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "kanana-2-30b-a3b-d8")
    assert r.kv_k.shape == (8, 1, 16384 * 16, 640)
    assert r.kv_v.shape == (8, 1, 16384 * 16, 0)     # no second pool
    assert r.state_pools == ()
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    sparse = aparams["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (7, 128, 2048, 1536)
    assert sparse["w_router"].dtype == jnp.float32
    decode = program.startswith("decode")
    if decode:
        lowered = r._lower_decode(aparams, 32, full_mb, 32, False)
    else:
        lowered = r._lower_prefill(aparams, 1, 1024, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    experts = [jax.ShapeDtypeStruct((7 * 128, *sparse[k].shape[2:]),
                                    jnp.bfloat16)
               for k in ("w_gate_up", "we_down")]
    assert pool_copies(text, [r.kv_k, *experts]) == []
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert ("%paged_flash_decode_latent_stats" in text) == decode
    assert ("%paged_flash_prefill_packed_latent" in text) == (not decode)
    mem = compiled.memory_analysis()
    # Weights 10.14 GB and the pool 2.68 GB are arguments; a latent row
    # costs a decode program no temporary of its own, and a prefill
    # program no window and no score tensor (0.61 GB with them, PR 38).
    assert 12.8e9 < mem.argument_size_in_bytes < 12.9e9
    assert mem.temp_size_in_bytes < 0.4e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("program", ["decode-32x32", "prefill-1x1024"])
def test_four_stream_dispatch_programs_compile_for_v5e(v5e, program):
    """The decode and the fullest prefill program of xing4.0-29b-a4b-d7's
    envelope (deployment.json's flags, published widths, 2 dense + 5 sparse
    layers with all 64 experts, a residual of 4 streams) compile for a v5e,
    fit its HBM beside 9.85 GB of weights and the 2.35 GB latent pool, copy
    neither the pool nor the experts' stacks, hold the Mosaic kernels (the
    latent decode or prefill kernel in the dense layers' scan and in the
    sparse one, the two grouped matmuls) and the stream mix under its scopes, with the
    Sinkhorn iterations as loops (a program with them unrolled was six
    times the instructions and did not fit the compile cache's cap with
    its 47 siblings: PERF.md section 6, PR 38)."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "xing4.0-29b-a4b-d7")
    assert r.kv_k.shape == (7, 1, 16384 * 16, 640)
    assert r.kv_v.shape == (7, 1, 16384 * 16, 0)
    assert r.residual_report() == {"hc_mult": 4, "hc_mix": "xla"}
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    sparse = aparams["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (5, 64, 3584, 2048)
    assert sparse["hc_attn_phi"].shape == (5, 14336, 24)
    assert sparse["hc_attn_phi"].dtype == jnp.float32
    decode = program.startswith("decode")
    if decode:
        lowered = r._lower_decode(aparams, 32, full_mb, 32, False)
    else:
        lowered = r._lower_prefill(aparams, 1, 1024, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    experts = [jax.ShapeDtypeStruct((5 * 64, *sparse[k].shape[2:]),
                                    jnp.bfloat16)
               for k in ("w_gate_up", "we_down")]
    assert pool_copies(text, [r.kv_k, *experts]) == []
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert ("%paged_flash_decode_latent_stats" in text) == decode
    assert ("%paged_flash_prefill_packed_latent" in text) == (not decode)
    for scope in ("attn_proj/hc_pre", "ffn/hc_pre", "attn_proj/hc_post",
                  "ffn/hc_post", "logits/hc_head"):
        assert scope in text, scope
    # Four sublayers' code (two scans of two), each with its Sinkhorn loop
    # (2 iterations a trip): a third of the unrolled program's 26.6k.
    assert len(re.findall(r"= \S+ \w[\w-]*\(", text)) < 12_000
    mem = compiled.memory_analysis()
    assert 12.1e9 < mem.argument_size_in_bytes < 12.3e9
    assert mem.temp_size_in_bytes < (0.2e9 if decode else 0.7e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _without_source_locations(text: str) -> str:
    """A compiled program's text without what moves with a line number:
    metadata, the location tables, the Mosaic kernels' serialized bodies."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"[A-Za-z0-9+/=]{200,}", "<payload>", text)
    return "\n".join(
        ln for ln in text.splitlines() if not re.match(
            r'^(\d+ ["{]|FileNames|FunctionNames|FileLocations|StackFrames)',
            ln))


def test_latent_prefill_program_is_the_parents_on_v5e(v5e):
    """kanana-2-30b-a3b-d8's fullest RECTANGLE prefill program ([8, 128])
    is pinned by the hash of its compiled text without source locations: PR
    39 replaced PR 35's windowed program on purpose (the history read in
    place: no window, no family with one); PR 56 replaced it again on
    purpose (2690042c18a0360e.. was the latent rectangle kernel's: the
    rectangle now goes to ``paged_flash_prefill_packed_latent`` as a row
    whose segments begin at multiples of T). A PR that changes this program
    on purpose writes the new hash here."""
    import hashlib

    from production_stack_tpu.engine.runner import _bucket

    r = _deployment_runner(v5e, "kanana-2-30b-a3b-d8")
    # Since PR 48 the deployment's own dispatches are packed rows; the
    # pinned program is the rectangle a runner with an adapter or a
    # draft's ring a row still builds (``prefill_packs`` false).
    r.__dict__["prefill_packs"] = False
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    text = r._lower_prefill(
        r._abstract_params(), 8, 128, full_mb, False).compile().as_text()
    reads_its_pool_in_place(text, r, 8)
    body = _without_source_locations(text)
    assert hashlib.sha1(body.encode()).hexdigest() == \
        "f24115dbd6f8ccff3eb4e588064088112be42e7d"


# ---- prefill attention over latent rows: the flash kernel (PR 39)
@pytest.mark.parametrize("rows,t", [(8, 128), (4, 256), (1, 1024), (1, 128)],
                         ids=lambda x: str(x))
def test_latent_prefill_kernel_compiles_for_v5e(v5e, rows, t):
    """The latent prefill kernel alone, handed a RECTANGLE (laid as a row
    whose segments begin at multiples of T, since PR 56), at both latent
    deployments' shapes (32 heads over ONE 640-lane row a token, values its
    first 512, block 16) and their fullest rectangles: Mosaic takes it (a block's [32
    queries, 32 heads, 640] as [1024, 640] with no relayout, 64 MiB of
    VMEM, the page copies), no transpose of q or of the output surrounds
    it, and its device operation carries the prefill kernels' name, not
    the decode kernels' (the benchmark counts decode steps by the prefix
    ``paged_flash_decode``)."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_prefill_latent,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = paged_flash_prefill_latent.lower(
        sds((rows, t, 32, 640), jnp.bfloat16),
        sds((rows, t, 1, 640), jnp.bfloat16), sds((rows,), jnp.int32),
        sds((8, 1, NUM_SLOTS, 640), jnp.bfloat16),
        sds((rows, 192), jnp.int32), sds((rows,), jnp.int32),
        sds((1,), jnp.int32),
        block_size=BLOCK_SIZE, value_dim=512, scale=192 ** -0.5).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%paged_flash_prefill_packed_latent" in text
    assert not re.search(r"%paged_flash_prefill(_latent)?[.\s]", text)
    assert "%paged_flash_decode" not in text      # an operation's name
    assert not re.search(r"= bf16\[[\d,]+\]\S* (copy|transpose)\(", text)
    assert compiled.out_info.shape == (rows, t, 32, 512)


@pytest.mark.parametrize("t", [1024, 128])
def test_packed_latent_prefill_kernel_compiles_for_v5e(v5e, t):
    """The packed latent kernel alone at both latent deployments' shapes
    and the widest and the narrowest row of their envelope (8 segments):
    Mosaic takes the packed kernel's body over ONE page stream and the
    token-major query block (a sub-block's [16 queries, 32 heads, 640] as
    [512, 640] with no relayout), no transpose or copy of q or of the
    output surrounds it, and the device operation's name says which
    kernel it is."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        paged_flash_prefill_packed_latent,
    )

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = paged_flash_prefill_packed_latent.lower(
        sds((1, t, 32, 640), jnp.bfloat16), sds((1, t, 1, 640), jnp.bfloat16),
        sds((8,), jnp.int32), sds((8, 1, NUM_SLOTS, 640), jnp.bfloat16),
        sds((8, 192), jnp.int32), sds((8,), jnp.int32), sds((1,), jnp.int32),
        block_size=BLOCK_SIZE, value_dim=512, scale=192 ** -0.5).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%paged_flash_prefill_packed_latent" in text
    assert "%paged_flash_decode" not in text      # an operation's name
    assert not re.search(r"= bf16\[[\d,]+\]\S* (copy|transpose)\(", text)
    assert compiled.out_info.shape == (1, t, 32, 512)


# (deployment, layers, the parent's temp_size_in_bytes of the windowed
# [8, 128] program: PR 38's tree gathered 3072 keys a row and held the
# float32 scores; measured at PR 39.)
LATENT_PREFILL_PROGRAMS = {
    "kanana-2-30b-a3b-d8": (8, 607_355_392),
    "xing4.0-29b-a4b-d7": (7, 631_235_072),
}


@pytest.mark.parametrize("name", list(LATENT_PREFILL_PROGRAMS))
def test_latent_prefill_programs_hold_the_flash_kernel_on_v5e(v5e, name):
    """The fullest prefill program of the two latent deployments (since PR
    48 the packed row of 1024 tokens), lowered
    for a v5e as the engine lowers it: ONE family a (rows, t), its chunk
    attends through the latent flash kernel over the pool
    (``prefill_attn`` "pallas"), the pool is written in place, nothing of
    a window's shape (3072 keys a row) is gathered, no float32 tensor of
    the scores' shape exists, and its temporaries are far below the
    parent's."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.attention import prefill_attn_path
    from production_stack_tpu.ops.kv_write import pool_copies

    layers, parent_temp = LATENT_PREFILL_PROGRAMS[name]
    r = _deployment_runner(v5e, name)
    assert r.prefill_reads_pool and r.kv_pools == 1
    assert r.kv_k.shape[0] == layers and r.kv_value_dim == 512
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    assert full_mb * 16 == 3072
    fams = [f for f in r.reachable_prefill_families() if f[1] == 1024]
    assert fams == [(1, 1024, full_mb, False)]
    compiled = r._lower_prefill(r._abstract_params(), *fams[0]).compile()
    text = compiled.as_text()
    assert prefill_attn_path(text) == "pallas"
    assert "%paged_flash_prefill_packed_latent" in text
    assert "%paged_flash_decode" not in text      # an operation's name
    assert pool_copies(text, [r.kv_k]) == []
    for dims in re.findall(r"[a-z]\w*\[([\d,]+)\]", text):
        shape = tuple(int(x) for x in dims.split(","))
        if shape == tuple(r.kv_k.shape):
            continue
        # A gathered window [.., 8 rows, 3072 keys, 640] or a score
        # tensor [.., 3072 keys]: neither is there.
        assert 3072 not in shape, shape
    assert compiled.memory_analysis().temp_size_in_bytes < parent_temp / 2


# What the RECTANGLE prefill programs lower to for a described v5e: sha256
# of the module's text without locations and without the Mosaic kernels'
# serialized bodies, which carry the checkout's path and line numbers (the
# bodies are held by the kernels' jaxprs, tests/test_chip_compile_dense.py).
# Over K/V rows PR 45's programs still (the parent of PR 46, which gave the
# dense deployments a second form of dispatch beside them); over latent rows
# PR 56's, which hands the rectangle to the packed body as a row whose
# segments begin at multiples of T (PR 45's: 5c5f75cc068c10e1,
# 22be0e6501ecf8fc, 6e63f654eef0fb27, fc8c9c7bdbc1342b). A PR that changes
# these programs on purpose writes the new digests here.
_PARENT_PREFILL_TEXT = {
    ("olmo-hybrid-7b-d16", 1, 128): "8d7ed2eace3103d5",
    ("olmo-hybrid-7b-d16", 16, 128): "75209d90425a943e",
    ("granite-4.0-h-micro", 1, 128): "7f5d94d2e2f99f3d",
    ("granite-4.0-h-micro", 16, 128): "808fe16c0d7e1615",
    ("lfm2-8b-a1b-d16", 1, 128): "09fdbfc9a3401f0c",
    ("lfm2-8b-a1b-d16", 8, 128): "940c596990f0cce9",
    ("kanana-2-30b-a3b-d8", 1, 128): "8be70ef43fb7c6cf",
    ("kanana-2-30b-a3b-d8", 8, 128): "97ec62b1ce951fc6",
    ("xing4.0-29b-a4b-d7", 1, 128): "5df9e2685214dcb4",
    ("xing4.0-29b-a4b-d7", 8, 128): "119938af3059c33b",
}


@pytest.mark.parametrize("name,rows,t", sorted(_PARENT_PREFILL_TEXT))
def test_rectangle_prefill_programs_lower_to_the_parents_text(v5e, name,
                                                              rows, t):
    """The state-keeping deployments run PR 45's prefill programs; whatever
    still dispatches rectangles over latent rows runs PR 56's: the narrowest
    and the widest family of each lowers for a v5e to the text pinned
    above, and holds its pool kind's kernel and no other."""
    r = _deployment_runner(v5e, name)
    if name not in ("olmo-hybrid-7b-d16", "granite-4.0-h-micro"):
        # The latent deployments' own dispatches are packed rows since PR
        # 48 and the short-convolution deployment's since PR 50; what is
        # held to the parent's text is the rectangle program a runner with
        # an adapter or a draft's ring a row still builds
        # (``prefill_packs`` false: the cached property, said for it).
        assert r.prefill_packs
        r.__dict__["prefill_packs"] = False
    assert not r.prefill_packs
    fams = r.reachable_prefill_families()
    fam = next(f for f in (fams[0], fams[-1]) if f[:2] == (rows, t))
    assert _prefill_text_digest(r, fam) == _PARENT_PREFILL_TEXT[name, rows, t]
    kernels = set(re.findall(
        r'kernel_name = "(paged_flash_prefill\w*)"',
        r._lower_prefill(r._abstract_params(), *fam).as_text()))
    assert kernels == {"paged_flash_prefill_packed_latent" if r.kv_pools == 1
                       else "paged_flash_prefill"}
