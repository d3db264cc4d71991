"""Compile for a DESCRIBED TPU v5e (no chip attached): rings, scans and spans.
The bounded span (trinity-mini-d8), the window ring (mimo-v2.5-ep16) and the
selective scan beside rings (phi-4-mini-flash): their dispatch programs and
what they take of the compile cache. tests/chip_compile_helpers.py says how
and why.
"""

import pytest
import jax
import jax.numpy as jnp

from tests.chip_compile_helpers import (
    _deployment_runner,
    reads_its_pool_in_place,
)
from tests.chip_compile_helpers import (  # noqa: F401  (fixtures)
    v5e,
)


# ---- trinity-mini-d8: a span inside the paged kernels (PR 47)
# Instructions of a compiled dispatch program (two scans: the dense layers'
# and the sparse layers', ONE attention operator each whatever the list of
# layer kinds; kanana's 4003 and 4257 stand beside).
AFMOE_INSTRUCTIONS = 6000


@pytest.mark.parametrize("program", ["decode-16x32", "decode-8x32",
                                     "prefill-1x2048", "prefill-1x128"])
def test_bounded_span_dispatch_programs_compile_in_place_for_v5e(v5e,
                                                                 program):
    """The decode program at the widest and at the window's 8-row bucket and
    the longest and the shortest packed prefill program of trinity-mini-d8's
    envelope (deployment.json's flags, published widths, all 128 experts of
    6 sparse layers, a span of 2048 keys in 6 of 8 layers) compile for a
    v5e, fit its HBM beside 11.97 GB of weights and the 2.15 GB K/V pool,
    and copy neither a pool nor an expert stack. They hold the Mosaic
    kernels: the BOUNDED paged kernel in the dense layers' scan and in the
    sparse one (decode, or the packed flash prefill) and the two grouped
    matmuls."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.attention import prefill_attn_path
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "trinity-mini-d8")
    assert r.kv_k.shape == r.kv_v.shape == (8, 4, 8192 * 16, 128)
    assert r.prefill_reads_pool and r.prefill_packs and r.fwd_stats
    assert r.span_report() == {"span_layers": [0, 1, 2, 4, 5, 6],
                               "span": 2048}
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    sparse = aparams["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (6, 128, 2048, 2048)
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
    experts = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for k in (
        "w_gate_up", "we_down") for shape in (
            sparse[k].shape, (6 * 128, *sparse[k].shape[2:]))]
    assert pool_copies(text, [r.kv_k, *experts]) == []
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert ("%paged_flash_decode" in text) == decode
    # The computation's name, not the bare word: a decode program's table of
    # source frames may name the packed kernel's wrapper where a small jitted
    # helper it calls (same shapes: 8 rows, 8 segments) was first traced
    # under lfm2's packed programs above and its jaxpr, frames and all, is
    # cached.
    assert ("%paged_flash_prefill_packed" in text) == (not decode)
    if not decode:
        assert prefill_attn_path(text) == "pallas"
    for scope in ("embed", "attn_proj", "attn_core", "attn_span", "ffn",
                  "moe_route", "moe_experts", "moe_gmm", "moe_shared",
                  "logits", "kv_write", "sample"):
        assert f"/{scope}/" in text, scope
    instructions = sum(1 for ln in text.splitlines() if " = " in ln)
    assert instructions < AFMOE_INSTRUCTIONS, instructions
    mem = compiled.memory_analysis()
    # Weights 11.97 GB and K/V 2.15 GB are arguments; a program's
    # temporaries fit beside them.
    assert 14.0e9 < mem.argument_size_in_bytes < 14.3e9
    assert mem.temp_size_in_bytes < 0.9e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_programs_of_the_bounded_span_deployment_fit_the_compile_cache(v5e):
    """5 prefill programs (1 x {128..2048}) and the decode families of
    trinity-mini-d8's envelope, counted before chip time; the chip machine
    caps a configuration's compile cache at 192 MiB (PERF.md section 6,
    PR 31 and PR 33), and a program of this family serializes to a few MB
    (its executables measured on the chip: PERF.md section 6, PR 47)."""
    r = _deployment_runner(v5e, "trinity-mini-d8")
    prefill = r.reachable_prefill_families()
    assert [f[:2] for f in prefill] == [(1, t) for t in
                                        (128, 256, 512, 1024, 2048)]
    assert {f[3] for f in prefill} == {False}
    assert len(r.reachable_decode_families()) + len(prefill) <= 24


# ---- mimo-v2.5-ep16: a window ring in the state slots, a share of the
# experts (PR 52)
# Instructions of a compiled dispatch program (ONE scan over the sparse
# layers with both kinds of attention under a ``cond``, the dense layer
# traced once beside it).
MIMO_INSTRUCTIONS = 9000


@pytest.mark.parametrize("program", ["decode-32x32", "decode-8x32",
                                     "prefill-1x2048", "prefill-1x128",
                                     "prefill-16x128"])
def test_window_ring_dispatch_programs_compile_in_place_for_v5e(v5e,
                                                                program):
    """The decode program at the widest bucket and at 8 rows and three
    prefill rectangles of mimo-v2.5-ep16's envelope (deployment.json's
    flags, published widths, 16 of 256 experts of 11 sparse layers, nine
    window layers' rings in the state slots, three full layers paged at 256
    lanes) compile for a v5e, fit its HBM beside 11.83 GB of weights, the
    2.01 GB K/V pool and the rings, and copy neither a pool nor an expert
    stack. They hold the Mosaic kernels of the FULL layers (the paged
    decode kernel, or the flash prefill kernel, once in the dense layer and
    once in the scan) and the two grouped matmuls; a DECODE program holds a
    fifth, the window layers' step in place in the carried rings (PR 53:
    ``ring_step`` reads ``"pallas"``, its time under ``ring_attend``, and no
    carried ring is copied at the ``cond``s it stands between); a prefill
    program's window layers are XLA under their three scopes."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.attention import (
        prefill_attn_path,
        ring_step_path,
    )
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "mimo-v2.5-ep16")
    assert r.kv_k.shape == r.kv_v.shape == (3, 4, 10240 * 16, 256)
    # A key's 192 lanes STORED in a row of 256: what it took in HBM before.
    assert [p.shape for p in r.state_pools] == [
        (33, 9, 8, 128, 256), (33, 9, 8, 128, 128)]
    assert r.prefill_reads_pool and not r.prefill_packs
    assert r.fwd_stats[-1] == "assignments_elsewhere"
    assert r.ring_report() == {
        "window_layers": [1, 2, 3, 4, 6, 7, 8, 9, 10],
        "ring": {"ring_k": [8, 128, 192], "ring_v": [8, 128, 128]},
        "experts_held": [0, 16], "experts_routed": 256}
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    sparse = aparams["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (11, 16, 4096, 4096)
    assert sparse["w_router"].shape == (11, 4096, 256)
    assert sparse["w_router"].dtype == jnp.float32
    assert aparams["lm_head"].shape == (4096, 19072)
    decode = program.startswith("decode")
    rows, t = (int(x) for x in program.split("-")[1].split("x"))
    if decode:
        lowered = r._lower_decode(aparams, rows, full_mb, t, False)
    else:
        assert (rows, t, full_mb, False) in r.reachable_prefill_families()
        lowered = r._lower_prefill(aparams, rows, t, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    experts = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for k in (
        "w_gate_up", "we_down") for shape in (
            sparse[k].shape, (11 * 16, *sparse[k].shape[2:]))]
    carried = [jax.ShapeDtypeStruct((rows, *p.shape[1:]), p.dtype)
               for p in r.state_pools] if decode else []
    assert pool_copies(
        text, [r.kv_k, *r.state_pools, *carried, *experts]) == []
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (5 if decode else 4)
    assert ("%paged_flash_decode" in text) == decode
    assert ring_step_path(text) == ("pallas" if decode else None)
    if decode:
        # The kernel's time is booked where the statement's was.
        assert any("/ring_attend/" in ln and "ring_step_in_place" in ln
                   for ln in text.splitlines() if "tpu_custom_call" in ln)
    else:
        assert prefill_attn_path(text) == "pallas"
        reads_its_pool_in_place(text, r, rows)
    for scope in ("embed", "attn_proj", "attn_core", "ring_attend",
                  *(() if decode else ("attn_sink", "ring_write")), "ffn",
                  "moe_route", "moe_experts", "moe_gmm", "logits",
                  "kv_write", "state_read", "state_write", "sample"):
        assert f"/{scope}/" in text, scope
    instructions = sum(1 for ln in text.splitlines() if " = " in ln)
    assert instructions < MIMO_INSTRUCTIONS, instructions
    mem = compiled.memory_analysis()
    # Weights 11.83 GB, K/V 2.01 GB and the rings' pools (0.23 GB as laid
    # out) are arguments; a program's temporaries fit beside them.
    assert 13.9e9 < mem.argument_size_in_bytes < 14.3e9, \
        mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes < 1.2e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_programs_of_the_window_ring_deployment_fit_the_compile_cache(v5e):
    """8 prefill rectangles and the decode families of mimo-v2.5-ep16's
    envelope, counted before chip time (the chip machine caps a
    configuration's compile cache at 192 MiB: PERF.md section 6, PR 31 and
    PR 33)."""
    r = _deployment_runner(v5e, "mimo-v2.5-ep16")
    prefill = r.reachable_prefill_families()
    assert [f[:2] for f in prefill] == [
        (1, 128), (1, 256), (1, 512), (1, 1024), (1, 2048), (8, 128),
        (8, 256), (16, 128)]
    assert {f[3] for f in prefill} == {False}
    assert len(r.reachable_decode_families()) + len(prefill) <= 24


# ---- phi-4-mini-flash: a selective scan beside window rings in the state
# slots, ONE paged layer read by eight, a second half that caches nothing
# (PR 54)
# Instructions of a compiled dispatch program (a scan over the first half's
# (S6, window) pairs, the two layers between, a scan over the second half's
# (memory unit, cross) pairs).
SAMBAY_INSTRUCTIONS = 9000


@pytest.mark.parametrize("program", ["decode-48x32", "decode-8x32",
                                     "prefill-1x2048", "prefill-1x128",
                                     "prefill-16x128"])
def test_sambay_dispatch_programs_compile_in_place_for_v5e(v5e, program):
    """The decode program at the widest bucket and at 8 rows and three
    prefill rectangles of phi-4-mini-flash's envelope (deployment.json's
    flags, published widths, all 32 layers and 200064 rows of vocabulary)
    compile for a v5e, fit its HBM beside 7.70 GB of weights, the 1.34 GB
    K/V pool of ONE layer and 1.19 GB of state, and copy neither a pool nor
    a weight stack. A prefill program holds the selective scan as its
    Mosaic kernel, in both places an S6 layer stands, and no serial loop of
    XLA steps a token; the paged kernel stands twice (the full layer and
    the cross layers' scan). A DECODE program holds a third, the window
    layers' step in place in the carried rings (PR 55: 4 queries a KV row
    lie 8 sublanes a head in the kernel's scratch, a row's 10 heads of 512
    slots are one block of 2.5 MiB; ``ring_step`` reads ``"pallas"``, its
    time under ``ring_attend``, and no carried ring is copied around it)."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.attention import (
        prefill_attn_path,
        ring_step_path,
    )
    from production_stack_tpu.ops.kv_write import pool_copies
    from production_stack_tpu.ops.selective_scan import chunk_path

    r = _deployment_runner(v5e, "phi-4-mini-flash")
    assert r.kv_k.shape == r.kv_v.shape == (1, 10, 16384 * 16, 128)
    assert [p.shape for p in r.state_pools] == [
        (49, 8, 10, 512, 128), (49, 8, 10, 512, 128), (49, 9, 16, 5120),
        (49, 9, 120, 128)]
    assert r.state_pools[2].dtype == jnp.float32
    assert r.prefill_reads_pool and not r.prefill_packs
    assert r.ring_report()["window_layers"] == [1, 3, 5, 7, 9, 11, 13, 15]
    assert r.ring_report()["paged_layer_readers"] == [
        17, 19, 21, 23, 25, 27, 29, 31]
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    layers = aparams["layers"]
    assert layers["ffn"]["w_in"].shape == (32, 2560, 20480)
    assert layers["s6"]["a_log"].shape == (9, 16, 5120)
    assert layers["s6"]["a_log"].dtype == jnp.float32
    assert layers["attn"]["wqkv"].shape == (9, 2560, 5120)
    assert layers["cross"]["wqkv"].shape == (7, 2560, 2560)
    assert layers["gmu"]["in_proj"].shape == (7, 2560, 5120)
    assert aparams["embed"].shape == (200064, 2560)
    assert "lm_head" not in aparams
    decode = program.startswith("decode")
    rows, t = (int(x) for x in program.split("-")[1].split("x"))
    if decode:
        lowered = r._lower_decode(aparams, rows, full_mb, t, False)
    else:
        assert (rows, t, full_mb, False) in r.reachable_prefill_families()
        lowered = r._lower_prefill(aparams, rows, t, full_mb, False)
    compiled = lowered.compile()      # raises where HBM or VMEM overflow
    text = compiled.as_text()
    stacks = [jax.ShapeDtypeStruct(x.shape, x.dtype)
              for x in (layers["ffn"]["w_in"], layers["ffn"]["w_out"],
                        layers["s6"]["in_proj"], layers["attn"]["wqkv"],
                        aparams["embed"])]
    carried = [jax.ShapeDtypeStruct((rows, *p.shape[1:]), p.dtype)
               for p in r.state_pools] if decode else []
    assert pool_copies(
        text, [r.kv_k, *r.state_pools, *carried, *stacks]) == []
    # The paged kernel of the full layer and of the cross layers' scan; a
    # decode step's ring kernel in the first half's scan; a prefill chunk's
    # selective scan in the first half's scan and in layer 16.
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if decode else 4)
    assert ("%paged_flash_decode" in text) == decode
    assert chunk_path(text) == (None if decode else "pallas")
    assert ring_step_path(text) == ("pallas" if decode else None)
    if decode:
        # The kernel's time is booked where the statement's was.
        assert any("/ring_attend/" in ln and "ring_step_in_place" in ln
                   for ln in text.splitlines() if "tpu_custom_call" in ln)
    else:
        assert prefill_attn_path(text) == "pallas"
        reads_its_pool_in_place(text, r, rows)
    for scope in ("embed", "attn_proj", "attn_core", "s6_conv",
                  "s6_step" if decode else "s6_chunk", "ring_attend",
                  *(() if decode else ("ring_write",)), "diff_attn", "gmu",
                  "xdec_attend", "ffn", "logits", "kv_write", "state_read",
                  "state_write", "sample"):
        assert f"/{scope}/" in text, scope
    instructions = sum(1 for ln in text.splitlines() if " = " in ln)
    assert instructions < SAMBAY_INSTRUCTIONS, instructions
    mem = compiled.memory_analysis()
    # Weights 7.70 GB, K/V 1.34 GB and the state's pools 1.19 GB are
    # arguments; a program's temporaries (a decode program's carried rows,
    # 1.16 GB at 48) fit beside them.
    assert 10.1e9 < mem.argument_size_in_bytes < 10.5e9, \
        mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes < 3.0e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_programs_of_the_sambay_deployment_fit_the_compile_cache(v5e):
    """8 prefill rectangles and the decode families of phi-4-mini-flash's
    envelope, counted before chip time (the chip machine caps a
    configuration's compile cache at 192 MiB: PERF.md section 6, PR 31 and
    PR 33)."""
    r = _deployment_runner(v5e, "phi-4-mini-flash")
    prefill = r.reachable_prefill_families()
    assert [f[:2] for f in prefill] == [
        (1, 128), (1, 256), (1, 512), (1, 1024), (1, 2048), (8, 128),
        (8, 256), (16, 128)]
    assert {f[3] for f in prefill} == {False}
    assert len(r.reachable_decode_families()) + len(prefill) <= 28
