"""Compile for a DESCRIBED TPU v5e (no chip attached): learned sparse
selection over paged latent rows beside rings of latent rows
(dots3-note-prev-ep16): its dispatch programs and what they take of the
compile cache. A file of its own beside tests/test_chip_compile_latent.py
and tests/test_chip_compile_rings.py, whose families it joins, so that no
file of these takes more than its 200 s alone (ROADMAP D14) and ``--dist
loadfile`` hands it to another worker. tests/chip_compile_helpers.py says
how and why.
"""

import pytest
import jax
import jax.numpy as jnp

import json
import os

from tests.chip_compile_helpers import CONFIGS_DIR, _described_runner
from tests.chip_compile_helpers import (  # noqa: F401  (fixtures)
    v5e,
)



def _deployment_runner(v5e, name):
    """``chip_compile_helpers._deployment_runner`` with the row cap the
    deployment sets (``--max-prefill-seqs``)."""
    with open(os.path.join(CONFIGS_DIR, name, "deployment.json")) as f:
        flags = {x["flag"]: x["value"]
                 for x in json.load(f)["engine_flags"]}
    return _described_runner(
        v5e, os.path.join(CONFIGS_DIR, name),
        max_model_len=int(flags["--max-model-len"]),
        max_num_seqs=int(flags["--max-num-seqs"]),
        max_num_batched_tokens=int(flags["--max-num-batched-tokens"]),
        max_prefill_seqs=int(flags["--max-prefill-seqs"]),
        num_decode_steps=int(flags["--num-decode-steps"]),
        num_kv_blocks=int(flags["--num-kv-blocks"]))


# Instructions of a compiled dispatch program (ONE scan over the sparse
# layers with both kinds of attention under a ``cond``, the dense layer
# traced once beside it; a full layer's chunk holds its selection four
# times, a cut of the history each).
DOTS_INSTRUCTIONS = 14000


@pytest.mark.parametrize("program", ["decode-16x16", "prefill-1x2048",
                                     "prefill-1x128"])
def test_selected_latent_dispatch_programs_compile_in_place_for_v5e(
        v5e, program):
    """The decode program at the widest bucket and the longest and the
    shortest prefill rows of dots3-note-prev-ep16's envelope
    (deployment.json's flags, published widths, 16 of 256 experts of 9
    sparse layers beside the shared one, six sliding layers' rings of
    latent rows in the state slots, four full layers paged at 640 lanes
    with their index keys in the second pool at 128) compile for a v5e, fit
    its HBM beside 10.30 GB of weights, the 2.01 GB pools and the rings,
    and copy neither a pool nor an expert stack (the index key as the
    row's last tile made XLA lay the whole pool out again: 15 GB, this
    PR's first compile). A decode program GATHERS: the index pool by block
    (a cut of the history each) and 2048 latent rows a row; its only
    Mosaic kernels are the two grouped matmuls."""
    from production_stack_tpu.engine.runner import _bucket
    from production_stack_tpu.ops.kv_write import pool_copies

    r = _deployment_runner(v5e, "dots3-note-prev-ep16")
    assert r.kv_k.shape == (4, 1, 20480 * 16, 640)
    assert r.kv_v.shape == (4, 1, 20480 * 16, 128)
    assert [p.shape for p in r.state_pools] == [(17, 6, 1, 513, 1152)]
    assert r.prefill_reads_pool and not r.prefill_packs
    assert r.fwd_stats[-2:] == ("index_keys_visible", "index_keys_selected")
    assert r.ring_report() == {
        "window_layers": [2, 3, 4, 6, 7, 8],
        "ring": {"ring_c": [1, 513, 1088]},
        "index_topk": 2048, "index_key_lanes": 128,
        "experts_held": [0, 16], "experts_routed": 256}
    full_mb = _bucket(r.config.max_blocks_per_seq, 1,
                      r.config.max_blocks_per_seq)
    aparams = r._abstract_params()
    sparse = aparams["layers"]["sparse"]
    assert sparse["w_gate_up"].shape == (9, 16, 5120, 3072)
    assert sparse["w_router"].shape == (9, 5120, 256)
    assert sparse["w_router"].dtype == jnp.float32
    assert aparams["lm_head"].shape == (5120, 19008)
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
            sparse[k].shape, (9 * 16, *sparse[k].shape[2:]))]
    carried = [jax.ShapeDtypeStruct((rows, *p.shape[1:]), p.dtype)
               for p in r.state_pools] if decode else []
    assert pool_copies(
        text, [r.kv_k, r.kv_v, *r.state_pools, *carried, *experts]) == []
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "%paged_flash" not in text
    if decode:
        # The index pool by blocks of whole tiles, a cut of the history a
        # branch, and the selected rows.
        for blocks in (136, 272, 544, 1088):
            assert f"bf16[{rows},{blocks},16,128]" in text
        assert f"bf16[{rows},2048,640]" in text
    for scope in ("embed", "attn_proj", "attn_core", "attn_index",
                  "attn_select", "ring_attend", "ring_write", "ffn",
                  "moe_route", "moe_experts", "moe_gmm", "moe_shared",
                  "logits", "kv_write", "state_read", "state_write",
                  "sample"):
        assert f"/{scope}/" in text, scope
    instructions = sum(1 for ln in text.splitlines() if " = " in ln)
    assert instructions < DOTS_INSTRUCTIONS, instructions
    mem = compiled.memory_analysis()
    # Weights 10.30 GB, the two pools 2.01 GB and the rings' pool (0.12 GB
    # as laid out) are arguments; a program's temporaries fit beside them.
    assert 12.3e9 < mem.argument_size_in_bytes < 12.7e9, \
        mem.argument_size_in_bytes
    # (a rectangle of 16 rows would gather 16 rows' pages of a layer:
    # 2.7 GB; the deployment's row cap is 1)
    assert mem.temp_size_in_bytes < 2.0e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_programs_of_the_selected_latent_deployment_fit_the_compile_cache(
        v5e):
    """5 prefill rows (one sequence a dispatch) and the decode families of
    dots3-note-prev-ep16's envelope, counted before chip time (the chip
    machine caps a configuration's compile cache at 192 MiB: PERF.md
    section 6, PR 31 and PR 33)."""
    r = _deployment_runner(v5e, "dots3-note-prev-ep16")
    prefill = r.reachable_prefill_families()
    assert [f[:2] for f in prefill] == [
        (1, 128), (1, 256), (1, 512), (1, 1024), (1, 2048)]
    assert {f[3] for f in prefill} == {False}
    assert len(r.reachable_decode_families()) + len(prefill) <= 16
