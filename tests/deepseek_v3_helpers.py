"""What the DeepSeek-V3 test files share (tests/test_deepseek_v3*.py, and
tests/test_xing4_rows.py its packed-row comparison): the reference's import,
the tiny engine, and the comparison of a sequence's served log-probabilities
with the reference's. pytest collects nothing here.
"""

import os
import sys

import jax
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models.config import ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import deepseek_v3_ref as ref  # noqa: E402

TOL = 5e-5
TOP = 20


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "kv_lora_rank": mc.kv_lora_rank, "v_head_dim": mc.v_head_dim,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_norm_eps,
        "first_k_dense_replace": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "norm_topk_prob": mc.norm_topk_prob,
    }


def make_engine(**over) -> ServingEngine:
    cfg = dict(model="tiny-deepseek-v3", max_model_len=512,
               num_kv_blocks=128, num_decode_steps=8, dtype="float32",
               max_num_seqs=8, max_num_batched_tokens=64, max_prefill_seqs=8)
    cfg.update(over)
    return ServingEngine(EngineConfig(**cfg))


def prompt(n: int, salt: int):
    return [int(x) for x in np.random.default_rng(salt).integers(1, 512, n)]


def add(eng, name, tokens, max_tokens) -> Sequence:
    seq = Sequence(name, list(tokens), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True,
        logprobs=TOP))
    eng.scheduler.add_sequence(seq)
    return seq


def step(eng):
    batch = eng.scheduler.schedule()
    tokens, lps = eng.runner.execute(batch, 0)
    eng.scheduler.update_after_step(batch, tokens, lps)
    return batch


def drive(eng) -> list:
    batches = []
    while eng.scheduler.has_work():
        batches.append(step(eng))
    return batches


def worst(eng, seq, wrong=()) -> float:
    """Largest |log-probability difference| of a finished sequence's
    outputs against the reference over the same tokens."""
    tokens = seq.all_token_ids
    logits = ref.forward(eng.runner.params, hf_config(eng.model_config),
                         tokens[:-1], wrong)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    n_prompt = len(seq.prompt_token_ids)
    assert len(seq.output_logprobs) == len(seq.output_token_ids)
    diffs = []
    for i, (chosen, top) in enumerate(seq.output_logprobs):
        row = logp[n_prompt - 1 + i]
        diffs.append(chosen - row[seq.output_token_ids[i]])
        assert len(top) == TOP
        diffs += [lp - row[tok] for tok, lp in top]
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


def packed_row_against_rectangle(monkeypatch, tiny, module):
    """Five prompts through an engine whose prefill dispatches are packed
    rows over the latent pool (``prefill_packs``: the tiny model with heads
    enough to fill a sublane tile in float32, on the paged path) and through
    one made to dispatch rectangles: a prompt alone, then at once a prefix
    hit on it, one that crosses the budget and two short ones. The packed
    engine's log-probabilities are the reference's, and both engines serve
    the same tokens. ``module``: the test module's ``make_engine``, ``add``,
    ``drive``, ``worst`` (tests/test_xing4_rows.py calls this with its own)."""
    import dataclasses

    from production_stack_tpu.models import config as models_config

    name = tiny.name + "-8-heads"
    monkeypatch.setitem(
        models_config.NAMED_CONFIGS, name, dataclasses.replace(
            tiny, num_heads=8, num_kv_heads=8, name=name))
    shared = prompt(64, 80)
    served = {}
    for form in ("packed", "rectangle"):
        eng = module.make_engine(model=name, attn_impl="paged",
                                 max_num_batched_tokens=512)
        assert eng.runner.kv_pools == 1
        assert eng.runner.prefill_packs and eng.scheduler.prefill_packed
        assert {f[0] for f in eng.runner.reachable_prefill_families()} == {1}
        if form == "rectangle":
            eng.runner.__dict__["prefill_packs"] = False
            eng.scheduler.prefill_packed = False
        first = module.add(eng, "g0", shared + prompt(10, 81), 3)
        module.drive(eng)
        seqs = [first] + [
            module.add(eng, f"g{i + 1}", tokens, 5) for i, tokens in
            enumerate((shared + prompt(12, 82), prompt(470, 2), prompt(5, 3),
                       prompt(40, 4)))]
        prefills = [b for b in module.drive(eng) if b.kind == "prefill"]
        assert seqs[1].num_cached_tokens == 64
        assert all(b.packed for b in prefills) is (form == "packed")
        if form == "packed":
            assert max(len(b.seqs) for b in prefills) == 4
            # The prefix hit (history 64) lay in one row with first
            # chunks, and the long prompt crossed the budget.
            assert any(64 in b.chunk_starts and 0 in b.chunk_starts
                       for b in prefills)
            assert sum(seqs[2] in b.seqs for b in prefills) > 1
            for seq in seqs:
                assert module.worst(eng, seq) < TOL
        served[form] = [
            (seq.output_token_ids, [lp for lp, _ in seq.output_logprobs])
            for seq in seqs]
    for (toks_p, lps_p), (toks_r, lps_r) in zip(served["packed"],
                                                served["rectangle"]):
        assert toks_p == toks_r and len(toks_p) in (3, 5)
        np.testing.assert_allclose(lps_p, lps_r, atol=TOL, rtol=0)
