"""What the LFM2-MoE test files share (tests/test_lfm2_moe*.py, beside
tests/test_lfm2_moe_ops.py): the reference's import, the tiny engines, and the
comparison of a sequence's served log-probabilities with the reference's.
pytest collects nothing here.
"""

import dataclasses
import json
import os
import sys

import jax
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models.config import (
    LFM2_LAYER_TYPES,
    TINY_LFM2_MOE,
    ModelConfig,
)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import lfm2_moe_ref as ref  # noqa: E402

TOL = 2e-3
TOP = 20
CHUNK = 64          # make_engine's max_num_batched_tokens
CUT = os.path.join(ROOT, "benchmarks", "chip", "configs",
                   "lfm2-8b-a1b-d16", "config.json")
# The cut's 16 entries (four whole periods) beside the published 24.
TINY_CUT = dataclasses.replace(
    TINY_LFM2_MOE, num_layers=16, layer_types=LFM2_LAYER_TYPES[:16],
    name="tiny-lfm2-moe-d16")


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "layer_types": list(mc.layer_types),
        "num_dense_layers": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
    }


def make_engine(model="tiny-lfm2-moe", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=512, num_kv_blocks=128,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=CHUNK, max_prefill_seqs=8)
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


def step(eng, edit=None):
    """One dispatch, synchronously: schedule, (edit), run, apply."""
    batch = eng.scheduler.schedule()
    if edit is not None:
        edit(batch)
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
    mc = eng.model_config
    tokens = seq.all_token_ids
    logits = ref.forward(eng.runner.params, hf_config(mc), tokens[:-1],
                         wrong, chunk=CHUNK)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    n_prompt = len(seq.prompt_token_ids)
    assert len(seq.output_logprobs) == len(seq.output_token_ids)
    diffs = []
    for i, (chosen, top) in enumerate(seq.output_logprobs):
        row = logp[n_prompt - 1 + i]
        diffs.append(chosen - row[seq.output_token_ids[i]])
        assert len(top) == TOP
        diffs += [lp - row[tok] for tok, lp in top]
    # A reference that overflowed (a wrong model may) is as far as can be.
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))


# ---- config.json: what is read, what is refused ------------------------------
def cut() -> dict:
    with open(CUT) as f:
        return json.load(f)
