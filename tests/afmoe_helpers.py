"""What the AFMoE test files share (tests/test_afmoe*.py): the reference's
import, the tiny engines, and the comparison of a sequence's served
log-probabilities with the reference's. pytest collects nothing here.
"""

import os
import sys

import jax
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models.config import TINY_AFMOE, ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import afmoe_ref as ref  # noqa: E402

TOL = 1e-3
TOP = 20
CHUNK = 64          # make_engine's max_num_batched_tokens
SPAN = TINY_AFMOE.sliding_window


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "layer_types": list(mc.layer_types),
        "sliding_window": mc.sliding_window,
        "num_dense_layers": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "route_norm": mc.norm_topk_prob,
        "route_scale": mc.routed_scaling_factor,
        "mup_enabled": mc.embedding_multiplier != 1.0,
    }


def make_engine(model="tiny-afmoe", **over) -> ServingEngine:
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


def step(eng):
    """One dispatch, synchronously: schedule, run, apply."""
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
    # A reference that overflowed (a wrong model may) is as far as can be.
    return float(np.max(np.nan_to_num(np.abs(diffs), nan=np.inf)))
