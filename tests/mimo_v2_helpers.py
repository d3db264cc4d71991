"""What the MiMo-V2 test files share (tests/test_mimo_v2*.py): the reference's
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
from production_stack_tpu.models.config import TINY_MIMO_V2, ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import mimo_v2_ref as ref  # noqa: E402

TOL = 1e-3
TOP = 20
CHUNK = 256         # make_engine's max_num_batched_tokens
W = TINY_MIMO_V2.sliding_window
LENGTHS = (1, 127, 128, 129, 3 * 128 + 5)


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "model_type": "mimo_v2",
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "vocab_size": mc.vocab_size,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "swa_num_key_value_heads": mc.swa_num_kv_heads,
        "head_dim": mc.head_dim, "v_head_dim": mc.v_head_dim,
        "layernorm_epsilon": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "swa_rope_theta": mc.swa_rope_theta,
        # floor(head_dim x factor) to whole pairs is rotary_dim.
        "partial_rotary_factor": (mc.rotary_dim + 0.5) / mc.head_dim,
        "hybrid_layer_pattern": [int(t == "sliding_attention")
                                 for t in mc.layer_types],
        "moe_layer_freq": [int(i >= mc.first_k_dense_replace)
                           for i in range(mc.num_layers)],
        "sliding_window": mc.sliding_window,
        "attention_value_scale": mc.attention_value_scale,
        "add_swa_attention_sink_bias": mc.swa_attention_sink,
        "n_routed_experts": mc.n_routed_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "ep_size": mc.ep_size, "ep_rank": mc.ep_rank,
    }


def make_engine(model="tiny-mimo-v2", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=1024, num_kv_blocks=320,
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


def drive(eng) -> list:
    """Dispatches, synchronously, until nothing is left: schedule, run,
    apply."""
    batches = []
    while eng.scheduler.has_work():
        batch = eng.scheduler.schedule()
        tokens, lps = eng.runner.execute(batch, 0)
        eng.scheduler.update_after_step(batch, tokens, lps)
        batches.append(batch)
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
