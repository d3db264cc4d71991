"""What the dots3-note test files share (tests/test_dots3*.py): the
reference's import, the tiny engines, and the comparison of a sequence's
served log-probabilities with the reference's. pytest collects nothing here.
"""

import os
import sys

import jax
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Sequence
from production_stack_tpu.models.config import TINY_DOTS3, ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import dots3_ref as ref  # noqa: E402

TOL = 1e-3
TOP = 20
CHUNK = 128         # make_engine's max_num_batched_tokens
W = TINY_DOTS3.sliding_window          # 33
TOPK = TINY_DOTS3.index_topk           # 48
# The window's edge and the indexer's before, at and behind a chunk's; the
# longest is three chunks.
LENGTHS = (1, 32, 33, 34, 47, 49, 2 * 128 + 21)


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "model_type": "dots3_note",
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "vocab_size": mc.vocab_size, "rms_norm_eps": mc.rms_norm_eps,
        "layer_types": list(mc.layer_types),
        "first_k_dense_replace": mc.first_k_dense_replace,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_heads,
        "q_lora_rank": mc.q_lora_rank, "kv_lora_rank": mc.kv_lora_rank,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "v_head_dim": mc.v_head_dim, "rope_theta": mc.rope_theta,
        "rope_scaling": None,
        "index_n_heads": mc.index_n_heads,
        "index_head_dim": mc.index_head_dim, "index_topk": mc.index_topk,
        "swa_num_attention_heads": mc.swa_num_heads,
        "swa_num_key_value_heads": mc.swa_num_heads,
        "swa_q_lora_rank": mc.swa_q_lora_rank,
        "swa_kv_lora_rank": mc.swa_kv_lora_rank,
        "swa_qk_nope_head_dim": mc.swa_qk_nope_head_dim,
        "swa_qk_rope_head_dim": mc.swa_qk_rope_head_dim,
        "swa_v_head_dim": mc.swa_v_head_dim,
        "swa_rope_theta": mc.swa_rope_theta,
        "sliding_window_size": mc.sliding_window,
        "apply_mla_qkv_lora_rescale": mc.mla_lora_rescale,
        "attention_gate_type": "headwise",
        "swa_attention_gate_type": "headwise",
        "attention_bias": False, "hidden_act": "silu",
        "n_routed_experts": mc.n_routed_experts,
        "n_shared_experts": mc.n_shared_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "moe_layer_freq": 1, "tie_word_embeddings": False,
        "max_position_embeddings": mc.max_position_embeddings,
        "ep_size": mc.ep_size, "ep_rank": mc.ep_rank,
    }


def make_engine(model="tiny-dots3", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=512, num_kv_blocks=160,
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
