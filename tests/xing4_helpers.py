"""What the Xing4.0 test files share (tests/test_xing4*.py): the reference's
import, the tiny engine, and the comparison of a sequence's served
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
from production_stack_tpu.models.config import ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import xing4_ref as ref  # noqa: E402

TOL = 5e-5
ROUTING_TOL = 1e-3
TOP = 20


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    ys = mc.rope_scaling
    return {
        "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "kv_lora_rank": mc.kv_lora_rank, "v_head_dim": mc.v_head_dim,
        "q_lora_rank": mc.q_lora_rank or None,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_norm_eps,
        "rope_scaling": None if ys is None else {
            "type": "yarn", "factor": ys.factor,
            "original_max_position_embeddings":
                ys.original_max_position_embeddings,
            "beta_fast": ys.beta_fast, "beta_slow": ys.beta_slow,
            "mscale": ys.mscale, "mscale_all_dim": ys.mscale_all_dim},
        "first_k_dense_replace": mc.first_k_dense_replace,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "norm_topk_prob": mc.norm_topk_prob,
        "hc_mult": mc.hc_mult, "hc_sinkhorn_iters": mc.hc_sinkhorn_iters,
        "hc_eps": mc.hc_eps, "mhc_h_res_clamp_min": mc.hc_res_clamp[0],
        "mhc_h_res_clamp_max": mc.hc_res_clamp[1],
    }


def make_engine(**over) -> ServingEngine:
    cfg = dict(model="tiny-xing4", max_model_len=512,
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
