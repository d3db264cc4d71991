"""What the Granite 4.0 hybrid test files share (tests/test_granite_hybrid*.py):
the reference's import, the tiny engine, and the comparison of a sequence's
served log-probabilities with the reference's. pytest collects nothing here.
"""

import os
import sys

import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.models.config import ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import granite_hybrid_ref as ref  # noqa: E402


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "rms_norm_eps": mc.rms_norm_eps,
        "layer_types": list(mc.layer_types),
        "mamba_n_heads": mc.mamba_n_heads, "mamba_d_head": mc.mamba_d_head,
        "mamba_d_state": mc.mamba_d_state,
        "mamba_conv_bias": mc.mamba_conv_bias,
        "embedding_multiplier": mc.embedding_multiplier,
        "attention_multiplier": mc.attention_multiplier,
        "residual_multiplier": mc.residual_multiplier,
        "logits_scaling": mc.logits_scaling,
    }


def make_engine(model="tiny-granite-hybrid", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=512, num_kv_blocks=128,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=64, max_prefill_seqs=8)
    cfg.update(over)
    return ServingEngine(EngineConfig(**cfg))


def prompt(n: int, salt: int):
    return [int(x) for x in np.random.default_rng(salt).integers(1, 512, n)]


def step(eng, edit=None):
    """One dispatch, synchronously: schedule, (edit), run, apply."""
    batch = eng.scheduler.schedule()
    if edit is not None:
        edit(batch)
    tokens, lps = eng.runner.execute(batch, 0)
    eng.scheduler.update_after_step(batch, tokens, lps)
    return batch
