"""What the Phi-4-mini-flash test files share (tests/test_phi4flash*.py): the
reference's import, the tiny engines, and the comparison of a sequence's
served log-probabilities with the reference's. pytest collects nothing here.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import ServingEngine
from production_stack_tpu.models.config import TINY_PHI4FLASH, ModelConfig


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "reference"))
import phi4flash_ref as ref  # noqa: E402

TOL = 1e-3
CHUNK = 256         # make_engine's max_num_batched_tokens
W = TINY_PHI4FLASH.sliding_window
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "chip", "configs",
                          "phi-4-mini-flash")
F32 = jnp.float32


def hf_config(mc: ModelConfig) -> dict:
    """The HF config.json keys the reference reads, from a ModelConfig."""
    return {
        "model_type": "phi4flash", "mb_per_layer": 2,
        "hidden_size": mc.hidden_size, "num_hidden_layers": mc.num_layers,
        "intermediate_size": mc.intermediate_size,
        "vocab_size": mc.vocab_size,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "layer_norm_eps": mc.rms_norm_eps,
        "sliding_window": mc.sliding_window,
        "mamba_d_state": mc.mamba_d_state, "mamba_d_conv": mc.mamba_d_conv,
        "mamba_expand": mc.mamba_d_inner // mc.hidden_size,
        "mamba_dt_rank": mc.mamba_dt_rank,
        "tie_word_embeddings": True,
    }


def make_engine(model="tiny-phi4flash", **over) -> ServingEngine:
    cfg = dict(model=model, max_model_len=1024, num_kv_blocks=320,
               num_decode_steps=8, dtype="float32", max_num_seqs=8,
               max_num_batched_tokens=CHUNK, max_prefill_seqs=8)
    cfg.update(over)
    return ServingEngine(EngineConfig(**cfg))


def prompt(n: int, salt: int):
    return [int(x) for x in np.random.default_rng(salt).integers(1, 512, n)]
