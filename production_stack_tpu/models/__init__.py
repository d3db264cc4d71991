"""Model registry: arch name -> the module that is that architecture.

A module declares ``init_params``, ``forward``, ``compute_logits``, its HF
checkpoint maps (``HF_LAYER_MAP``, ``HF_TOP_MAP``, ``required_layer_leaves``,
``finish_params``), ``LORA_TARGETS``, ``PAGED_DECODE_VALIDATED``,
``position_bound`` and ``cache_specs`` (models/llama.py lists them with
their meaning). Nothing
outside this package asks for an architecture by name.
"""

from production_stack_tpu.models import (
    afmoe,
    deepseek_v3,
    dots3_note,
    granite_hybrid,
    lfm2_moe,
    llama,
    mimo_v2,
    olmo_hybrid,
    opt,
    phi4flash,
)
from production_stack_tpu.models.config import (
    LLAMA3_8B,
    NAMED_CONFIGS,
    OPT_125M,
    CacheSpecs,
    TINY_LLAMA,
    ModelConfig,
    resolve_model_config,
)

_ARCHS = {"llama": llama, "opt": opt, "olmo_hybrid": olmo_hybrid,
          "deepseek_v3": deepseek_v3, "granite_hybrid": granite_hybrid,
          "lfm2_moe": lfm2_moe, "afmoe": afmoe, "mimo_v2": mimo_v2,
          "phi4flash": phi4flash, "dots3_note": dots3_note}


def get_model(cfg: ModelConfig):
    if cfg.arch not in _ARCHS:
        raise ValueError(f"Unknown arch {cfg.arch!r}; available: {list(_ARCHS)}")
    return _ARCHS[cfg.arch]


def cache_specs(cfg: ModelConfig) -> CacheSpecs:
    """What the config's architecture caches per sequence, by layer kind."""
    return get_model(cfg).cache_specs(cfg)


__all__ = [
    "ModelConfig", "resolve_model_config", "get_model", "cache_specs",
    "NAMED_CONFIGS", "TINY_LLAMA", "OPT_125M", "LLAMA3_8B",
]
