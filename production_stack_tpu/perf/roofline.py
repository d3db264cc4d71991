"""Decode HBM-bandwidth roofline accounting, shared by bench.py and the
live engine telemetry (docs/PERF.md).

Each fused decode step streams every weight byte once (amortized over the
whole batch) plus each row's live KV, so the AGGREGATE ceiling is
``PEAK_BW / (param_bytes / batch + kv_bytes_per_token * avg_ctx)``
tokens/sec — the honest denominator for a memory-bound batched decode
(SURVEY.md §6). ``bench.py`` computes it post hoc for a run's JSON line;
``ServingEngine.stats()`` computes it continuously against the rolling
dispatch window so a TPU slice reports its own roofline position as
``pstpu:live_hbm_bw_pct``.
"""

import os
from typing import Optional

# Peak HBM bandwidth per chip in GB/s, keyed by the ``device_kind`` JAX
# reports (public Cloud TPU spec sheets; v5e also in the on-chip-measurement
# guide). One table for the bench JSON line and the live engine gauges. A
# TPU kind that is not here is an ERROR unless the operator gives the peak
# (``--hbm-peak-gbps`` / $PSTPU_PEAK_HBM_GBS) — never a silent v5e default.
# Only "TPU v5 lite" has been read off an attached chip (chip_smoke.py).
HBM_PEAK_GBPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 819.0,    # v5e
    "TPU v5": 2765.0,        # v5p
    "TPU v6 lite": 1638.0,   # v6e
}


def peak_hbm_gbps(platform: str, device_kind: str,
                  override: Optional[float] = None) -> Optional[float]:
    """The roofline denominator for the device a run actually found.

    ``override`` (flag or $PSTPU_PEAK_HBM_GBS) wins. On the CPU backend
    there is no HBM: None, and every roofline share derived from it
    reports nothing instead of a share of some accelerator's peak."""
    if override is None:
        env = os.environ.get("PSTPU_PEAK_HBM_GBS")
        override = float(env) if env else None
    if override is not None:
        return float(override)
    if platform == "cpu":
        return None
    try:
        return HBM_PEAK_GBPS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak HBM bandwidth known for device kind {device_kind!r} "
            f"(known: {sorted(HBM_PEAK_GBPS_BY_DEVICE_KIND)}); pass "
            f"--hbm-peak-gbps or set PSTPU_PEAK_HBM_GBS"
        ) from None


def roofline_components(model: str, weight_dtype_bytes: float,
                        kv_cache_dtype: str, batch: int, avg_ctx: float,
                        peak_gbs: Optional[float],
                        tokens_per_target_step: float = 1.0,
                        num_chips: int = 1) -> dict:
    """Aggregate decode roofline from the model's analytic byte counts —
    WEIGHT bytes (compute dtype, amortized over the batch) split from KV
    bytes (the KV-CACHE storage dtype + per-slot scale overhead, per row):
    int8 KV halves the depth-dominant term, which is why the roofline
    itself roughly doubles at long context. Pure function (unit-pinned by
    tests/test_kv_quant.py).

    ``tokens_per_target_step``: speculative decoding's effective emitted
    tokens per target-model step (1 + acceptance_rate * N; docs/PERF.md
    round 8). Each target step still streams the same weight+KV bytes,
    but they amortize over that many emitted tokens, so the effective
    tokens/sec ceiling scales by the factor (the draft model's own bytes
    are deliberately excluded — the draft is sized to be negligible).

    ``num_chips``: devices the serving mesh occupies (tp x sp x dp). The
    aggregate HBM roofline scales with the chip count — each tp shard
    streams 1/tp of the weights and 1/tp of the KV per step over its OWN
    HBM, so the denominator's bytes-per-chip shrink by the chip count
    (equivalently: peak bandwidth multiplies). Without this the
    ``hbm_bw_pct`` of a tp>1 run would flatter itself against a
    single-chip ceiling (docs/PERF.md round 9)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models.config import resolve_model_config

    # No peak known (peak_hbm_gbps on the CPU backend): the byte components
    # still hold, the ceiling itself is None.
    peak = None if peak_gbs is None else peak_gbs * max(1, int(num_chips))
    mc = resolve_model_config(model)
    d, f, v = mc.hidden_size, mc.intermediate_size, mc.vocab_size
    dh, h, hkv, nl = mc.head_dim_, mc.num_heads, mc.num_kv_heads, mc.num_layers
    per_layer = d * (h * dh) + 2 * d * (hkv * dh) + (h * dh) * d + 3 * d * f
    embed = v * d * (1 if mc.tie_word_embeddings else 2)
    param_bytes = (nl * per_layer + embed) * weight_dtype_bytes
    kv_bytes_per_token = EngineConfig(
        kv_cache_dtype=kv_cache_dtype
    ).kv_cache_bytes_per_token(mc)
    step_bytes_per_row = param_bytes / batch + kv_bytes_per_token * avg_ctx
    factor = max(1.0, float(tokens_per_target_step))
    return {
        "kv_cache_dtype": kv_cache_dtype,
        "param_bytes": param_bytes,
        "kv_bytes_per_token": kv_bytes_per_token,
        "kv_bytes_per_step_per_row": kv_bytes_per_token * avg_ctx,
        "tokens_per_target_step": factor,
        "num_chips": max(1, int(num_chips)),
        "roofline_tok_s": (
            None if peak is None
            else peak * 1e9 / step_bytes_per_row * factor
        ),
    }
