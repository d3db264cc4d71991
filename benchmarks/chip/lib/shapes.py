"""Operations and bytes a dense llama-shaped decoder needs, from its HF
``config.json`` alone. The yardstick's arithmetic: a roofline share is
(least time for these at the device's peaks) / (device time in the trace).

Counted: the matrix multiplications (2 FLOPs per multiply-add), attention's
QK^T and PV over the context, the logits. Not counted: norms, rotary,
softmax, activations, sampling (a few percent at these widths) -- so a share
errs low, never high. Bytes are what must cross HBM once: every weight read
once per program step, the context's keys and values read once per query
row, new keys and values written once."""

from typing import Dict

BF16 = 2


def dims(cfg: dict) -> Dict[str, int]:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kv_heads = cfg.get("num_key_value_heads", heads)
    return {
        "layers": cfg["num_hidden_layers"], "hidden": cfg["hidden_size"],
        "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "q": heads * head_dim, "kv": kv_heads * head_dim,
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one layer: q, k, v, o and the gated FFN."""
    d = dims(cfg)
    return (d["hidden"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["hidden"]
            + 3 * d["hidden"] * d["ffn"])


def param_count(cfg: dict) -> int:
    d = dims(cfg)
    embed = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)
    return d["layers"] * layer_params(cfg) + embed


def kv_bytes_per_token(cfg: dict) -> int:
    d = dims(cfg)
    return 2 * d["layers"] * d["kv"] * BF16


def step_weight_bytes(cfg: dict) -> int:
    """Weights one program step reads: every layer and the logits matrix
    (the embedding lookup reads a row per token, not the table)."""
    d = dims(cfg)
    return (d["layers"] * layer_params(cfg) + d["vocab"] * d["hidden"]) * BF16


def decode_step(cfg: dict, rows: float, context: float) -> Dict[str, float]:
    """One decode step of ``rows`` sequences at a mean ``context``."""
    d = dims(cfg)
    flops = rows * (2 * d["layers"] * layer_params(cfg)
                    + 2 * d["vocab"] * d["hidden"]
                    + 4 * d["layers"] * d["q"] * context)
    byts = step_weight_bytes(cfg) + rows * (context + 1) * kv_bytes_per_token(cfg)
    return {"flops": flops, "bytes": byts}


def prefill(cfg: dict, new_tokens: float, context: float,
            rows: float) -> Dict[str, float]:
    """Prefill of ``new_tokens`` prompt tokens in all, attending a mean
    ``context`` (cached prefix included), with one logits row per
    sequence."""
    d = dims(cfg)
    flops = (new_tokens * (2 * d["layers"] * layer_params(cfg)
                           + 4 * d["layers"] * d["q"] * context)
             + rows * 2 * d["vocab"] * d["hidden"])
    return {"flops": flops}


def least_seconds(work: Dict[str, float], peak: dict) -> Dict[str, float]:
    """The roofline bound and which side sets it."""
    t_flops = work.get("flops", 0.0) / (peak["bf16_tflops"] * 1e12)
    t_bytes = work.get("bytes", 0.0) / (peak["hbm_gbps"] * 1e9)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
