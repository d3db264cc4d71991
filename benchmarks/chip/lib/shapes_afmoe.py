"""Operations and bytes of an AFMoE-shaped decoder (HF ``afmoe``: rotary GQA
layers whose queries see a bounded span of keys mixed with position-free
layers that see all of them, a gate on the attention output, four norms a
layer, leading dense FFNs, then sigmoid-routed experts beside a shared one,
an untied head) from its HF ``config.json`` alone: ``lib/shapes.py``'s
arithmetic for the architecture that file cannot count (it reckons every
layer a dense llama layer that reads every key) and ``lib/shapes_lfm.py``
cannot read (no span, no gate, no shared expert).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, rotary, softmax, sigmoid, top-k, the
sort of the (token, expert) pairs, activations, sampling -- so a share errs
low, never high. What is particular here:

  * a query at position p of a ``sliding_attention`` layer sees min(p + 1,
    ``sliding_window``) keys, of a ``full_attention`` layer p + 1: K/V bytes
    and attention FLOPs are those of the keys SEEN (``keys_seen``), not of
    the keys the one block table holds, and not of the superpage a kernel
    rounds a bound down to;
  * a step reads the routed experts that its rows CHOSE
    (``pstpu:moe_experts_touched_total`` / ``pstpu:moe_layer_calls_total``),
    never ``num_experts``; the shared expert is read every step;
  * the grouped matmuls' count is ``lib/shapes_lfm.py:moe_gmm``'s, which
    reads the same keys of a config (no third copy of that arithmetic).
"""

from typing import Dict, Iterable

from benchmarks.chip.lib import shapes_lfm

BF16, F32 = 2, 4
SLIDING = "sliding_attention"


def dims(cfg: dict) -> Dict[str, int]:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kinds = cfg["layer_types"]
    dense = cfg.get("num_dense_layers", 0)
    return {
        "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "head_dim": head_dim, "q": heads * head_dim,
        "kv": cfg.get("num_key_value_heads", heads) * head_dim,
        "span": cfg.get("sliding_window") or 0,
        "sliding": sum(k == SLIDING for k in kinds),
        "full": sum(k != SLIDING for k in kinds),
        "ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"],
        "shared": cfg.get("num_shared_experts", 0),
        "top_k": cfg["num_experts_per_tok"],
        "dense": dense, "sparse": cfg["num_hidden_layers"] - dense,
        "layers": cfg["num_hidden_layers"],
    }


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o and the output gate (the per-head norms aside)."""
    d = dims(cfg)
    return d["hidden"] * (2 * d["q"] + 2 * d["kv"]) + d["q"] * d["hidden"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["expert_ffn"]


def shared_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["shared"] * expert_params(cfg)


def router_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["hidden"] * d["experts"]


def sparse_ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["experts"] * expert_params(cfg) + shared_params(cfg) \
        + router_params(cfg)


def dense_ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["hidden"] * d["ffn"]


def embedding_params(cfg: dict) -> int:
    """The table and the untied head."""
    d = dims(cfg)
    return 2 * d["vocab"] * d["hidden"]


def small_params(cfg: dict) -> int:
    """What the matrices' count leaves aside: four norms a layer and the
    one behind the last, the per-head norms' two weights a layer, the
    router's bias."""
    d = dims(cfg)
    return (4 * d["layers"] + 1) * d["hidden"] \
        + d["layers"] * 2 * d["head_dim"] + d["sparse"] * d["experts"]


def matrix_params(cfg: dict) -> int:
    """Every matrix and the table: ISSUE 47's hand count."""
    d = dims(cfg)
    return (d["layers"] * attention_params(cfg)
            + d["sparse"] * sparse_ffn_params(cfg)
            + d["dense"] * dense_ffn_params(cfg)
            + embedding_params(cfg))


def param_count(cfg: dict) -> int:
    """Every parameter of the served tree."""
    return matrix_params(cfg) + small_params(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of every layer: what a token HOLDS in the pool."""
    d = dims(cfg)
    return 2 * d["layers"] * d["kv"] * BF16


def keys_seen(cfg: dict, context: float) -> float:
    """Keys a query behind ``context`` tokens sees (itself aside), summed
    over the layers: the span's worth in a sliding layer, all in a full
    one."""
    d = dims(cfg)
    return d["sliding"] * min(context, max(d["span"] - 1, 0)) \
        + d["full"] * context


def mean_keys_seen(cfg: dict, contexts: Iterable[float]) -> float:
    contexts = list(contexts)
    return sum(keys_seen(cfg, c) for c in contexts) / len(contexts)


def step_fixed_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads whatever its rows chose: every
    layer's attention, the dense layers' FFN, the shared experts, the
    routers (float32), the logits matrix (the embedding lookup reads a row
    a token, not the table)."""
    d = dims(cfg)
    bf16 = (d["layers"] * attention_params(cfg)
            + d["dense"] * dense_ffn_params(cfg)
            + d["sparse"] * shared_params(cfg)
            + d["vocab"] * d["hidden"])
    return bf16 * BF16 + d["sparse"] * router_params(cfg) * F32


def active_params(cfg: dict) -> int:
    """What one token multiplies through every layer and the head."""
    d = dims(cfg)
    return (d["layers"] * attention_params(cfg)
            + d["dense"] * dense_ffn_params(cfg)
            + d["sparse"] * (d["top_k"] * expert_params(cfg)
                             + shared_params(cfg) + router_params(cfg))
            + d["vocab"] * d["hidden"])


def decode_attention(cfg: dict, row_steps: float,
                     keys_a_row: float) -> Dict[str, float]:
    """The paged decode kernel's work over ``row_steps`` row-steps whose
    query sees ``keys_a_row`` keys summed over the layers (``keys_seen``):
    K and V of one KV-head group's width a key, read once; QK^T and PV."""
    d = dims(cfg)
    return {"flops": row_steps * keys_a_row * 4 * d["q"],
            "bytes": row_steps * keys_a_row * 2 * d["kv"] * BF16}


def decode_step(cfg: dict, rows: float, keys_a_row: float,
                experts_touched: float) -> Dict[str, float]:
    """One decode step of ``rows`` LIVE sequences whose queries each see
    ``keys_a_row`` keys summed over the layers and whose sparse layers each
    touched ``experts_touched`` distinct experts: the fixed weights once,
    the touched experts' matrices, each layer's K/V under its span, the
    step's own K/V written."""
    d = dims(cfg)
    attn = decode_attention(cfg, rows, keys_a_row)
    return {
        "flops": rows * 2 * active_params(cfg) + attn["flops"],
        "bytes": step_fixed_weight_bytes(cfg)
        + d["sparse"] * experts_touched * expert_params(cfg) * BF16
        + attn["bytes"] + rows * kv_bytes_per_token(cfg),
    }


def prefill_attention(cfg: dict, keys_in_span: float) -> Dict[str, float]:
    """The prefill kernel's products over queries that see ``keys_in_span``
    keys in all, summed over tokens and layers
    (``pstpu:attn_keys_in_span_total``'s count): QK^T and PV."""
    return {"flops": keys_in_span * 4 * dims(cfg)["q"]}


def moe_gmm(cfg: dict, calls: float, pairs: float,
            experts_touched: float) -> Dict[str, float]:
    """``lib/shapes_lfm.py:moe_gmm`` (it reads ``hidden_size``,
    ``moe_intermediate_size``: the same keys here)."""
    return shapes_lfm.moe_gmm(cfg, calls, pairs, experts_touched)


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts ``rows`` tokens touch if every token's choice were
    uniform and independent: E (1 - (1 - k/E)^rows)."""
    d = dims(cfg)
    return d["experts"] * (1.0 - (1.0 - d["top_k"] / d["experts"]) ** rows)
