"""Operations and bytes of a MiMo-V2-shaped decoder (HF ``mimo_v2``: full
attention layers that page their keys mixed with window layers that keep a
per-sequence ring of ``sliding_window`` keys, the two kinds with different KV
head counts, keys wider than values, one leading dense FFN, then
sigmoid-routed experts of which THIS CHIP holds a share, an untied head over
a slice of the vocabulary) from its ``config.json`` alone: the arithmetic
``lib/shapes.py`` cannot count (it reckons every layer a dense llama layer
that reads every key and every expert) and ``lib/shapes_afmoe.py`` cannot
read (one KV head count, one head width, every expert here, every key held).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, rotary, softmax, sigmoid, top-k, the
sort of the (token, expert) pairs, activations, sampling, and every lane of
padding (a paged row's 256 lanes for 192 + 128 of payload, a ring's 256 for
192) -- so a share errs low, never high. What is particular here:

  * ``n_routed_experts`` is the count HELD here and ``ep_size`` the chips
    that share a layer: the router is ``n_routed_experts * ep_size`` wide,
    a token's ``num_experts_per_tok`` choices fall here with probability
    1 / ``ep_size`` each, and a step reads the held experts its rows CHOSE
    (``pstpu:moe_experts_touched_total`` / ``pstpu:moe_layer_calls_total``);
  * a decode query of a full layer reads its context's keys (192 lanes) and
    values (128) of ``num_key_value_heads`` heads; of a window layer the
    ring's min(context, ``sliding_window``) keys and values of
    ``swa_num_key_value_heads`` heads, and writes one row of it;
  * ``vocab_size`` is the slice served.
"""

from typing import Dict

BF16, F32 = 2, 4


def dims(cfg: dict) -> Dict[str, int]:
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    heads = cfg["num_attention_heads"]
    return {
        "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": heads, "dk": cfg["head_dim"], "dv": cfg["v_head_dim"],
        "kv_full": cfg["num_key_value_heads"],
        "kv_window": cfg.get("swa_num_key_value_heads",
                             cfg["num_key_value_heads"]),
        "window": cfg["sliding_window"],
        "full": sum(p == 0 for p in pattern),
        "windowed": sum(p == 1 for p in pattern),
        "ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "held": cfg["n_routed_experts"],
        "ep_size": cfg.get("ep_size", 1),
        "experts": cfg["n_routed_experts"] * cfg.get("ep_size", 1),
        "top_k": cfg["num_experts_per_tok"],
        "dense": sum(f == 0 for f in freq),
        "sparse": sum(f == 1 for f in freq),
        "layers": cfg["num_hidden_layers"],
    }


def attention_params(cfg: dict, kv_heads: int) -> int:
    """W_q, W_k, W_v, W_o of a layer with ``kv_heads`` KV heads."""
    d = dims(cfg)
    return d["hidden"] * (d["heads"] * d["dk"] + kv_heads * (d["dk"]
                                                            + d["dv"])) \
        + d["heads"] * d["dv"] * d["hidden"]


def all_attention_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["full"] * attention_params(cfg, d["kv_full"]) \
        + d["windowed"] * attention_params(cfg, d["kv_window"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["expert_ffn"]


def router_params(cfg: dict) -> int:
    """The router's WHOLE width, whatever share of the experts is here."""
    d = dims(cfg)
    return d["hidden"] * d["experts"]


def dense_ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["hidden"] * d["ffn"]


def embedding_params(cfg: dict) -> int:
    """The table's and the untied head's slices."""
    d = dims(cfg)
    return 2 * d["vocab"] * d["hidden"]


def small_params(cfg: dict) -> int:
    """What the matrices' count leaves aside: two norms a layer and the one
    behind the last, a window layer's sinks, the router's bias."""
    d = dims(cfg)
    return (2 * d["layers"] + 1) * d["hidden"] \
        + d["windowed"] * d["heads"] + d["sparse"] * d["experts"]


def matrix_params(cfg: dict) -> int:
    """Every matrix held here and the table's slice: ISSUE 52's hand
    count."""
    d = dims(cfg)
    return (all_attention_params(cfg)
            + d["sparse"] * (d["held"] * expert_params(cfg)
                             + router_params(cfg))
            + d["dense"] * dense_ffn_params(cfg)
            + embedding_params(cfg))


def param_count(cfg: dict) -> int:
    """Every parameter of the served tree."""
    return matrix_params(cfg) + small_params(cfg)


def paged_bytes_per_token(cfg: dict) -> int:
    """Keys and values a token's full layers must keep: PAYLOAD (the pool
    pads each to whole 128-lane tiles of one width)."""
    d = dims(cfg)
    return d["full"] * d["kv_full"] * (d["dk"] + d["dv"]) * BF16


def ring_row_bytes(cfg: dict) -> int:
    """One position of one window layer's ring: keys and values."""
    d = dims(cfg)
    return d["kv_window"] * (d["dk"] + d["dv"]) * BF16


def ring_bytes_per_seq(cfg: dict) -> int:
    """What a sequence's window layers keep whole: PAYLOAD."""
    d = dims(cfg)
    return d["windowed"] * d["window"] * ring_row_bytes(cfg)


def pool_bytes_per_token_if_paged(cfg: dict) -> int:
    """What a token would keep if every layer paged every key (payload):
    the ONE pool of ``trinity-mini-d8``'s kind."""
    d = dims(cfg)
    return paged_bytes_per_token(cfg) + d["windowed"] * ring_row_bytes(cfg)


def step_fixed_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads whatever its rows chose: every
    layer's attention, the dense layers' FFN, the routers (float32), the
    head's slice (the embedding lookup reads a row a token)."""
    d = dims(cfg)
    bf16 = (all_attention_params(cfg) + d["dense"] * dense_ffn_params(cfg)
            + d["vocab"] * d["hidden"])
    return bf16 * BF16 + d["sparse"] * router_params(cfg) * F32


def active_params(cfg: dict) -> float:
    """What one token multiplies HERE through every layer and the head: of
    its ``top_k`` experts a layer, 1 / ``ep_size`` are held here."""
    d = dims(cfg)
    return (all_attention_params(cfg) + d["dense"] * dense_ffn_params(cfg)
            + d["sparse"] * (d["top_k"] / d["ep_size"] * expert_params(cfg)
                             + router_params(cfg))
            + d["vocab"] * d["hidden"])


def ring_attend(cfg: dict, row_steps: float, context: float
                ) -> Dict[str, float]:
    """The window layers' decode statement over ``row_steps`` live
    row-steps at a mean ``context``: each layer reads the ring's
    min(context, window) keys and values once and writes one row; QK^T and
    PV over them."""
    d = dims(cfg)
    keys = min(context, d["window"])
    return {
        "flops": row_steps * d["windowed"] * keys * 2 * d["heads"]
        * (d["dk"] + d["dv"]),
        "bytes": row_steps * d["windowed"] * (keys + 1)
        * ring_row_bytes(cfg),
    }


def full_attend(cfg: dict, row_steps: float, context: float
                ) -> Dict[str, float]:
    """The full layers' decode attention: the context's keys and values
    (payload) read once, the step's own written."""
    d = dims(cfg)
    return {
        "flops": row_steps * d["full"] * context * 2 * d["heads"]
        * (d["dk"] + d["dv"]),
        "bytes": row_steps * (context + 1) * paged_bytes_per_token(cfg),
    }


def decode_step(cfg: dict, rows: float, context: float,
                experts_touched: float) -> Dict[str, float]:
    """One decode step of ``rows`` LIVE sequences at a mean ``context``
    whose sparse layers each touched ``experts_touched`` distinct experts
    OF THE HELD: the fixed weights once, the touched experts' matrices,
    the full layers' keys and values at the context, the rings read and
    one row of each written."""
    d = dims(cfg)
    ring, full = ring_attend(cfg, rows, context), \
        full_attend(cfg, rows, context)
    return {
        "flops": rows * 2 * active_params(cfg) + ring["flops"]
        + full["flops"],
        "bytes": step_fixed_weight_bytes(cfg)
        + d["sparse"] * experts_touched * expert_params(cfg) * BF16
        + ring["bytes"] + full["bytes"],
    }


def moe_gmm(cfg: dict, calls: float, pairs: float,
            experts_touched: float) -> Dict[str, float]:
    """The grouped matmuls (gate and up as one, then down) of ``calls``
    sparse-layer calls that computed ``pairs`` (token, expert) pairs HERE
    in all and touched ``experts_touched`` distinct held experts a call
    (``lib/shapes_lfm.py:moe_gmm``'s count)."""
    d = dims(cfg)
    f, h = d["expert_ffn"], d["hidden"]
    return {
        "flops": pairs * 2 * expert_params(cfg),
        "bytes": calls * experts_touched * expert_params(cfg) * BF16
        + pairs * ((h + f) * BF16 + (2 * f + h) * F32),
    }


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct HELD experts ``rows`` tokens touch if every token's choice
    were uniform and independent over the router's whole width."""
    d = dims(cfg)
    return d["held"] * (1.0 - (1.0 - d["top_k"] / d["experts"]) ** rows)
