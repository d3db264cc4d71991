"""Operations and bytes of a dots3-note-shaped decoder (HF ``dots3_note``:
TWO kinds of latent attention, full layers that page ``[c | k_r]`` beside an
indexer's key and attend the ``index_topk`` keys it selects, sliding layers
that keep ``sliding_window_size`` latent rows a sequence in a ring; a
headwise gate; one leading dense FFN, then sigmoid-routed experts of which
THIS CHIP holds a share beside one shared expert; an untied head over a slice
of the vocabulary) from its ``config.json`` alone: the arithmetic
``lib/shapes.py`` cannot count and ``lib/shapes_moe.py`` /
``lib/shapes_mimo.py`` cannot read (every key read, one kind of latent
attention or none).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, rotary, softmax, sigmoid, ReLU, top-k,
the sort of the (token, expert) pairs, activations, sampling, and every lane
of padding (a paged row's 640 lanes for 576 of payload, a ring's 1152 for
1088) -- so a share errs low, never high. What is particular here:

  * a decode query of a FULL layer reads its context's L index keys
    (``index_head_dim`` lanes each) and the **min(L, index_topk)** latent
    rows its indexer selected, WHATEVER implements them (a gather, a mask
    over every row, a kernel in place): a program that reads every row is
    below its roofline by what it read for nothing;
  * of a SLIDING layer the ring's min(L, ``sliding_window_size``) latent
    rows, and writes one;
  * the attention is counted in its ABSORBED form (a head's query against
    the latent row's ``kv_lora_rank + qk_rope_head_dim`` lanes, the values
    its first ``kv_lora_rank``), as it is served;
  * ``n_routed_experts`` is the count HELD here and ``ep_size`` the chips
    that share a layer (``lib/shapes_mimo.py``'s reading); the ONE shared
    expert is read by every step; ``vocab_size`` is the slice served.
"""

from typing import Dict

BF16, F32 = 2, 4
FULL, SLIDING = "full_attention", "sliding_attention"


def dims(cfg: dict) -> Dict[str, int]:
    types = cfg["layer_types"]
    dense = cfg.get("first_k_dense_replace", 0)
    return {
        "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "full": sum(t == FULL for t in types),
        "sliding": sum(t == SLIDING for t in types),
        "dense": dense, "sparse": cfg["num_hidden_layers"] - dense,
        "window": cfg["sliding_window_size"],
        "topk": cfg["index_topk"], "index_heads": cfg["index_n_heads"],
        "index_dim": cfg["index_head_dim"],
        "ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "held": cfg["n_routed_experts"],
        "ep_size": cfg.get("ep_size", 1),
        "experts": cfg["n_routed_experts"] * cfg.get("ep_size", 1),
        "shared": cfg.get("n_shared_experts") or 0,
        "top_k": cfg["num_experts_per_tok"],
    }


def kind(cfg: dict, sliding: bool) -> Dict[str, int]:
    """A kind of layer's (heads, q rank, kv rank, nope, rope, v)."""
    p = "swa_" if sliding else ""
    return {"heads": cfg[p + "num_attention_heads"],
            "q_rank": cfg[p + "q_lora_rank"],
            "rank": cfg[p + "kv_lora_rank"],
            "nope": cfg[p + "qk_nope_head_dim"],
            "rope": cfg[p + "qk_rope_head_dim"],
            "v": cfg[p + "v_head_dim"]}


def latent_attention_params(cfg: dict, sliding: bool) -> int:
    """q_a, q_b, kv_a, kv_b, o and the headwise gate of one layer."""
    d, k = dims(cfg), kind(cfg, sliding)
    h = d["hidden"]
    return (h * k["q_rank"] + k["q_rank"] * k["heads"] * (k["nope"]
                                                          + k["rope"])
            + h * (k["rank"] + k["rope"])
            + k["rank"] * k["heads"] * (k["nope"] + k["v"])
            + k["heads"] * k["v"] * h + h * k["heads"])


def indexer_params(cfg: dict) -> int:
    """A full layer's indexer: wq_b, wk, weights_proj."""
    d = dims(cfg)
    return (cfg["q_lora_rank"] * d["index_heads"] * d["index_dim"]
            + d["hidden"] * d["index_dim"] + d["hidden"] * d["index_heads"])


def attention_params(cfg: dict, sliding: bool) -> int:
    return latent_attention_params(cfg, sliding) \
        + (0 if sliding else indexer_params(cfg))


def all_attention_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["full"] * attention_params(cfg, False) \
        + d["sliding"] * attention_params(cfg, True)


def expert_params(cfg: dict) -> int:
    """One expert, routed or shared: gate, up, down."""
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
    behind the last, the two latent norms a layer, the indexer's LayerNorm
    (weight and bias), the router's bias."""
    d = dims(cfg)
    latent = sum(n * (kind(cfg, s)["q_rank"] + kind(cfg, s)["rank"])
                 for n, s in ((d["full"], False), (d["sliding"], True)))
    return (2 * d["layers"] + 1) * d["hidden"] + latent \
        + d["full"] * 2 * d["index_dim"] + d["sparse"] * d["experts"]


def matrix_params(cfg: dict) -> int:
    """Every matrix held here and the table's slice: ISSUE 58's hand
    count."""
    d = dims(cfg)
    return (all_attention_params(cfg)
            + d["sparse"] * ((d["held"] + d["shared"]) * expert_params(cfg)
                             + router_params(cfg))
            + d["dense"] * dense_ffn_params(cfg)
            + embedding_params(cfg))


def param_count(cfg: dict) -> int:
    """Every parameter of the served tree."""
    return matrix_params(cfg) + small_params(cfg)


def latent_row_lanes(cfg: dict, sliding: bool) -> int:
    """PAYLOAD lanes of a kind's latent row: ``[c | k_r]``."""
    k = kind(cfg, sliding)
    return k["rank"] + k["rope"]


def _tiles(lanes: int) -> int:
    return -(-lanes // 128) * 128


def paged_bytes_per_token(cfg: dict, padded: bool = False) -> int:
    """What a token's full layers page: the latent row and the indexer's
    key; ``padded``: as the pool lays them (whole 128-lane tiles)."""
    d = dims(cfg)
    lanes = latent_row_lanes(cfg, False)
    return d["full"] * ((_tiles(lanes) if padded else lanes)
                        + d["index_dim"]) * BF16


def ring_row_bytes(cfg: dict, padded: bool = False) -> int:
    """One position of one sliding layer's ring."""
    lanes = latent_row_lanes(cfg, True)
    return (_tiles(lanes) if padded else lanes) * BF16


def ring_bytes_per_seq(cfg: dict, padded: bool = False) -> int:
    """What a sequence's sliding layers keep whole."""
    d = dims(cfg)
    return d["sliding"] * d["window"] * ring_row_bytes(cfg, padded)


def pool_bytes_per_token_if_paged(cfg: dict) -> int:
    """What a token would keep, as laid out, if every layer paged its
    latent row: the one pool of ``kanana-2-30b-a3b-d8``'s kind."""
    d = dims(cfg)
    return paged_bytes_per_token(cfg, True) \
        + d["sliding"] * ring_row_bytes(cfg, True)


def expanded_bytes_per_token(cfg: dict) -> int:
    """What a token's keys and values of heads would take, expanded."""
    d = dims(cfg)
    return sum(n * k["heads"] * (k["nope"] + k["rope"] + k["v"]) * BF16
               for n, k in ((d["full"], kind(cfg, False)),
                            (d["sliding"], kind(cfg, True))))


def step_fixed_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads whatever its rows chose: every
    layer's attention (indexers included), the dense layers' FFN, the
    shared experts, the routers (float32), the head's slice (the embedding
    lookup reads a row a token)."""
    d = dims(cfg)
    bf16 = (all_attention_params(cfg) + d["dense"] * dense_ffn_params(cfg)
            + d["sparse"] * d["shared"] * expert_params(cfg)
            + d["vocab"] * d["hidden"])
    return bf16 * BF16 + d["sparse"] * router_params(cfg) * F32


def active_params(cfg: dict) -> float:
    """What one token multiplies HERE through every layer and the head: of
    its ``top_k`` experts a layer, 1 / ``ep_size`` are held here."""
    d = dims(cfg)
    return (all_attention_params(cfg) + d["dense"] * dense_ffn_params(cfg)
            + d["sparse"] * ((d["top_k"] / d["ep_size"] + d["shared"])
                             * expert_params(cfg) + router_params(cfg))
            + d["vocab"] * d["hidden"])


def index_scan(cfg: dict, steps: float, row_steps: float, context: float
               ) -> Dict[str, float]:
    """The full layers' indexers over ``row_steps`` live row-steps of
    ``steps`` decode steps at a mean ``context``: the projections' weights
    once a step, every visible key's index key read once, a head's score
    against each."""
    d = dims(cfg)
    return {
        "flops": row_steps * d["full"] * (
            2 * indexer_params(cfg)
            + context * 2 * d["index_heads"] * d["index_dim"]),
        "bytes": steps * d["full"] * indexer_params(cfg) * BF16
        + row_steps * d["full"] * (context + 1) * d["index_dim"] * BF16,
    }


def selected_attend(cfg: dict, row_steps: float, context: float
                    ) -> Dict[str, float]:
    """The full layers' attention over what was SELECTED: min(context,
    index_topk) latent rows read (payload), the step's own written;
    absorbed QK^T over the row, PV over its compressed part."""
    d, k = dims(cfg), kind(cfg, False)
    keys = min(context, d["topk"])
    lanes = latent_row_lanes(cfg, False)
    return {
        "flops": row_steps * d["full"] * keys * 2 * k["heads"]
        * (lanes + k["rank"]),
        "bytes": row_steps * d["full"] * (keys + 1) * lanes * BF16,
    }


def ring_attend(cfg: dict, row_steps: float, context: float
                ) -> Dict[str, float]:
    """The sliding layers' decode statement: each reads the ring's
    min(context, window) latent rows once and writes one."""
    d, k = dims(cfg), kind(cfg, True)
    keys = min(context, d["window"])
    lanes = latent_row_lanes(cfg, True)
    return {
        "flops": row_steps * d["sliding"] * keys * 2 * k["heads"]
        * (lanes + k["rank"]),
        "bytes": row_steps * d["sliding"] * (keys + 1)
        * ring_row_bytes(cfg),
    }


def decode_step(cfg: dict, rows: float, context: float,
                experts_touched: float) -> Dict[str, float]:
    """One decode step of ``rows`` LIVE sequences at a mean ``context``
    whose sparse layers each touched ``experts_touched`` distinct experts
    OF THE HELD: the fixed weights once, the touched experts' matrices,
    the index keys of the context, the selected rows, the rings."""
    d = dims(cfg)
    index = index_scan(cfg, 0, rows, context)
    chosen = selected_attend(cfg, rows, context)
    ring = ring_attend(cfg, rows, context)
    return {
        "flops": rows * 2 * active_params(cfg)
        + rows * d["full"] * context * 2 * d["index_heads"] * d["index_dim"]
        + chosen["flops"] + ring["flops"],
        "bytes": step_fixed_weight_bytes(cfg)
        + d["sparse"] * experts_touched * expert_params(cfg) * BF16
        + index["bytes"] + chosen["bytes"] + ring["bytes"],
    }


def prefill_token_flops(cfg: dict, context: float) -> Dict[str, float]:
    """What one prompt token at position ``context`` costs here: its
    matrices, and its attention and index scores were only what is
    selected or windowed attended (ISSUE 58's 3.3 + 3-6 GFLOP)."""
    d = dims(cfg)
    full, slid = kind(cfg, False), kind(cfg, True)
    attn = d["full"] * (
        context * 2 * d["index_heads"] * d["index_dim"]
        + min(context, d["topk"]) * 2 * full["heads"]
        * (latent_row_lanes(cfg, False) + full["rank"])) \
        + d["sliding"] * min(context, d["window"]) * 2 * slid["heads"] \
        * (latent_row_lanes(cfg, True) + slid["rank"])
    return {"matrices": 2 * active_params(cfg), "attention": attn}


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
