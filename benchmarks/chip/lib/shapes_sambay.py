"""Operations and bytes of a SambaY decoder (``model_type: phi4flash``:
selective-scan layers with a float32 state and a conv window a sequence
alternating with window-attention layers that keep a per-sequence ring, ONE
full-attention layer that pages its keys and values, then gated memory
units alternating with cross-attention layers that read that one layer's
rows; a fused gated FFN in every layer, a tied head) from its HF
``config.json`` alone: ``lib/shapes.py``'s arithmetic for the architecture
that file cannot count (it reckons every layer a dense llama layer with its
own K/V).

Counted, as there: matrix products (2 FLOPs a multiply-add) and what must
cross HBM once. Not counted: norms, the convolution's few multiplies, gates,
softmax, the differential's subtraction and norm, activations, sampling --
so a share errs low, never high. The scan is ``6 N D`` operations a token
and layer (the decay's product and exponential, the state's multiply-add,
the contraction with C) on the VECTOR and transcendental units, whose peaks
``peaks.json`` does not hold: they are counted against the matrix unit's
peak, which is far above them, so the scan's shares are its BYTES' share and
err low where the vector unit is the bound. The same work whatever
implements it: a ring is counted at min(context, window) keys a row, the
shared layer's keys at the context once a READER (eight: the kernel reads
them again for every layer that attends to them).
"""

from typing import Dict

BF16, F32 = 2, 4


def dims(cfg: dict) -> Dict[str, int]:
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    rank = cfg.get("mamba_dt_rank", "auto")
    pairs = layers // 4
    return {
        "layers": layers, "hidden": hidden, "ffn": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "q": hidden,
        "kv": cfg.get("num_key_value_heads", heads) * (hidden // heads),
        "inner": cfg.get("mamba_expand", 2) * hidden,
        "n": cfg.get("mamba_d_state", 16),
        "conv_width": cfg.get("mamba_d_conv", 4),
        "rank": -(-hidden // 16) if rank == "auto" else rank,
        "window": cfg["sliding_window"],
        # Layers by kind: S6, window, full, memory unit, cross.
        "s6": pairs + 1, "ring": pairs, "full": 1, "gmu": pairs - 1,
        "cross": pairs - 1,
        # Layers that read the ONE paged layer's rows: itself and the
        # cross layers.
        "readers": pairs,
    }


def ffn_params(cfg: dict) -> int:
    """The fused gate | up in-projection, the out-projection, the norm."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["ffn"] + 2 * d["hidden"]


def s6_params(cfg: dict) -> Dict[str, int]:
    """An S6 mixer's parameters by what they are stored in: ``bf16``
    (W_in, the conv and its bias, W_x, W_dt, W_out, the norm) and ``f32``
    (A_log, D, b_dt)."""
    d = dims(cfg)
    di, n = d["inner"], d["n"]
    return {
        "bf16": d["hidden"] * 2 * di + di * (d["conv_width"] + 1)
        + di * (d["rank"] + 2 * n) + d["rank"] * di + di * d["hidden"]
        + 2 * d["hidden"],
        "f32": di * n + 2 * di,
    }


def self_attention_params(cfg: dict) -> Dict[str, int]:
    """W_qkv and W_o with their biases, the norm and the sub-norm (bf16);
    the four lambda vectors (f32)."""
    d = dims(cfg)
    head = d["hidden"] // cfg["num_attention_heads"]
    cols = d["q"] + 2 * d["kv"]
    return {"bf16": d["hidden"] * cols + cols + d["q"] * d["hidden"]
            + d["hidden"] + 2 * d["hidden"] + 2 * head,
            "f32": 4 * head}


def cross_attention_params(cfg: dict) -> Dict[str, int]:
    d = dims(cfg)
    head = d["hidden"] // cfg["num_attention_heads"]
    return {"bf16": 2 * (d["hidden"] * d["q"] + d["hidden"])
            + 2 * d["hidden"] + 2 * head,
            "f32": 4 * head}


def gmu_params(cfg: dict) -> int:
    d = dims(cfg)
    return 2 * d["hidden"] * d["inner"] + 2 * d["hidden"]


def mixer_params(cfg: dict) -> Dict[str, int]:
    """Every layer's mixer, summed, by storage."""
    d = dims(cfg)
    s6, att, cross = (s6_params(cfg), self_attention_params(cfg),
                      cross_attention_params(cfg))
    return {
        "bf16": d["s6"] * s6["bf16"] + (d["ring"] + 1) * att["bf16"]
        + d["cross"] * cross["bf16"] + d["gmu"] * gmu_params(cfg),
        "f32": d["s6"] * s6["f32"] + (d["ring"] + 1) * att["f32"]
        + d["cross"] * cross["f32"],
    }


def param_count(cfg: dict) -> int:
    """Every parameter of the served tree: the mixers, the FFNs, the table
    (the head is tied to it) and the final norm."""
    d = dims(cfg)
    return (sum(mixer_params(cfg).values()) + d["layers"] * ffn_params(cfg)
            + d["vocab"] * d["hidden"] + 2 * d["hidden"])


def step_weight_bytes(cfg: dict) -> int:
    """Weights one program step reads: every layer and the logits matrix
    (the embedding lookup reads a row per token, not the table)."""
    d = dims(cfg)
    m = mixer_params(cfg)
    return ((m["bf16"] + d["layers"] * ffn_params(cfg)
             + d["vocab"] * d["hidden"] + 2 * d["hidden"]) * BF16
            + m["f32"] * F32)


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of the ONE paged layer."""
    return 2 * dims(cfg)["kv"] * BF16


def ring_bytes_per_seq(cfg: dict) -> int:
    """The window layers' rings of a sequence, full."""
    d = dims(cfg)
    return d["ring"] * d["window"] * kv_bytes_per_token(cfg)


def s6_state_bytes_per_seq_layer(cfg: dict) -> int:
    d = dims(cfg)
    return d["n"] * d["inner"] * F32


def conv_bytes_per_seq_layer(cfg: dict) -> int:
    d = dims(cfg)
    return (d["conv_width"] - 1) * d["inner"] * BF16


def state_bytes_per_seq(cfg: dict) -> int:
    """What one sequence holds whole, whatever its length: the rings, the
    scans' states and the conv windows."""
    d = dims(cfg)
    return ring_bytes_per_seq(cfg) + d["s6"] * (
        s6_state_bytes_per_seq_layer(cfg) + conv_bytes_per_seq_layer(cfg))


def scan_flops_per_token_layer(cfg: dict) -> int:
    d = dims(cfg)
    return 6 * d["n"] * d["inner"]


def shared_kv_attend(cfg: dict, row_steps: float, context: float
                     ) -> Dict[str, float]:
    """The paged decode kernel's work over ``row_steps`` live row-steps at
    a mean ``context``: the ONE paged layer's keys and values read once a
    READER (the full layer and every cross layer), QK^T and PV of every
    query head over them (a packed head scores 2 d lanes and sums 2 d)."""
    d = dims(cfg)
    return {
        "flops": row_steps * d["readers"] * 8 * d["q"] * context,
        "bytes": row_steps * d["readers"] * context
        * kv_bytes_per_token(cfg),
    }


def ring_attend(cfg: dict, row_steps: float, context: float
                ) -> Dict[str, float]:
    """The window layers' decode attention: min(context, window) keys and
    values a layer read, one row of each written."""
    d = dims(cfg)
    held = min(context, d["window"])
    return {
        "flops": row_steps * d["ring"] * 8 * d["q"] * held,
        "bytes": row_steps * d["ring"] * (held + 1) * kv_bytes_per_token(cfg),
    }


def s6_step(cfg: dict, row_steps: float) -> Dict[str, float]:
    """The scan of ``row_steps`` live row-steps through every S6 layer: the
    state and the conv window read once and written once; dt, B, C, u and z
    in and y out (float32, a row of D_inner or N each)."""
    d = dims(cfg)
    per = 2 * (s6_state_bytes_per_seq_layer(cfg)
               + conv_bytes_per_seq_layer(cfg)) \
        + (4 * d["inner"] + 2 * d["n"]) * F32
    return {"flops": row_steps * d["s6"] * scan_flops_per_token_layer(cfg),
            "bytes": row_steps * d["s6"] * per}


def s6_chunk(cfg: dict, tokens: float) -> Dict[str, float]:
    """The scan over ``tokens`` prompt tokens through every S6 layer: dt
    and u in and y out, B and C, float32, once a token; the state of a row
    crosses once a CHUNK and is not counted (it errs low)."""
    d = dims(cfg)
    return {
        "flops": tokens * d["s6"] * scan_flops_per_token_layer(cfg),
        "bytes": tokens * d["s6"] * (3 * d["inner"] + 2 * d["n"]) * F32,
    }


def decode_step(cfg: dict, rows: float, context: float) -> Dict[str, float]:
    """One decode step of ``rows`` LIVE sequences at a mean ``context``:
    the weights once; a row's paged keys once a reader, its rings, its
    scan states and conv windows read and written; the new token's rows
    written."""
    d = dims(cfg)
    m = mixer_params(cfg)
    matrices = m["bf16"] + m["f32"] + d["layers"] * ffn_params(cfg)
    shared = shared_kv_attend(cfg, rows, context)
    ring = ring_attend(cfg, rows, context)
    scan = s6_step(cfg, rows)
    return {
        "flops": rows * (2 * matrices + 2 * d["vocab"] * d["hidden"])
        + shared["flops"] + ring["flops"] + scan["flops"],
        "bytes": step_weight_bytes(cfg) + shared["bytes"] + ring["bytes"]
        + rows * d["s6"] * 2 * (s6_state_bytes_per_seq_layer(cfg)
                                + conv_bytes_per_seq_layer(cfg))
        + rows * kv_bytes_per_token(cfg),
    }
