from production_stack_tpu.ops.attention import (
    gather_window,
    paged_attention_xla,
    window_attention,
    write_kv_to_pool,
)

__all__ = [
    "gather_window", "paged_attention_xla", "window_attention",
    "write_kv_to_pool",
]
