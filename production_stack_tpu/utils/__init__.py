from production_stack_tpu.utils.logging import init_logger
from production_stack_tpu.utils.misc import (
    SingletonMeta,
    SingletonABCMeta,
    cdiv,
    pow2_bucket,
    prefill_rectangle,
    prefill_rectangles,
    prefill_row_cap,
    prefill_t_floor,
    round_up,
    window_mb_bucket,
    parse_comma_separated,
    parse_static_model_names,
    parse_static_urls,
    set_ulimit,
    validate_url,
)
from production_stack_tpu.utils.hashring import HashRing

__all__ = [
    "init_logger",
    "SingletonMeta",
    "SingletonABCMeta",
    "cdiv",
    "pow2_bucket",
    "prefill_rectangle",
    "prefill_rectangles",
    "prefill_row_cap",
    "prefill_t_floor",
    "round_up",
    "window_mb_bucket",
    "parse_comma_separated",
    "parse_static_model_names",
    "parse_static_urls",
    "set_ulimit",
    "validate_url",
    "HashRing",
]
