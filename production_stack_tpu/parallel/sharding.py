"""Parameter / KV-pool sharding rules (Megatron-style TP, GSPMD execution).

Column-parallel projections shard their OUTPUT dim over "tp"; row-parallel
projections shard their INPUT dim; XLA's sharding propagation then keeps
attention fully head-local and inserts one reduce(-scatter)/all-gather pair
per block, riding ICI. A dim that doesn't divide the axis size falls back to
replication (matters for GQA when kv_heads < tp).
"""

from typing import Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.parallel.mesh import AXIS_TP


def _ns(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def _shard_if_divisible(mesh: Mesh, dim_size: int, spec_tuple) -> NamedSharding:
    tp = mesh.shape[AXIS_TP]
    if dim_size % tp != 0:
        spec_tuple = tuple(None if s == AXIS_TP else s for s in spec_tuple)
    return _ns(mesh, *spec_tuple)


def param_shardings(cfg: ModelConfig, mesh: Mesh, params: Dict) -> Dict:
    """Build a NamedSharding pytree matching the model's param structure.

    Works for both model families because it keys on leaf NAMES:
    column-parallel = {wq, wk, wv, w_gate, w_up, fc1} (+ their biases),
    row-parallel = {wo, w_down, fc2}; everything else replicated except the
    embedding tables, which shard the hidden dim.
    """
    d = cfg.hidden_size
    rep = _ns(mesh)

    col = {"wq", "wk", "wv", "w_gate", "w_up", "fc1"}
    col_bias = {"bq", "bk", "bv", "fc1_b"}
    row = {"wo", "w_down", "fc2"}

    def layer_leaf(name: str, leaf: jax.Array) -> NamedSharding:
        # Layer leaves carry a leading L axis.
        if name in col:
            return _shard_if_divisible(mesh, leaf.shape[-1], (None, None, AXIS_TP))
        if name in col_bias:
            return _shard_if_divisible(mesh, leaf.shape[-1], (None, AXIS_TP))
        if name in row:
            return _shard_if_divisible(mesh, leaf.shape[-2], (None, AXIS_TP, None))
        return rep

    out: Dict = {}
    for key, leaf in params.items():
        if key == "layers":
            # Leaves stacked on L, or one such dict per layer kind.
            out["layers"] = {
                n: ({m: layer_leaf(m, x) for m, x in l.items()}
                    if isinstance(l, dict) else layer_leaf(n, l))
                for n, l in leaf.items()
            }
        elif key in ("embed", "pos_embed"):
            out[key] = _shard_if_divisible(mesh, d, (None, AXIS_TP))
        elif key == "lm_head":
            out[key] = _shard_if_divisible(mesh, leaf.shape[-1], (None, AXIS_TP))
        else:
            out[key] = rep
    return out


def kv_pool_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """KV pools [L, Hkv, num_slots, Dh]: shard kv heads over tp (matches the
    head-sharded q/k/v activations, so paged attention needs no collectives).
    """
    return _shard_if_divisible(
        mesh, cfg.num_kv_heads, (None, AXIS_TP, None, None)
    )


def kv_scale_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """Per-slot dequant scale pools [L, Hkv, num_slots] for int8 KV caches
    (--kv-cache-dtype int8): kv-head-sharded exactly like the payload pools
    so each tp shard dequantizes its local heads with local scales."""
    return _shard_if_divisible(
        mesh, cfg.num_kv_heads, (None, AXIS_TP, None)
    )
