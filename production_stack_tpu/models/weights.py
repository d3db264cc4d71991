"""HuggingFace checkpoint loading into the stacked-layer param tree.

The reference stack loads weights inside external vLLM images from a PVC/HF
cache (reference helm/templates/deployment-vllm-multi.yaml:144-150,
tutorials/03-load-model-from-pv.md). Here loading is in-repo and TPU-shaped:

  * Source: a LOCAL model directory (zero-egress environment) containing
    ``*.safetensors`` shards (preferred) or ``pytorch_model*.bin``.
  * Per-tensor streaming: each HF tensor is read, transposed to our
    [in, out] convention, written into a preallocated numpy stack
    ``[L, ...]``, and the completed stack is ``jax.device_put`` with its
    TP sharding immediately — peak host memory is one param stack, not
    the whole checkpoint.
"""

import os
import re
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from production_stack_tpu.models import get_model
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

_LAYER_RE = re.compile(r"\.(?:layers|decoder\.layers)\.(\d+)\.")
# ``mlp.experts.17.up_proj.weight``: an expert's tensor. A module maps it as
# ``mlp.experts.*.up_proj.weight``; its stack has an expert axis behind the
# layer's, [L, E, ...].
_EXPERT_RE = re.compile(r"^(.*\.experts\.)(\d+)(\..*)$")

def _iter_checkpoint_tensors(model_dir: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (hf_name, numpy array) streaming over checkpoint shards."""
    st_files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if st_files:
        from safetensors import safe_open

        for fname in st_files:
            with safe_open(os.path.join(model_dir, fname), framework="np") as f:
                for name in f.keys():
                    yield name, f.get_tensor(name)
        return
    bin_files = sorted(
        f for f in os.listdir(model_dir)
        if f.startswith("pytorch_model") and f.endswith(".bin")
    )
    if not bin_files:
        raise FileNotFoundError(
            f"No *.safetensors or pytorch_model*.bin in {model_dir}"
        )
    import torch

    for fname in bin_files:
        state = torch.load(
            os.path.join(model_dir, fname), map_location="cpu",
            weights_only=True,
        )
        for name, tensor in state.items():
            yield name, tensor.to(torch.float32).numpy()


def load_hf_params(
    cfg: ModelConfig,
    model_dir: str,
    dtype,
    shardings: Optional[Dict] = None,
) -> Dict:
    """Load an HF checkpoint into the stacked-layer tree of the config's
    model module (its ``HF_LAYER_MAP`` / ``HF_TOP_MAP`` name the leaves),
    device_put'ing each completed stack.

    ``shardings``: optional pytree (same structure as the result) of
    NamedShardings — each leaf goes straight to its TP shard placement.
    """
    import jax

    model = get_model(cfg)
    per_layer_map, top_map = model.HF_LAYER_MAP, model.HF_TOP_MAP
    nl = cfg.num_layers
    # A module with more than one kind of layer stacks its leaves by kind
    # and says where each layer goes (``layer_slots``: (kind, index in the
    # kind's stack) per layer); stacks are then keyed "kind/leaf". A layer
    # whose sublayers are of independent kinds (models/lfm2_moe.py) gives
    # {leaf: (kind, index)} instead.
    slots = model.layer_slots(cfg) if hasattr(model, "layer_slots") else None
    sizes: Dict[str, int] = {}
    if slots is not None:
        for slot in slots:
            filed = slot.values() if isinstance(slot, dict) else (slot,)
            for kind in {kind for kind, _ in filed}:
                sizes[kind] = sizes.get(kind, 0) + 1

    stacks: Dict[str, np.ndarray] = {}   # our layer leaf -> [L, ...] buffer
    filled: Dict[str, set] = {}          # our layer leaf -> set of layer idxs
    top: Dict[str, np.ndarray] = {}
    seen_experts: Dict[str, Dict[int, set]] = {}  # leaf -> layer -> experts
    # Leaves the module computes with in float32 whatever ``dtype`` is.
    keep_f32 = set(getattr(model, "FLOAT32_LEAVES", ()))

    # A module whose checkpoint fuses several of its leaves into one tensor
    # takes it apart first (models/mimo_v2.py:split_fused).
    split = getattr(model, "split_fused", None)
    # Expert parallelism: this chip files the experts it holds, numbered
    # from its first (0 where every expert is here), and passes over the
    # others.
    first_expert = cfg.ep_rank * cfg.n_routed_experts

    def file_layer_tensor(hf_name, layer_idx, suffix, tensor):
        em = _EXPERT_RE.match(suffix)
        if em is not None:
            suffix = em.group(1) + "*" + em.group(3)
        mapped = per_layer_map.get(suffix)
        if mapped is None:
            logger.debug("Skipping unmapped tensor %s", hf_name)
            return
        ours, transpose = mapped
        t = tensor.T if transpose else tensor
        if nl <= layer_idx < nl + cfg.num_nextn_predict_layers:
            # Published next-token-prediction layers lie behind the
            # last layer and are not served (models/deepseek_v3.py).
            logger.debug("Skipping next-token-prediction tensor %s",
                         hf_name)
            return
        if layer_idx >= nl:
            raise ValueError(
                f"Checkpoint tensor {hf_name} indexes layer {layer_idx} "
                f"but the config has only {nl} layers"
            )
        depth = nl
        if slots is not None:
            slot = slots[layer_idx]
            kind, layer_idx = slot[ours] if isinstance(slot, dict) \
                else slot
            ours, depth = f"{kind}/{ours}", sizes[kind]
        if em is not None:
            # Filed per (layer, expert); a layer counts as filled when
            # its last expert has arrived (holes: the check below).
            e, n_e = int(em.group(2)) - first_expert, cfg.n_routed_experts
            if cfg.ep_size > 1 and not 0 <= e < n_e:
                return
            if ours not in stacks:
                stacks[ours] = np.empty((depth, n_e) + t.shape, t.dtype)
                filled[ours] = set()
                seen_experts[ours] = {}
            stacks[ours][layer_idx, e] = t
            seen = seen_experts[ours].setdefault(layer_idx, set())
            seen.add(e)
            if len(seen) == n_e:
                filled[ours].add(layer_idx)
            return
        if ours not in stacks:
            stacks[ours] = np.empty((depth,) + t.shape, t.dtype)
            filled[ours] = set()
        stacks[ours][layer_idx] = t
        filled[ours].add(layer_idx)

    for hf_name, tensor in _iter_checkpoint_tensors(model_dir):
        m = _LAYER_RE.search(hf_name)
        if m is not None:
            layer_idx = int(m.group(1))
            suffix = hf_name[m.end():]
            pieces = ((suffix, tensor),) if split is None or layer_idx >= nl \
                else split(cfg, layer_idx, suffix, tensor)
            for suffix, piece in pieces:
                file_layer_tensor(hf_name, layer_idx, suffix, piece)
        else:
            mapped = top_map.get(hf_name)
            if mapped is None:
                logger.debug("Skipping unmapped tensor %s", hf_name)
                continue
            ours, transpose = mapped
            top[ours] = tensor.T if transpose else tensor

    # Completeness is checked per LAYER-INDEX SET, not by count: a sharded
    # checkpoint that repeats layer 0 and omits layer 7 has the right count
    # but would serve garbage for the missing layer.
    holes = {
        k: sorted(set(range(len(stacks[k]))) - s) for k, s in filled.items()
        if s != set(range(len(stacks[k])))
    }
    if holes:
        raise ValueError(
            f"Incomplete checkpoint: missing layer indices {holes}"
        )
    required = model.required_layer_leaves(cfg)
    if slots is not None:
        required = {f"{kind}/{leaf}" for kind, leaves in required.items()
                    for leaf in leaves}
    absent = required - set(stacks)
    if absent:
        raise ValueError(
            f"Incomplete checkpoint: no tensors at all for {sorted(absent)}"
        )

    params: Dict = {"layers": {}}
    for name in list(stacks):
        kind, _, leaf = name.rpartition("/")
        arr = jax.numpy.asarray(
            stacks[name],
            dtype=jax.numpy.float32 if leaf in keep_f32 else dtype)
        into, placed = params["layers"], (shardings or {}).get("layers", {})
        if kind:
            into, placed = into.setdefault(kind, {}), placed.get(kind, {})
        if leaf in placed:
            arr = jax.device_put(arr, placed[leaf])
        into[leaf] = arr
        stacks[name] = None  # free host memory promptly
    for name, leaf in top.items():
        arr = jax.numpy.asarray(leaf, dtype=dtype)
        if shardings is not None and name in shardings:
            arr = jax.device_put(arr, shardings[name])
        params[name] = arr

    params = model.finish_params(cfg, params)
    logger.info(
        "Loaded %d layer stacks + %d top-level tensors from %s",
        len(params["layers"]), len(top), model_dir,
    )
    return params
