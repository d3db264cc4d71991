"""Device mesh construction.

The reference's tensor parallelism is a flag passed through to external vLLM
images with NCCL underneath (reference helm/templates/deployment-vllm-multi.yaml:97-100
plus the /dev/shm volume :235-238). Here TP/DP/SP are axes of ONE
jax.sharding.Mesh over the TPU slice; XLA inserts the ICI collectives — there
is no communication backend to hand-write.

Axes:
  * "dp" — data parallel (batch-sharded decode within one engine process;
           cross-pod DP remains router-level replicas, as in the reference).
  * "sp" — sequence parallel (ring-attention prefill for long contexts).
  * "tp" — tensor parallel (Megatron-style column/row sharded matmuls).
"""

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DP, AXIS_SP, AXIS_TP = "dp", "sp", "tp"


def make_mesh(
    dp: int = 1,
    sp: int = 1,
    tp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    need = dp * sp * tp
    if need > len(devices):
        raise ValueError(
            f"Mesh dp={dp} sp={sp} tp={tp} needs {need} devices, "
            f"have {len(devices)}"
        )
    grid = np.array(devices[:need]).reshape(dp, sp, tp)
    return Mesh(grid, (AXIS_DP, AXIS_SP, AXIS_TP))


def single_device_mesh() -> Mesh:
    return make_mesh(1, 1, 1)
