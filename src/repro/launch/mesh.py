"""Production meshes.

Everything is a *function* (never module-level device state): importing this
module must not initialise jax's backend, because the dry-run needs to set
XLA_FLAGS before first jax use while tests run on the single real CPU
device.

Production topology (TPU v5e): one pod = a 16x16 slice (256 chips);
multi-pod = 2 pods = 512 chips. Mesh axes:

  pod     crosses the inter-pod DCN boundary: *pure data parallelism* —
          the only cross-pod collective is the gradient all-reduce
          (optionally int8-compressed, `repro.optim.compress`)
  data    intra-pod data parallelism + fsdp (ZeRO-3 parameter sharding)
  model   tensor/sequence parallelism (Megatron-style)
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model shards by logical-axis constraints that GSPMD
    # propagates (`repro.parallel.axes.constrain`); `jax.make_mesh`
    # defaults to Explicit axes, under which those constraints and the
    # embedding gather are refused
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1,
                   pod: Optional[int] = None) -> Mesh:
    """A small mesh over whatever devices exist (tests / examples)."""
    n = data * model * (pod or 1)
    devs = np.asarray(jax.devices()[:n])
    if pod is None:
        return Mesh(devs.reshape(data, model), ("data", "model"))
    return Mesh(devs.reshape(pod, data, model), ("pod", "data", "model"))


def mesh_devices_required(multi_pod: bool) -> int:
    return 512 if multi_pod else 256


def describe(mesh: Mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items()) \
        + f" ({mesh.size} chips)"
