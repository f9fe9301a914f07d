"""Device mesh construction for serving replicas.

A serving replica owns some set of ICI-connected chips (v5e-1, v5e-4,
v5e-8...).  The mesh axes follow the scaling-book convention:

- ``dp``: data parallel — request batches split across this axis; no
  parameter communication.
- ``tp``: tensor parallel — transformer weight matrices shard across this
  axis; activations all-reduce over ICI inside each layer.
- ``sp``: sequence parallel — long-context attention rotates K/V around
  this axis (ring attention).

Axis sizes are static per-deployment config (the control-plane spec's
`parallelism` block, control/spec.py); there is no dynamic re-meshing — a
new mesh is a new model load, same as a replica restart in the reference.
"""

import contextlib
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

import numpy as np


@dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape.  Sizes of 1 are valid (axis present but
    trivial) so jitted code can always reference all three axes."""

    dp: int = 1
    tp: int = 1
    sp: int = 1
    axis_order: Sequence[str] = ("dp", "sp", "tp")

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.sp

    def sizes(self) -> Dict[str, int]:
        return {"dp": self.dp, "tp": self.tp, "sp": self.sp}


def build_mesh(config: Optional[MeshConfig] = None, devices=None,
               **axis_sizes):
    """Build a jax.sharding.Mesh from a MeshConfig (or dp=/tp=/sp= kwargs).

    Axis order puts ``tp`` innermost: tensor-parallel collectives are the
    most latency-sensitive, and innermost mesh axes map to the
    closest-neighbor ICI links on TPU device orderings.
    """
    import jax
    from jax.sharding import Mesh

    if config is None:
        config = MeshConfig(**axis_sizes)
    devices = list(devices if devices is not None else jax.devices())
    n = config.num_devices
    if n > len(devices):
        raise ValueError(
            f"mesh {config.sizes()} needs {n} devices; "
            f"{len(devices)} available")
    shape = tuple(getattr(config, a) for a in config.axis_order)
    dev_array = np.array(devices[:n]).reshape(shape)
    return Mesh(dev_array, tuple(config.axis_order))


def single_device_mesh(device=None):
    """Degenerate 1-device mesh so single-chip and multi-chip serving share
    one code path (everything is pjit over a mesh; XLA elides the trivial
    collectives)."""
    import jax

    return build_mesh(MeshConfig(), devices=[device or jax.devices()[0]])


def mesh_scope(mesh):
    """Context in which an engine traces and calls its model programs:
    `jax.set_mesh(mesh)`, or nothing without a mesh.  The attention
    dispatchers (ops/) read the ambient mesh at trace time to run their
    Pallas kernels under `shard_map`."""
    import jax

    return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)
