"""Device mesh construction and axis conventions.

Axis vocabulary (fixed across the framework):

- ``dp``   data parallel (batch sharding; gradients all-reduced over it)
- ``fsdp`` fully-sharded data parallel (params sharded, all-gathered per layer)
- ``pp``   pipeline parallel (layer stages; activations ppermute'd)
- ``tp``   tensor parallel (hidden/head sharding inside matmuls)
- ``sp``   sequence/context parallel (ring attention / Ulysses over tokens)
- ``ep``   expert parallel (an expert layer's weights shard over it)

Reference role: replaces Ray Train's torch process-group setup
(python/ray/train/torch/config.py [unverified]) and the NCCL group bootstrap
in python/ray/util/collective — on TPU the "process group" is just a Mesh and
the collectives are compiled into the program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")

_local = threading.local()


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each mesh axis; -1 on at most one axis means "absorb the rest".

    Unspecified axes default to 1 so every sharding annotation in the
    framework is valid on any mesh (a size-1 axis is a no-op shard).
    """

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    def sizes(self, n_devices: int) -> Tuple[int, ...]:
        vals = [self.dp, self.fsdp, self.pp, self.tp, self.sp, self.ep]
        if vals.count(-1) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(v for v in vals if v != -1)
        if n_devices % fixed:
            raise ValueError(
                f"mesh {vals} does not divide {n_devices} devices")
        if -1 in vals:
            vals[vals.index(-1)] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {vals} uses {fixed} devices, have {n_devices}")
        return tuple(vals)


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    **axis_sizes: int,
) -> Mesh:
    """Build a Mesh over all (or given) devices with the standard axes.

    ``make_mesh(dp=2, tp=4)`` or ``make_mesh(MeshConfig(tp=4))``. Axes are
    laid out innermost-last so that tp/sp/ep (highest-bandwidth-need axes)
    map to adjacent devices on the ICI torus — the device order jax returns
    is torus-major on TPU, so contiguity ≈ ICI proximity.
    """
    if config is None:
        config = MeshConfig(**axis_sizes) if axis_sizes else MeshConfig()
    elif axis_sizes:
        raise ValueError("pass either a MeshConfig or axis kwargs, not both")
    if devices is None:
        devices = jax.devices()
    sizes = config.sizes(len(devices))
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, AXES)


def mesh_shape(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def get_mesh() -> Optional[Mesh]:
    """The ambient mesh set by :func:`mesh_context` (or None)."""
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _local.mesh = prev
