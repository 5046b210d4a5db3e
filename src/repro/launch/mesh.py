"""Production meshes (DESIGN §5). Functions, not module constants, so
importing this module never touches jax device state."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis
    carries only the gradient all-reduce (lowest-frequency collective on
    the lowest-bandwidth links)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh for tests/elastic-restore experiments."""
    return jax.make_mesh(tuple(shape), tuple(axes))


def make_host_mesh(devices=None) -> Mesh:
    """(data=N, model=1) data-parallel mesh over ``devices`` (default:
    every device of this host — one on a CPU or a one-chip host, four on
    a v5e 2x2 host)."""
    devs = jax.local_devices() if devices is None else list(devices)
    return Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))


def data_axes_of(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
