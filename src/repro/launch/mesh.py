"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model") — for the
registration solver this IS the paper's p1 x p2 pencil grid; for the LM
architectures it is (data parallel+FSDP) x (tensor/expert parallel).

Multi-pod: 2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is an extra data-parallel dimension (LMs) / an ensemble axis of
independent registration problems (the paper's embarrassingly-parallel
multi-subject dimension).

Defined as functions (never module-level constants) so importing this
module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to materialize placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """The one place this repo builds a mesh: every axis is ``Auto``.

    The pencil FFT, the halo exchange and the LM sharding rules are written
    for GSPMD propagation (``Auto`` axes); ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which their rolls, reshapes and duplicate specs
    are refused.  ``devices`` defaults to ``jax.devices()``; pass described
    devices (``jax.experimental.topologies``) to compile for absent chips.
    """
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axis_size(mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.shape else 1


def mesh_axes_size(mesh, ax) -> int:
    """Device count behind one pencil dimension; the axis entry may be a
    tuple of mesh axis names, e.g. ("pod", "data")."""
    names = ax if isinstance(ax, tuple) else (ax,)
    size = 1
    for n in names:
        size *= int(mesh.shape[n])
    return size


def validate_mesh_for_grid(mesh, grid_shape, axes=("data", "model")) -> None:
    """Pencil decomposition requires the first two grid dims to divide."""
    p1, p2 = mesh_axes_size(mesh, axes[0]), mesh_axes_size(mesh, axes[1])
    n1, n2, n3 = grid_shape
    if n1 % p1 or n2 % p2:
        raise ValueError(f"grid {grid_shape} not divisible by pencil mesh ({p1},{p2})")
    # FFT transposes additionally need (paper Fig. 4 layout):
    if n2 % p1 or n3 % p2:
        raise ValueError(
            f"transposed pencil layout needs N2 % p1 == 0 and N3 % p2 == 0; "
            f"got grid {grid_shape}, mesh ({p1},{p2})"
        )
