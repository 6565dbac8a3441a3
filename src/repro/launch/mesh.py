"""Production mesh construction.

Axis semantics (DESIGN.md §2): ``model`` is the fast interconnect tier (the
paper's intra-node NVLink analogue — hpZ secondary groups and the qgZ intra
hop live here), ``data`` the slower tier, and ``pod`` the slowest (inter-pod
DCI).  The ZeRO world is ALL axes flattened; "model" does not mean tensor
parallelism — it carries sequence-parallel activations and the fast-tier
collectives.

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run pins the device count before first use).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the shard_map code here
    names its collectives explicitly and lets the compiler place the rest."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    The dry-run environment exposes 512 placeholder devices; the single-pod
    mesh takes the first 256 so both meshes build in one process.  Device
    ids are row-major over the mesh (host platform preserves order), which
    the dry-run's collective-tier classifier relies on.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) > n:
        devs = devs[:n]
    return make_mesh(shape, axes, devices=devs)


def make_test_mesh(shape: Tuple[int, ...] = None, axes: Tuple[str, ...] = None):
    """Small mesh over however many (simulated) devices exist."""
    n = jax.device_count()
    if shape is None:
        if n >= 8:
            shape, axes = (2, n // 4, 2), ("pod", "data", "model")
        elif n >= 4:
            shape, axes = (n // 2, 2), ("data", "model")
        else:
            shape, axes = (1, n), ("data", "model")
    return make_mesh(shape, axes)


def mesh_axis_names(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)
