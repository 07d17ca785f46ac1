"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — only `launch/dryrun.py` forces the 512-way
host-device platform, everything else sees the real devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape, axes) -> Mesh:
    """`jax.make_mesh` with every axis `Auto`: the model code places data
    with `with_sharding_constraint` and lets GSPMD propagate, which only
    Auto axes allow (JAX's default is Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 v5e pod slice; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever this host has (CPU smoke testing)."""
    n = len(jax.devices())
    return auto_mesh((n,), ("data",))


def batch_axes_for(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
