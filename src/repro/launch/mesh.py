"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state. Single pod: (data=16, model=16) = 256 chips of TPU v5e; multi-pod:
(pod=2, data=16, model=16) = 512 chips. The ``pod`` axis composes with
``data`` (logical dp = (pod, data)) for batch/FSDP shardings.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def abstract_mesh(**axes):
    """Device-free mesh for rule/spec math — tests and dry analysis."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axes.values()), tuple(axes.keys()))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (fake) host devices exist — tests."""
    return _mesh((data, model), ("data", "model"))
