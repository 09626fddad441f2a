"""Production meshes.

``make_production_mesh()`` is a FUNCTION (importing this module never
touches jax device state):

* single-pod:  (16, 16)    axes ('data', 'model')      — 256 chips
* multi-pod:   (2, 16, 16) axes ('pod', 'data', 'model') — 512 chips

The ``pod`` axis is an outer data-parallel axis: batch shards over
('pod', 'data'); cross-pod traffic is only the gradient reduction in
training and nothing in serving.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "SINGLE_POD", "MULTI_POD"]

SINGLE_POD = ((16, 16), ("data", "model"))
MULTI_POD = ((2, 16, 16), ("pod", "data", "model"))


def _auto(axes: tuple[str, ...]) -> tuple:
    """Auto axis types: the substrate shards with ``with_sharding_constraint``,
    which ``jax.make_mesh``'s default Explicit axes refuse."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape, axes = MULTI_POD if multi_pod else SINGLE_POD
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import"
        )
    return jax.make_mesh(shape, axes, axis_types=_auto(axes), devices=devices[:n])


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary sub-mesh (tests use (1,2)/(2,2,2)-sized variants)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(
        shape, axes, axis_types=_auto(axes), devices=jax.devices()[:n]
    )
