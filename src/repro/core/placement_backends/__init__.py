"""Pluggable Alg-2 block-placement backends.

The scheduler's hot path — *is this TFS row placeable?* for a whole block
of power-sorted rows — dispatches through a registry of interchangeable
engines (see :mod:`.base` for the contract and how to register new ones):

* ``"scalar"`` — the exact Alg-2/Alg-3 oracle, one row at a time;
* ``"numpy"``  — vectorized (B,) state advance, zero-dependency default
  (alias: ``"batched"``, the pre-refactor name);
* ``"jax"``    — jit'd ``lax.while_loop`` sweep at float64 inside the
  scoped :func:`.jax_runtime.x64` (emulated float64 on a TPU; lazy:
  registered on first lookup);
* ``"pallas"`` — the fused Pallas kernel
  (:mod:`repro.kernels.placement_step`), blocks tiled through VMEM
  (lazy; float32 on a TPU, float64 interpret mode elsewhere);
* ``"auto"``   — best available of the above (see
  :func:`.base.resolve_engine`).

``chip_smoke.py`` at the repository root holds both device engines'
plans to the numpy engine and the scalar oracle on a TPU.
"""

from .base import (
    BatchPlacement,
    InstanceBatch,
    PlacementBackend,
    PlacementOptions,
    available_backends,
    backend_names,
    dispatch_instance_blocks,
    get_backend,
    place_instance_blocks,
    prepare_block,
    register_backend,
    resolve_engine,
)

# Importing the zero-dependency backends registers them; jax/pallas are
# registered lazily by the registry (see base._LAZY_BACKENDS).
from . import numpy_backend as _numpy_backend  # noqa: F401
from . import scalar_backend as _scalar_backend  # noqa: F401

__all__ = [
    "BatchPlacement",
    "InstanceBatch",
    "PlacementBackend",
    "PlacementOptions",
    "available_backends",
    "backend_names",
    "dispatch_instance_blocks",
    "get_backend",
    "place_instance_blocks",
    "prepare_block",
    "register_backend",
    "resolve_engine",
]
