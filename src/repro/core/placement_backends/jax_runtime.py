"""What the jax and pallas engines need from the JAX runtime.

Two decisions live here and nowhere else:

* **Precision.**  The placement chain is float64 (the scalar oracle's
  add/sub chains).  :func:`x64` is the one scope that turns 64-bit jax
  types on, leaving the process-wide float32 default (which the model
  substrate relies on) untouched.  The jax engine runs every sweep inside
  it; on a TPU, XLA emulates float64.  Mosaic has no float64, so
  :func:`pallas_precision` lowers the Pallas kernel at float32 on a TPU
  and interprets it at float64 everywhere else.
* **The persistent compile cache.**  :func:`configure_compile_cache`
  leaves JAX's own ``JAX_COMPILATION_CACHE_DIR`` in charge when it is
  set, and otherwise points the cache at ``.jax_cache/`` in the checkout:
  a fixed path, so a later process finds what an earlier one compiled.
"""

from __future__ import annotations

import contextlib
import functools
from pathlib import Path

import numpy as np

__all__ = ["x64", "pallas_precision", "configure_compile_cache", "CACHE_DIR"]

CACHE_DIR = Path(__file__).resolve().parents[4] / ".jax_cache"


def x64():
    """Context in which jax arrays default to 64-bit types."""
    import jax

    return jax.enable_x64(True)


def pallas_precision() -> tuple[type, contextlib.AbstractContextManager]:
    """``(dtype, scope)`` the Pallas kernel runs at on this backend.

    float32 with no scope when the kernel lowers for a TPU; float64 under
    :func:`x64` in interpret mode.
    """
    from repro.kernels.ops import on_tpu

    if on_tpu():
        return np.float32, contextlib.nullcontext()
    return np.float64, x64()


@functools.cache
def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory.

    Returns the directory in use.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX has read it into its config and nothing is changed here.
    """
    import jax

    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
