"""Ahead-of-time compiles of the placement sweeps for a described TPU v5e.

The TPU compiler is installed wherever jax is, and compiles for a chip
that is described rather than attached.  These tests compile the two
device engines' programs at the block sizes the scheduler's walk reaches
(the block ramp's 65,536-row cap; a 64-instance ``schedule_many`` round)
and fail on anything the chip's compiler refuses: a kernel layout Mosaic
cannot lower, a block shape the TPU does not tile, a float64 program the
chip cannot emulate.  Nothing runs, so nothing here speaks to results or
times; ``chip_smoke.py`` runs the same programs on the chip.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and test
workers import every test file.
"""

import functools
import os

import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

SOLO = (65536, 10, 6)  # (rows, n_t, n_f): one full-size block of the ramp
BATCH = (64, 1024, 7, 4)  # (instances, rows, n_t, n_f): a schedule_many round


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """``(shape, dtype) -> ShapeDtypeStruct`` placed on one described chip.

    The persistent compile cache is off for the module: a compile for a
    described chip is written to it but cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def test_pallas_solo_kernel_compiles(shape_on_chip):
    from repro.kernels.placement_step import placement_sweep_pallas

    rows, n_t, n_f = SOLO
    f32 = jnp.float32
    fn = jax.jit(
        functools.partial(placement_sweep_pallas, block_rows=1024, interpret=False)
    )
    compiled = fn.lower(
        shape_on_chip((rows, n_t), f32),
        shape_on_chip((n_t,), f32),
        shape_on_chip((n_f,), f32),
        shape_on_chip((n_f,), f32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_batch_kernel_compiles(shape_on_chip):
    from repro.kernels.placement_step import placement_sweep_batch_pallas

    B, rows, n_t, n_f = BATCH
    f32, i32 = jnp.float32, jnp.int32
    fn = jax.jit(
        functools.partial(
            placement_sweep_batch_pallas, block_rows=1024, interpret=False
        )
    )
    compiled = fn.lower(
        shape_on_chip((B, rows, n_t), f32),
        shape_on_chip((B, n_t), f32),
        shape_on_chip((B, n_f), f32),
        shape_on_chip((B, n_f), f32),
        shape_on_chip((B,), i32),
        shape_on_chip((B,), i32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_f64_sweep_compiles(shape_on_chip):
    from repro.core.placement_backends.jax_backend import _jitted_sweep
    from repro.core.placement_backends.jax_runtime import x64

    rows, n_t, n_f = SOLO
    with x64():
        f64 = jnp.float64
        compiled = _jitted_sweep().lower(
            shape_on_chip((rows, n_t), f64),
            shape_on_chip((n_t,), f64),
            shape_on_chip((n_f,), f64),
            shape_on_chip((n_f,), f64),
            shape_on_chip((), f64),
            repay_init=True,
        ).compile()
    assert "while" in compiled.as_text()


def test_jax_f64_batch_sweep_compiles(shape_on_chip):
    from repro.core.placement_backends.jax_backend import _jitted_batch_sweep
    from repro.core.placement_backends.jax_runtime import x64

    B, rows, n_t, n_f = BATCH
    with x64():
        f64, i64 = jnp.float64, jnp.int64
        compiled = _jitted_batch_sweep(1).lower(
            shape_on_chip((B, rows, n_t), f64),
            shape_on_chip((B, n_t), f64),
            shape_on_chip((B, n_f), f64),
            shape_on_chip((B, n_f), f64),
            shape_on_chip((B,), i64),
            shape_on_chip((B,), i64),
            shape_on_chip((), f64),
            repay_init=True,
        ).compile()
    assert "while" in compiled.as_text()
