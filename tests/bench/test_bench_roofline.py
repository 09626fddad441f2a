"""The placement sweep's roofline counts the same work on every engine."""

from __future__ import annotations

import pytest

import benchtest_util  # noqa: F401  (puts the checkout on sys.path)
from bench import roofline


def _rows(engine: str) -> int:
    from repro.configs.paper_examples import example1_fleet, example1_tasks
    from repro.core import PADPSFRScheduler, WalkStats

    ws = WalkStats()
    # Every engine sweeps the whole TFS (620 rows) when it counts rejects.
    PADPSFRScheduler(example1_fleet(), engine=engine).schedule(
        example1_tasks(), walk_stats=ws, count_all_rejects=True)
    return ws.rows


def test_bytes_equal_on_numpy_jax_and_pallas():
    costs = {e: roofline.sweep_cost(_rows(e), 6, 4) for e in ("numpy", "jax", "pallas")}
    assert costs["numpy"] == costs["jax"] == costs["pallas"]
    nbytes, ops = costs["numpy"]
    assert nbytes == 620 * (6 + roofline.OUT_WORDS) * roofline.WIDTH
    assert ops == 620 * (6 + 4) * roofline.STEP_OPS


def test_the_sweep_is_bound_by_memory_on_a_v5e():
    nbytes, ops = roofline.sweep_cost(102_976, 12, 8)
    least, bound = roofline.least_time(nbytes, ops, roofline.peak("TPU v5 lite"))
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9)


def test_a_device_missing_from_the_peaks_table_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak("cpu")
