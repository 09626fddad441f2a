"""The fpga_table1.solve mix on the CPU: every instance in its rank band, and the
work of a pass as PERF.md records it (seeds 6-11 of twelve)."""

import pytest

import benchtest_util as util


@pytest.mark.parametrize("seed", util.SEEDS[6:12])
def test_panel_lies_in_its_band_with_fixed_work(seed):
    util.check_panel("fpga_table1.solve", seed)
