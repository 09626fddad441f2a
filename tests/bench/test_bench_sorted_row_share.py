"""The reader of the lazy sort's reach: rows put in power order over the
TFS rows the walks held, read from a hand-made run record, and nothing
from a program that counts no such rows."""

import pytest

import benchtest_util as util
from bench import run

OLD = {"enumerate_us": 40_000.0, "place_us": 30_000.0, "sync_us": 5_000.0,
       "materialize_us": 1_000.0, "rows": 4_000, "n_blocks": 4, "block_sizes": [],
       "search_us": 24_000.0, "sort_us": 36_000.0, "gather_us": 2_000.0,
       "abandoned_rows": 1_000}


@pytest.mark.parametrize("cell", ["solve", "whatif"])
@pytest.mark.parametrize("sorted_rows,tfs_rows,share", [
    (102_976, 665_888, 15.464462492), (1_360, 665_888, 0.204238551), (620, 620, 100.0)])
def test_reader_turns_the_counters_into_a_share(cell, sorted_rows, tfs_rows, share):
    rec = {"answers": 2, "walk": {**OLD, "sorted_rows": sorted_rows, "tfs_rows": tfs_rows}}
    got = run.reader(util.ROOT / "bench", f"sorted_row_share.{cell}").read(rec)
    assert got == pytest.approx(share)


@pytest.mark.parametrize("walk", [OLD, {**OLD, "sorted_rows": 0, "tfs_rows": 0}],
                         ids=["parent", "no_exhaustive_walk"])
def test_reader_reads_nothing_without_the_counters(walk):
    rec = {"answers": 2, "walk": walk}
    assert run.reader(util.ROOT / "bench", "sorted_row_share.solve").read(rec) is None
