"""The four-chip what-if mix, and the readers of the walk's spans and counters.

The mesh mix is ``whatif_b16`` but for its engine and shard, so its panel
and its work are the ones the one-chip mix's tests hold.  Each reader
turns a hand-made run record into its per-answer number, and reads
nothing from a program that records no such span or counter.
"""

import json

import pytest

import benchtest_util as util
from bench import run


def test_mesh_mix_is_whatif_b16_laid_over_four_chips():
    traffic = util.ROOT / "bench" / "traffic"
    one = json.loads((traffic / "whatif_b16.json").read_text())
    four = json.loads((traffic / "whatif_b16_mesh4.json").read_text())
    assert (four["engine"], four["shard"]) == ("jax", 4)
    skip = {"engine", "shard", "note"}
    assert {k: v for k, v in four.items() if k not in skip} == {
        k: v for k, v in one.items() if k not in skip}


WALK = {"enumerate_us": 40_000.0, "place_us": 30_000.0, "sync_us": 5_000.0,
        "materialize_us": 1_000.0, "rows": 4_000, "n_blocks": 4, "block_sizes": [],
        "search_us": 24_000.0, "sort_us": 36_000.0, "gather_us": 2_000.0,
        "prepare_us": 6_000.0, "launch_us": 18_000.0, "unbatch_us": 3_000.0,
        "h2d_bytes": 2 * 1024 * 300,
        "d2h_bytes": 2 * 1024 * 5, "launches": 4, "abandoned_rows": 1_000}

# Two answers: each per-answer number is half the walk's total.
EXPECTED = {"search_ms": 12.0, "sort_ms": 18.0, "gather_ms": 1.0, "prepare_ms": 3.0,
            "launch_ms": 9.0, "unbatch_ms": 1.5, "h2d_kb": 300.0, "d2h_kb": 5.0, "launches": 2.0,
            "abandoned_row_share": 25.0}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_turns_the_walk_into_its_number(metric):
    rec = {"answers": 2, "walk": WALK}
    assert run.reader(util.ROOT / "bench", f"{metric}.solve").read(rec) == pytest.approx(
        EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_walk_without_it(metric):
    old = ("enumerate_us", "place_us", "sync_us", "materialize_us", "rows", "n_blocks",
           "block_sizes")
    rec = {"answers": 2, "walk": {k: WALK[k] for k in old}}
    assert run.reader(util.ROOT / "bench", f"{metric}.whatif").read(rec) is None
