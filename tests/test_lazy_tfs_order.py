"""The lazy power order of the exhaustive block walk.

``_sorted_tfs_blocks`` orders the TFS segment by segment, as far as the
walk reads it (``FeasibilityResult.tfs_power_segment``).  Whatever the
powers and the block schedule, its blocks, laid end to end, must be
``tfs_indices_by_power()`` element for element — ascending total power,
exact ties broken by flat TSS index — and the schedules built on it must
equal the scalar engine's, which walks the full sort.
"""

import numpy as np
import pytest

from repro import trace
from repro.core import (
    FleetSpec,
    PADPSFRScheduler,
    Task,
    TaskVariant,
    WalkStats,
    block_ramp,
    search_feasible,
)
from repro.core.scheduler import _coalesced_sizes, _sorted_tfs_blocks

from test_placement_batched import _assert_results_identical

# 6 tasks x 6 variants: |TSS| 46,656, more than the boundary sample holds,
# so segments are placed by sampled order statistics, not exact ones.
N_T, NV = 6, 6


def _tasks(n_t=N_T, nv=NV, rng=None, power_levels=None):
    rng = rng or np.random.default_rng(0)
    out = []
    for i in range(n_t):
        ths = np.sort(rng.uniform(0.3, 4.0, nv))
        if power_levels is None:
            pws = np.sort(rng.uniform(1.0, 9.0, nv))
        else:
            pws = np.sort(rng.choice(power_levels, nv))
        out.append(
            Task(
                name=f"T{i}",
                period=float(rng.uniform(20.0, 100.0)),
                data=float(rng.uniform(5.0, 80.0)),
                init_interval=float(rng.uniform(0.0, 8.0)),
                variants=tuple(
                    TaskVariant(cu=j + 1, throughput=float(th), power=float(pw))
                    for j, (th, pw) in enumerate(zip(ths, pws, strict=True))
                ),
            )
        )
    return out


def _heavy_ties(rng, n, k):
    return rng.integers(0, k, n).astype(np.float64) + 1.0


def _one_ulp(rng, n):
    base = 40.0
    levels = [base]
    for _ in range(3):
        levels.append(np.nextafter(levels[-1], np.inf))
    return np.asarray(levels)[rng.integers(0, len(levels), n)]


POWERS = {
    "ties2": lambda rng, n: _heavy_ties(rng, n, 2),
    "ties5": lambda rng, n: _heavy_ties(rng, n, 5),
    "distinct": lambda rng, n: rng.permutation(n).astype(np.float64) * 0.25 + 3.0,
    "one_ulp": _one_ulp,
    "uniform": lambda rng, n: rng.uniform(10.0, 50.0, n),
    "all_equal": lambda rng, n: np.full(n, 7.5),
}

SCHEDULES = {
    "ramp": lambda: block_ramp(),
    "fixed1": lambda: iter(lambda: 1, None),
    "fixed4096": lambda: iter(lambda: 4096, None),
    "coalesced": lambda: _coalesced_sizes(block_ramp(), 1 << 14),
}


def _feas(powers: str, fit_share: float = 0.6, seed: int = 7):
    """An exhaustive result over the tasks' TSS with planted powers."""
    feas = search_feasible(_tasks(), FleetSpec(n_f=4, t_slr=60.0, t_cfg=1.0))
    rng = np.random.default_rng(seed)
    feas.total_power = POWERS[powers](rng, feas.n_combos)
    feas.fit_mask = rng.random(feas.n_combos) < fit_share
    return feas


def _walk(feas, sizes, stop=None):
    """Every block of ``_sorted_tfs_blocks`` (up to ``stop`` rows), end to end."""
    shares, idx = [], []
    for s, i in _sorted_tfs_blocks(feas, sizes):
        assert len(s) == len(i) > 0
        shares.append(s)
        idx.append(i)
        if stop is not None and sum(map(len, idx)) >= stop:
            break
    if not idx:
        return np.zeros((0, len(feas.tasks))), np.zeros(0, dtype=np.int64)
    return np.concatenate(shares), np.concatenate(idx)


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("powers", sorted(POWERS))
def test_blocks_end_to_end_are_the_full_stable_sort(powers, schedule):
    feas = _feas(powers)
    want = feas.tfs_indices_by_power()
    shares, idx = _walk(feas, SCHEDULES[schedule]())
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_array_equal(shares, feas.shares_matrix(want))


@pytest.mark.parametrize("stop", [1, 64, 576, 4672])
@pytest.mark.parametrize("powers", ["ties5", "uniform"])
def test_a_walk_that_stops_early_orders_a_prefix(powers, stop):
    feas = _feas(powers)
    ws = WalkStats()
    with trace.scope(ws):
        _, idx = _walk(feas, block_ramp(), stop=stop)
    np.testing.assert_array_equal(idx, feas.tfs_indices_by_power()[: idx.size])
    assert ws.tfs_rows == feas.n_tfs
    assert idx.size <= ws.sorted_rows < ws.tfs_rows


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_an_empty_tfs_yields_no_block(schedule):
    feas = _feas("ties5", fit_share=0.0)
    assert feas.n_tfs == 0
    _, idx = _walk(feas, SCHEDULES[schedule]())
    assert idx.size == 0


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_a_tfs_smaller_than_the_first_block(schedule):
    feas = _feas("ties2", fit_share=0.0)
    rows = np.random.default_rng(3).choice(feas.n_combos, 10, replace=False)
    feas.fit_mask[rows] = True
    _, idx = _walk(feas, SCHEDULES[schedule]())
    np.testing.assert_array_equal(idx, feas.tfs_indices_by_power())
    assert idx.size == 10


@pytest.mark.parametrize("powers", ["ties2", "ties5", "one_ulp", "all_equal"])
def test_a_tie_group_is_never_split_between_segments(powers):
    """A segment aimed at a few rows takes the whole tie group it reaches,
    however large, and the next segment starts strictly above it."""
    feas = _feas(powers)
    full = feas.tfs_indices_by_power()
    above, got = -np.inf, []
    while sum(map(len, got)) < full.size:
        part, q = feas.tfs_power_segment(above, 3)
        assert part.size >= 1
        p = feas.total_power[part]
        assert np.all(p > above) and np.all(p <= q)
        if got:
            assert feas.total_power[got[-1][-1]] < p[0]
        got.append(part)
        above = q
    np.testing.assert_array_equal(np.concatenate(got), full)
    # The first segment, aimed at 3 rows, holds the whole lowest tie group.
    lowest = feas.total_power[full[0]]
    assert np.count_nonzero(feas.total_power[got[0]] == lowest) == np.count_nonzero(
        feas.fit_mask & (feas.total_power == lowest))


def test_infinite_and_nan_powers_come_last_as_in_the_full_sort():
    feas = _feas("ties5")
    rng = np.random.default_rng(11)
    feas.total_power[rng.choice(feas.n_combos, 50, replace=False)] = np.inf
    feas.total_power[rng.choice(feas.n_combos, 50, replace=False)] = np.nan
    _, idx = _walk(feas, iter(lambda: 4096, None))
    np.testing.assert_array_equal(idx, feas.tfs_indices_by_power())


def _tie_heavy_instances(n, n_t, nv, seed):
    rng = np.random.default_rng(seed)
    levels = np.asarray([2.0, 3.0, 3.5, 5.0])
    out = []
    for _ in range(n):
        tasks = _tasks(n_t, nv, rng, power_levels=levels)
        fleet = FleetSpec(n_f=int(rng.integers(2, 5)), t_slr=float(rng.uniform(60, 120)),
                          t_cfg=float(rng.uniform(0.5, 4.0)))
        out.append((tasks, fleet))
    return out


@pytest.mark.parametrize("count_all", [False, True], ids=["first", "all_rejects"])
@pytest.mark.parametrize("block_size", [None, 1, 4096], ids=["ramp", "b1", "b4096"])
def test_schedule_equals_the_scalar_engine(block_size, count_all):
    checked = 0
    for tasks, fleet in _tie_heavy_instances(6, 5, 4, seed=21):
        want = PADPSFRScheduler(fleet, engine="scalar").schedule(
            tasks, count_all_rejects=count_all)
        got = PADPSFRScheduler(fleet, engine="numpy", block_size=block_size).schedule(
            tasks, count_all_rejects=count_all)
        _assert_results_identical(got, want)
        assert (got.n_tss, got.n_tfs, got.n_tnfs) == (want.n_tss, want.n_tfs, want.n_tnfs)
        checked += want.feasible
    assert checked >= 3


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_schedule_many_equals_the_scalar_engine(engine):
    insts = _tie_heavy_instances(5, 5, 4, seed=33)
    fleet = insts[0][1]
    want = PADPSFRScheduler(fleet, engine="scalar").schedule_many([t for t, _ in insts])
    got = PADPSFRScheduler(fleet, engine=engine).schedule_many([t for t, _ in insts])
    for g, w in zip(got, want, strict=True):
        _assert_results_identical(g, w)
        assert g.n_tfs == w.n_tfs


def test_a_large_instance_with_a_shallow_winner_equals_the_scalar_engine():
    """|TSS| past the boundary sample: the Weyl-sampled boundaries, on a
    real instance, give the scalar engine's answer."""
    tasks = _tasks(6, 6, np.random.default_rng(5), power_levels=np.asarray([1.0, 2.0, 4.0]))
    fleet = FleetSpec(n_f=6, t_slr=400.0, t_cfg=1.0)
    ws = WalkStats()
    got = PADPSFRScheduler(fleet, engine="numpy").schedule(tasks, walk_stats=ws)
    want = PADPSFRScheduler(fleet, engine="scalar").schedule(tasks)
    _assert_results_identical(got, want)
    assert got.n_tss == 6**6 and got.n_tfs == want.n_tfs
    assert ws.tfs_rows == got.n_tfs and 0 < ws.sorted_rows < got.n_tfs
