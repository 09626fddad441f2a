"""Block-native streaming TFS enumeration: order, determinism, pipelining.

The block enumerator (``repro.core.feasibility.iter_feasible_pruned_blocks``)
must emit the TFS in *exactly* the order of the materialised
``tfs_indices_by_power()`` — ascending total power, exact-power ties broken
by TSS flat index — and so must the Python-heap streamer
(``iter_feasible_pruned``).  This file covers:

* combo-for-combo order parity of all three enumeration engines, on the
  paper's examples and randomized heterogeneous fleets;
* power-tie determinism across 100+ randomized fleets with discrete
  (tie-heavy) power tables;
* the tightened heterogeneous eq-7 prefix bound: streamed == exhaustive
  row sets (the bound prunes nothing the exhaustive filter keeps);
* block-size/ramp invariance of the streaming scheduler path and parity
  against both the exhaustive path and the scalar oracle engine;
* asynchronous ``dispatch_block`` parity (jax/pallas double buffering);
* the ``outer_sum`` in-place accumulation regression (bitwise equality +
  peak-memory cap on large products).
"""

import tracemalloc

import numpy as np
import pytest

from repro.configs.paper_examples import (
    example1_fleet,
    example1_tasks,
    example2_fleet,
    example2_tasks,
    example3_fleet,
    example3_tasks,
)
from repro.core import (
    FleetSpec,
    PADPSFRScheduler,
    Task,
    TaskSetCombo,
    TaskVariant,
    WalkStats,
    block_ramp,
    get_backend,
    iter_feasible_pruned,
    iter_feasible_pruned_blocks,
    outer_sum,
    search_feasible,
)
from repro.core.feasibility import _scalar_overhead_lb, config_overhead_lower_bound

from test_placement_batched import (
    _assert_results_identical,
    _random_fleet,
    _random_tasks,
)

try:
    import jax  # noqa: F401

    HAS_JAX = True
except ImportError:  # pragma: no cover - exercised by the no-jax CI leg
    HAS_JAX = False

needs_jax = pytest.mark.skipif(not HAS_JAX, reason="jax not installed")

PAPER_CASES = [
    (example1_tasks, example1_fleet),
    (example2_tasks, example2_fleet),
    (example3_tasks, example3_fleet),
]
PAPER_IDS = ["example1", "example2", "example3"]


def _materialized_order(tasks, fleet):
    feas = search_feasible(tasks, fleet)
    return [feas.combo_at(int(i)) for i in feas.tfs_indices_by_power()]


def _block_order(tasks, fleet, block_sizes):
    out = []
    for blk in iter_feasible_pruned_blocks(tasks, fleet, block_sizes):
        assert blk.shares.shape == blk.variant_idx.shape
        assert blk.total_power.shape == (len(blk),)
        out.extend(blk.materialize(r) for r in range(len(blk)))
    return out


# ---------------------------------------------------------------------------
# order parity: heap == blocks == materialized, combo for combo
# ---------------------------------------------------------------------------


class TestEnumerationOrderParity:
    @pytest.mark.parametrize("tasks_fn,fleet_fn", PAPER_CASES, ids=PAPER_IDS)
    def test_paper_examples_exact_order(self, tasks_fn, fleet_fn):
        tasks, fleet = tasks_fn(), fleet_fn()
        mat = _materialized_order(tasks, fleet)
        assert list(iter_feasible_pruned(tasks, fleet)) == mat
        assert _block_order(tasks, fleet, 64) == mat

    @pytest.mark.parametrize("block_sizes", [1, 3, 4096, None], ids=["b1", "b3", "b4096", "ramp"])
    def test_randomized_exact_order_any_blocking(self, block_sizes):
        rng = np.random.default_rng(101)
        sizes = block_ramp() if block_sizes is None else block_sizes
        rows = 0
        for _ in range(40):
            tasks = _random_tasks(rng)
            fleet = _random_fleet(rng)
            mat = _materialized_order(tasks, fleet)
            sizes_i = block_ramp() if block_sizes is None else sizes
            assert _block_order(tasks, fleet, sizes_i) == mat
            rows += len(mat)
        assert rows > 200

    def test_heap_streamer_exact_order_randomized(self):
        rng = np.random.default_rng(55)
        for _ in range(40):
            tasks = _random_tasks(rng)
            fleet = _random_fleet(rng)
            assert list(iter_feasible_pruned(tasks, fleet)) == _materialized_order(
                tasks, fleet
            )

    def test_block_shares_match_shares_matrix_bitwise(self):
        tasks, fleet = example1_tasks(), example1_fleet()
        feas = search_feasible(tasks, fleet)
        order = feas.tfs_indices_by_power()
        want = feas.shares_matrix(order)
        got = np.concatenate(
            [b.shares for b in iter_feasible_pruned_blocks(tasks, fleet, 100)]
        )
        assert got.shape == want.shape
        assert (got == want).all()  # bitwise, not approx

    def test_total_power_matches_outer_sum_bitwise(self):
        tasks, fleet = example1_tasks(), example1_fleet()
        feas = search_feasible(tasks, fleet)
        want = feas.total_power[feas.tfs_indices_by_power()]
        got = np.concatenate(
            [b.total_power for b in iter_feasible_pruned_blocks(tasks, fleet, 128)]
        )
        assert (got == want).all()

    def test_empty_task_set_single_empty_combo(self):
        fleet = FleetSpec(n_f=2, t_slr=50.0, t_cfg=1.0)
        blocks = list(iter_feasible_pruned_blocks((), fleet, 8))
        assert len(blocks) == 1 and len(blocks[0]) == 1
        combo = blocks[0].materialize(0)
        assert combo.variant_idx == () and combo.total_power == 0.0

    def test_block_sizes_validation(self):
        tasks, fleet = example1_tasks(), example1_fleet()
        with pytest.raises(ValueError, match="block_size must be >= 1"):
            list(iter_feasible_pruned_blocks(tasks, fleet, 0))


# ---------------------------------------------------------------------------
# power-tie determinism (satellite): discrete powers force exact ties
# ---------------------------------------------------------------------------


def _tie_tasks(rng, max_tasks=5, powers=(1.0, 2.0, 3.0)):
    n_t = int(rng.integers(2, max_tasks + 1))
    out = []
    for i in range(n_t):
        nv = int(rng.integers(2, 4))
        ths = np.sort(rng.uniform(0.3, 4.0, nv))
        pws = rng.choice(powers, nv)
        out.append(
            Task(
                name=f"T{i}",
                period=50.0,
                data=float(rng.uniform(5.0, 60.0)),
                init_interval=float(rng.uniform(0.0, 5.0)),
                variants=tuple(
                    TaskVariant(cu=j + 1, throughput=float(t), power=float(p))
                    for j, (t, p) in enumerate(zip(ths, pws, strict=True))
                ),
            )
        )
    return out


class TestPowerTieDeterminism:
    def test_streamed_and_materialized_agree_under_exact_ties(self):
        """Satellite: across 100+ randomized fleets with tie-heavy power
        tables, the streamed orders (heap and block) must equal the
        materialized stable-argsort order combo for combo."""
        rng = np.random.default_rng(42)
        ties = 0
        for _ in range(120):
            tasks = _tie_tasks(rng)
            fleet = _random_fleet(rng)
            feas = search_feasible(tasks, fleet)
            order = feas.tfs_indices_by_power()
            ties += int((np.diff(feas.total_power[order]) == 0).sum())
            mat = [feas.combo_at(int(i)) for i in order]
            assert list(iter_feasible_pruned(tasks, fleet)) == mat
            assert _block_order(tasks, fleet, 7) == mat
        assert ties > 500  # the instances actually exercised exact ties

    def test_tie_order_is_flat_index_order(self):
        """Within an exact-power tie run, combos come out in ascending TSS
        flat (C-order variant-index) order."""
        rng = np.random.default_rng(3)
        checked = 0
        for _ in range(40):
            tasks = _tie_tasks(rng)
            fleet = _random_fleet(rng)
            combos = list(iter_feasible_pruned(tasks, fleet))
            for a, b in zip(combos, combos[1:], strict=False):
                if a.total_power == b.total_power:
                    assert a.variant_idx < b.variant_idx
                    checked += 1
        assert checked > 100


# ---------------------------------------------------------------------------
# tightened heterogeneous eq-7 prefix bound
# ---------------------------------------------------------------------------


class TestHeteroPrefixBound:
    def test_scalar_overhead_twin_matches_vectorized(self):
        rng = np.random.default_rng(8)
        for _ in range(50):
            fleet = _random_fleet(rng)
            n_t = int(rng.integers(1, 7))
            w = rng.uniform(0.0, fleet.capacity * 1.5, 32)
            want = config_overhead_lower_bound(fleet, n_t, w)
            fn = _scalar_overhead_lb(fleet, n_t)
            got = np.asarray([fn(float(x)) for x in w])
            assert (got == want).all()  # bitwise twin

    def test_streamed_tfs_equals_exhaustive_on_hetero(self):
        """The prefix bound prunes nothing the exhaustive hetero filter
        keeps (and vice versa): identical row sets in identical order."""
        rng = np.random.default_rng(5)
        rows = 0
        for _ in range(60):
            tasks = _random_tasks(rng, max_tasks=4)
            fleet = _random_fleet(rng)
            if not fleet.is_heterogeneous:
                continue
            mat = _materialized_order(tasks, fleet)
            assert _block_order(tasks, fleet, 16) == mat
            assert list(iter_feasible_pruned(tasks, fleet)) == mat
            rows += len(mat)
        assert rows > 200


# ---------------------------------------------------------------------------
# scheduler streaming path: ramp invariance + cross-path parity
# ---------------------------------------------------------------------------


class TestStreamingSchedulerParity:
    def test_ramp_vs_fixed_block_sizes_identical(self):
        rng = np.random.default_rng(77)
        checked = 0
        for _ in range(20):
            tasks = _random_tasks(rng)
            fleet = _random_fleet(rng)
            results = []
            for bs in (None, 1, 3, 4096):
                for exhaustive in (True, False):
                    sched = PADPSFRScheduler(
                        fleet, exhaustive=exhaustive, block_size=bs
                    )
                    results.append(
                        sched.schedule(tasks, count_all_rejects=True)
                    )
            first = results[0]
            for other in results[1:]:
                _assert_results_identical(other, first)
                assert other.n_placement_rejects == first.n_placement_rejects
            if first.feasible:
                checked += 1
        assert checked > 5

    def test_streaming_matches_scalar_oracle_engine(self):
        rng = np.random.default_rng(13)
        for _ in range(25):
            tasks = _random_tasks(rng, max_tasks=4)
            fleet = _random_fleet(rng)
            rs = PADPSFRScheduler(
                fleet, engine="scalar", exhaustive=False
            ).schedule(tasks, count_all_rejects=True)
            rb = PADPSFRScheduler(fleet, exhaustive=False).schedule(
                tasks, count_all_rejects=True
            )
            _assert_results_identical(rb, rs)

    def test_walk_stats_record_ramp_and_phases(self):
        tasks, fleet = example1_tasks(), example1_fleet()
        ws = WalkStats()
        res = PADPSFRScheduler(fleet, exhaustive=False).schedule(
            tasks, count_all_rejects=True, walk_stats=ws
        )
        assert res.feasible
        assert ws.rows == 620  # full TFS walked under count_all_rejects
        assert ws.block_sizes[0] == 64  # the ramp starts small
        assert sum(ws.block_sizes) == ws.rows
        assert ws.total_us > 0
        d = ws.as_dict()
        assert d["n_blocks"] == len(ws.block_sizes)

    def test_early_winner_stops_enumeration(self):
        """A shallow winner must not walk (or even enumerate) the deep TFS:
        the adaptive ramp caps the scanned rows at the first block, and
        eager backends (numpy) resolve each block before pulling the next
        — no speculative second block."""
        tasks, fleet = example1_tasks(), example1_fleet()
        ws = WalkStats()
        res = PADPSFRScheduler(fleet, exhaustive=False).schedule(
            tasks, walk_stats=ws
        )
        assert res.feasible and res.chosen_rank == 4
        assert ws.rows == 64  # exactly the first ramp block


# ---------------------------------------------------------------------------
# asynchronous dispatch (double buffering)
# ---------------------------------------------------------------------------


@needs_jax
class TestAsyncDispatchParity:
    @pytest.mark.parametrize("engine", ["jax", "pallas"])
    def test_dispatch_block_equals_place_block(self, engine):
        rng = np.random.default_rng(21)
        backend = get_backend(engine)
        for _ in range(5):
            B, n_t, n_f = int(rng.integers(1, 40)), 4, 5
            shares = rng.uniform(1.0, 40.0, (B, n_t))
            iis = rng.uniform(0.0, 5.0, n_t)
            t_slr = rng.uniform(40.0, 90.0, n_f)
            t_cfg = rng.uniform(0.0, 6.0, n_f)
            resolve = backend.dispatch_block(shares, iis, t_slr, t_cfg, None)
            a = resolve()
            b = backend.place_block(shares, iis, t_slr, t_cfg, None)
            assert (a.feasible == b.feasible).all()
            assert (a.placed_tasks == b.placed_tasks).all()
            assert (a.n_splits == b.n_splits).all()
            assert (a.devices_used == b.devices_used).all()

    def test_pipelined_streaming_schedule_matches_scalar(self):
        rng = np.random.default_rng(31)
        for _ in range(8):
            tasks = _random_tasks(rng, max_tasks=4)
            fleet = _random_fleet(rng)
            rs = PADPSFRScheduler(
                fleet, engine="scalar", exhaustive=False
            ).schedule(tasks, count_all_rejects=True)
            rj = PADPSFRScheduler(fleet, engine="jax", exhaustive=False).schedule(
                tasks, count_all_rejects=True
            )
            _assert_results_identical(rj, rs)

    def test_dispatch_block_degenerate_blocks(self):
        backend = get_backend("jax")
        bp = backend.dispatch_block(
            np.zeros((3, 0)), [], np.ones(2), np.zeros(2), None
        )()
        assert bp.feasible.all()  # n_t == 0: vacuously feasible
        bp = backend.dispatch_block(
            np.ones((2, 2)), [1.0, 1.0], np.empty(0), np.empty(0), None
        )()
        assert not bp.feasible.any()  # n_f == 0: nothing places


# ---------------------------------------------------------------------------
# outer_sum in-place accumulation (satellite regression)
# ---------------------------------------------------------------------------


class TestOuterSumRegression:
    def test_bitwise_equal_to_left_fold(self):
        rng = np.random.default_rng(2)
        for _ in range(20):
            vecs = [
                rng.uniform(0.0, 50.0, int(rng.integers(1, 5)))
                for _ in range(int(rng.integers(1, 5)))
            ]
            got = outer_sum(vecs)
            acc = np.zeros((1,))
            for v in vecs:
                acc = (acc[:, None] + v[None, :]).reshape(-1)
            assert (got == acc).all()  # bitwise: same fold order

    def test_combo_folds_match_outer_sum_rows(self):
        """``TaskSetCombo`` totals are the enumerators' fold bit for bit.

        Warm replans bound numpy-folded candidate rows by a combo's
        ``total_power``; Python 3.12's compensated ``sum`` differs from
        the left fold on about a quarter of these rows, which once dropped
        the incumbent row from a warm arrival's candidates."""
        rng = np.random.default_rng(0)
        vecs = [rng.uniform(3.0, 9.0, 4) for _ in range(5)]
        folded = outer_sum(vecs)
        for flat, idx in enumerate(np.ndindex(*(len(v) for v in vecs))):
            vals = tuple(float(v[i]) for v, i in zip(vecs, idx, strict=True))
            combo = TaskSetCombo(idx, vals, vals)
            assert combo.total_power == folded[flat]
            assert combo.sum_shr == folded[flat]

    def test_empty_input(self):
        assert (outer_sum([]) == np.zeros(1)).all()

    def test_zero_length_factor_gives_empty_product(self):
        out = outer_sum([np.asarray([]), np.asarray([1.0, 2.0])])
        assert out.shape == (0,)
        out = outer_sum([np.asarray([1.0]), np.asarray([])])
        assert out.shape == (0,)

    def test_large_product_values(self):
        vecs = [np.arange(1.0, 11.0)] * 6 + [np.asarray([0.25, 0.5])]
        out = outer_sum(vecs)  # 2e6 rows
        assert out.shape == (2_000_000,)
        assert out[0] == 6 * 1.0 + 0.25
        assert out[-1] == 6 * 10.0 + 0.5
        idx = [3, 1, 4, 1, 5, 9, 1]
        flat = 0
        for i, v in zip(idx, vecs, strict=True):
            flat = flat * v.shape[0] + i
        assert out[flat] == sum(v[i] for i, v in zip(idx, vecs, strict=True))

    def test_peak_memory_capped_at_output_size(self):
        """The old fold held the previous level alive while materialising
        the next (1.5x output at a final 2-wide level); the in-place
        accumulate allocates the output once."""
        vecs = [np.arange(1.0, 11.0)] * 6 + [np.asarray([0.25, 0.5])]
        out_bytes = 2_000_000 * 8
        outer_sum(vecs)  # warm any numpy internals
        tracemalloc.start()
        outer_sum(vecs)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert peak < out_bytes * 1.25, f"peak {peak} vs output {out_bytes}"


# ---------------------------------------------------------------------------
# deep-rank smoke: the streaming pipeline end to end
# ---------------------------------------------------------------------------


def test_deep_band_instance_streams_to_the_winner():
    """A small version of the benchmark's deep-band instance: thousands of
    eq-7-passing rows fail placement before the winner; streamed and
    PR-2-style walks agree on winner, rank, and combo."""
    from benchmarks.scheduler_scale import _band_tasks
    from repro.core.scheduler import select_lowest_power_batched

    tasks = _band_tasks(7, 4, base=101.0)
    fleet = FleetSpec(n_f=5, t_slr=100.0, t_cfg=0.0)
    ws = WalkStats()
    res = PADPSFRScheduler(fleet, exhaustive=False).schedule(
        tasks, walk_stats=ws
    )
    assert res.feasible and res.chosen_rank > 100
    combo, _, rank, _ = select_lowest_power_batched(
        iter_feasible_pruned(tasks, fleet), tasks, fleet, block_size=512
    )
    assert rank == res.chosen_rank and combo == res.combo
    # the ramp actually ramped
    assert ws.block_sizes[0] == 64
    assert any(b > 64 for b in ws.block_sizes)
