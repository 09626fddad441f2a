"""Pluggable placement-backend architecture: registry, regressions, parity.

The backend contract (``repro.core.placement_backends``) pins every engine
to the scalar Alg-2/Alg-3 oracle bit-for-bit.  This file covers:

* registry semantics (names, aliases, ``auto``, custom registration);
* the empty-fleet and ``block_size`` regressions;
* ``_walk_tfs_blocks`` bookkeeping invariants across block sizes and
  ``count_all_rejects`` — backend-independent by construction;
* jax-gated cross-backend parity (jit'd ``lax.while_loop`` sweep and the
  fused Pallas kernel) on the paper's Figs 2-4 examples and >= 100
  randomized heterogeneous fleets under the engines' scoped x64
  (``placement_backends.jax_runtime.x64``).

The randomized-instance harness is shared with
``tests/test_placement_batched.py``.
"""

from pathlib import Path
from types import SimpleNamespace

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
    PADPSFRScheduler,
    available_backends,
    backend_names,
    get_backend,
    place_batch,
    place_combo,
    resolve_engine,
    search_feasible,
)
from repro.core.placement_backends import (
    BatchPlacement,
    PlacementOptions,
    prepare_block,
    register_backend,
)

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


def _full_tfs_block(tasks, fleet):
    feas = search_feasible(tasks, fleet)
    order = feas.tfs_indices_by_power()
    iis = [t.init_interval for t in tasks]
    return feas, order, feas.shares_matrix(order) if order.size else None, iis


def _assert_blocks_identical(a: BatchPlacement, b: BatchPlacement, ctx: str = ""):
    assert (a.feasible == b.feasible).all(), f"{ctx}: feasible mask"
    assert (a.placed_tasks == b.placed_tasks).all(), f"{ctx}: placed_tasks"
    assert (a.n_splits == b.n_splits).all(), f"{ctx}: n_splits"
    assert (a.devices_used == b.devices_used).all(), f"{ctx}: devices_used"


def _backend_vs_oracle(tasks, fleet, backend_name, **kw) -> int:
    """Backend verdicts vs the scalar oracle per row, over the full TFS."""
    feas, order, shares, iis = _full_tfs_block(tasks, fleet)
    if shares is None:
        return 0
    opts = PlacementOptions(**kw)
    bp = get_backend(backend_name).place_block(
        shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr, opts
    )
    for i, fi in enumerate(order):
        plan = place_combo(feas.combo_at(int(fi)), tasks, fleet, **kw)
        assert plan.feasible == bool(bp.feasible[i]), f"{backend_name} row {i}"
        if plan.feasible:
            assert plan.n_splits == int(bp.n_splits[i]), f"{backend_name} row {i}"
    return int(order.size)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_core_engines_registered(self):
        names = backend_names()
        for name in ("scalar", "numpy", "jax", "pallas"):
            assert name in names
        # zero-dependency engines are always available
        avail = available_backends()
        assert "numpy" in avail and "scalar" in avail

    def test_aliases_and_auto(self):
        assert resolve_engine("batched") == "numpy"
        assert resolve_engine("auto") in available_backends()
        if not HAS_JAX:
            assert resolve_engine("auto") == "numpy"

    def test_unknown_engine_raises(self):
        with pytest.raises(ValueError, match="unknown placement engine"):
            resolve_engine("fpga-magic")
        with pytest.raises(ValueError, match="unknown placement engine"):
            PADPSFRScheduler(example1_fleet(), engine="fpga-magic")

    def test_jax_engines_report_unavailable_without_jax(self):
        if HAS_JAX:
            assert "jax" in available_backends()
        else:
            assert "jax" not in available_backends()
            with pytest.raises(RuntimeError, match=r"install the \[jax\] extra"):
                get_backend("jax")

    def test_register_custom_backend(self):
        """The documented extension point: a registered class resolves by
        name and drives the scheduler end to end.  The fake engine is
        removed from the process-global registry afterwards."""
        from repro.core.placement_backends import base as backends_base

        try:

            @register_backend("numpy-echo-test")
            class EchoBackend:
                name = "numpy-echo-test"
                calls = 0

                @classmethod
                def available(cls):
                    return True

                def place_block(self, shares, iis, t_slr, t_cfg, opts=None):
                    type(self).calls += 1
                    return get_backend("numpy").place_block(
                        shares, iis, t_slr, t_cfg, opts
                    )

            tasks, fleet = example1_tasks(), example1_fleet()
            re = PADPSFRScheduler(fleet, engine="numpy-echo-test").schedule(tasks)
            rn = PADPSFRScheduler(fleet, engine="numpy").schedule(tasks)
            assert EchoBackend.calls > 0
            assert re.chosen_rank == rn.chosen_rank == 4
            assert re.combo == rn.combo
        finally:
            backends_base._REGISTRY.pop("numpy-echo-test", None)
            backends_base._INSTANCES.pop("numpy-echo-test", None)
        assert "numpy-echo-test" not in backend_names()

    def test_reregistering_name_replaces_cached_instance(self):
        """Overriding a name drops the previously cached instance."""
        from repro.core.placement_backends import base as backends_base
        from repro.core.placement_backends.numpy_backend import (
            NumpyPlacementBackend,
        )

        try:

            @register_backend("override-test")
            class FirstBackend(NumpyPlacementBackend):
                name = "override-test"

            first = get_backend("override-test")
            assert isinstance(first, FirstBackend)

            @register_backend("override-test")
            class SecondBackend(NumpyPlacementBackend):
                name = "override-test"

            second = get_backend("override-test")
            assert isinstance(second, SecondBackend)
            assert second is not first
        finally:
            backends_base._REGISTRY.pop("override-test", None)
            backends_base._INSTANCES.pop("override-test", None)


# ---------------------------------------------------------------------------
# regressions: empty fleet, block_size validation
# ---------------------------------------------------------------------------


class TestEmptyFleetRegression:
    """place_batch with n_f == 0 and n_t > 0 used to IndexError on the
    ``t_cfg_arr[jj]`` gather; it must return an all-infeasible verdict."""

    def _stub_fleet(self):
        return SimpleNamespace(
            n_f=0,
            t_slr_arr=np.empty(0, dtype=np.float64),
            t_cfg_arr=np.empty(0, dtype=np.float64),
        )

    def test_place_batch_empty_fleet_all_infeasible(self):
        shares = np.asarray([[10.0, 20.0], [5.0, 5.0]])
        bp = place_batch(shares, [1.0, 2.0], self._stub_fleet())
        assert not bp.feasible.any()
        assert (bp.placed_tasks == 0).all()
        assert (bp.devices_used == 0).all()

    @pytest.mark.parametrize("backend", ["numpy", "scalar"])
    def test_backends_empty_fleet(self, backend):
        shares = np.asarray([[10.0, 20.0]])
        bp = get_backend(backend).place_block(
            shares, [1.0, 2.0], np.empty(0), np.empty(0)
        )
        assert not bp.feasible.any()

    def test_empty_fleet_empty_tasks_vacuously_feasible(self):
        bp = place_batch(np.zeros((3, 0)), [], self._stub_fleet())
        assert bp.feasible.all()

    def test_prepare_block_shape_validation(self):
        with pytest.raises(ValueError, match=r"shares must be \(B, n_t\)"):
            prepare_block(np.zeros(4), [], np.ones(1), np.zeros(1), None)
        with pytest.raises(ValueError, match="init_intervals"):
            prepare_block(np.zeros((2, 3)), [1.0], np.ones(1), np.zeros(1), None)


class TestBlockSizeValidation:
    @pytest.mark.parametrize("bad", [0, -1, -4096])
    def test_rejects_nonpositive(self, bad):
        with pytest.raises(ValueError, match="block_size must be >= 1"):
            PADPSFRScheduler(example1_fleet(), block_size=bad)

    def test_block_size_one_still_schedules(self):
        res = PADPSFRScheduler(example1_fleet(), block_size=1).schedule(
            example1_tasks()
        )
        assert res.feasible and res.chosen_rank == 4

    def test_batched_selectors_reject_nonpositive(self):
        """The guard sits where block_size is consumed, not only in the
        facade: block_size=0 used to silently return no winner on the
        streaming path and raise an opaque range() error exhaustively."""
        from repro.core.scheduler import (
            _select_from_feasibility,
            select_lowest_power_batched,
        )

        tasks, fleet = example1_tasks(), example1_fleet()
        feas = search_feasible(tasks, fleet)
        with pytest.raises(ValueError, match="block_size must be >= 1"):
            select_lowest_power_batched(
                feas.iter_tfs_by_power(), tasks, fleet, block_size=0
            )
        with pytest.raises(ValueError, match="block_size must be >= 1"):
            _select_from_feasibility(feas, tasks, fleet, block_size=0)


# ---------------------------------------------------------------------------
# _walk_tfs_blocks bookkeeping invariants (backend-independent)
# ---------------------------------------------------------------------------


class TestWalkInvariants:
    """Chosen rank, reject count and plan must not depend on how the TFS
    stream is chopped into blocks, nor on the reject-counting mode."""

    @pytest.mark.parametrize("exhaustive", [True, False], ids=["exhaustive", "streaming"])
    def test_block_size_and_reject_mode_invariance(self, exhaustive):
        rng = np.random.default_rng(123)
        checked = 0
        for _ in range(25):
            tasks = _random_tasks(rng)
            fleet = _random_fleet(rng)
            results = {}
            for count_all in (False, True):
                per_block = []
                for bs in (1, 3, 4096):
                    sched = PADPSFRScheduler(
                        fleet, exhaustive=exhaustive, block_size=bs
                    )
                    per_block.append(
                        sched.schedule(tasks, count_all_rejects=count_all)
                    )
                first = per_block[0]
                for other in per_block[1:]:
                    _assert_results_identical(other, first)
                    assert other.n_placement_rejects == first.n_placement_rejects
                results[count_all] = first
            # Across reject modes the winner is invariant...
            assert results[False].feasible == results[True].feasible
            assert results[False].chosen_rank == results[True].chosen_rank
            assert results[False].combo == results[True].combo
            if results[False].feasible:
                # ...and without count_all the rejects are exactly the rows
                # ranked before the winner (all of which failed placement).
                assert (
                    results[False].n_placement_rejects
                    == results[False].chosen_rank
                )
                assert (
                    results[True].n_placement_rejects
                    >= results[False].n_placement_rejects
                )
                checked += 1
        assert checked > 5  # enough feasible instances actually exercised


# ---------------------------------------------------------------------------
# cross-backend parity: jax (jit'd while_loop) and pallas (fused kernel)
# ---------------------------------------------------------------------------


@needs_jax
class TestJaxBackendParity:
    @pytest.mark.parametrize("tasks_fn,fleet_fn", PAPER_CASES, ids=PAPER_IDS)
    def test_paper_examples_schedule_identical_to_scalar(self, tasks_fn, fleet_fn):
        tasks, fleet = tasks_fn(), fleet_fn()
        rj = PADPSFRScheduler(fleet, engine="jax").schedule(
            tasks, count_all_rejects=True
        )
        rs = PADPSFRScheduler(fleet, engine="scalar").schedule(
            tasks, count_all_rejects=True
        )
        _assert_results_identical(rj, rs)

    @pytest.mark.parametrize("tasks_fn,fleet_fn", PAPER_CASES, ids=PAPER_IDS)
    def test_paper_examples_full_tfs_bitwise_vs_numpy(self, tasks_fn, fleet_fn):
        tasks, fleet = tasks_fn(), fleet_fn()
        _, order, shares, iis = _full_tfs_block(tasks, fleet)
        if shares is None:
            pytest.skip("empty TFS")
        bn = get_backend("numpy").place_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )
        bj = get_backend("jax").place_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )
        _assert_blocks_identical(bj, bn, "jax-vs-numpy")

    def test_randomized_hetero_parity_100_instances(self):
        """engine="jax" agrees with the scalar oracle on >= 100 randomized
        heterogeneous fleets (acceptance criterion)."""
        rng = np.random.default_rng(42)
        rows_checked = 0
        instances = 0
        for _ in range(100):
            tasks = _random_tasks(rng)
            fleet = _random_fleet(rng)
            _, order, shares, iis = _full_tfs_block(tasks, fleet)
            if shares is not None:
                bn = get_backend("numpy").place_block(
                    shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
                )
                bj = get_backend("jax").place_block(
                    shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
                )
                _assert_blocks_identical(bj, bn, "jax-vs-numpy")
                rows_checked += int(order.size)
            rj = PADPSFRScheduler(fleet, engine="jax").schedule(
                tasks, count_all_rejects=True
            )
            rs = PADPSFRScheduler(fleet, engine="scalar").schedule(
                tasks, count_all_rejects=True
            )
            _assert_results_identical(rj, rs)
            instances += 1
        assert instances == 100
        assert rows_checked > 500

    def test_preemption_model_parity(self):
        """Parity holds under the refs-[9]/[10] capture/store knobs."""
        rng = np.random.default_rng(7)
        kw = dict(t_capture=12.0, t_store=12.0, repay_init=False)
        checked = 0
        for _ in range(20):
            tasks = _random_tasks(rng, max_tasks=4)
            fleet = _random_fleet(rng)
            checked += _backend_vs_oracle(tasks, fleet, "jax", **kw)
        assert checked > 50

    def test_block_handoff_matches_oracle_rows(self):
        """Spot-check the jax verdicts directly against the oracle (not
        just against numpy) on the paper's Example 1."""
        n = _backend_vs_oracle(example1_tasks(), example1_fleet(), "jax")
        assert n == 620  # the paper's |TFS|

    def test_scheduler_engine_auto_resolves_and_schedules(self):
        sched = PADPSFRScheduler(example1_fleet(), engine="auto")
        assert sched.engine in available_backends()
        res = sched.schedule(example1_tasks())
        assert res.feasible and res.chosen_rank == 4


@needs_jax
class TestPallasBackendParity:
    """The fused kernel runs in Pallas interpret mode off-TPU; verdicts
    must stay bit-identical to the numpy engine (and thus the oracle)."""

    @pytest.mark.parametrize("tasks_fn,fleet_fn", PAPER_CASES, ids=PAPER_IDS)
    def test_paper_examples_full_tfs_bitwise_vs_numpy(self, tasks_fn, fleet_fn):
        tasks, fleet = tasks_fn(), fleet_fn()
        _, order, shares, iis = _full_tfs_block(tasks, fleet)
        if shares is None:
            pytest.skip("empty TFS")
        bn = get_backend("numpy").place_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )
        bp = get_backend("pallas").place_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )
        _assert_blocks_identical(bp, bn, "pallas-vs-numpy")

    def test_example1_schedule_identical_to_scalar(self):
        tasks, fleet = example1_tasks(), example1_fleet()
        rp = PADPSFRScheduler(fleet, engine="pallas").schedule(
            tasks, count_all_rejects=True
        )
        rs = PADPSFRScheduler(fleet, engine="scalar").schedule(
            tasks, count_all_rejects=True
        )
        _assert_results_identical(rp, rs)

    def test_randomized_parity_10_instances(self):
        rng = np.random.default_rng(11)
        done = 0
        for _ in range(10):
            tasks = _random_tasks(rng, max_tasks=4)
            fleet = _random_fleet(rng, max_devices=4)
            _, order, shares, iis = _full_tfs_block(tasks, fleet)
            if shares is None:
                continue
            bn = get_backend("numpy").place_block(
                shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
            )
            bp = get_backend("pallas").place_block(
                shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
            )
            _assert_blocks_identical(bp, bn, "pallas-vs-numpy")
            done += 1
        assert done > 3


# ---------------------------------------------------------------------------
# scalar backend through the unified walk
# ---------------------------------------------------------------------------


def test_scalar_engine_matches_numpy_engine():
    rng = np.random.default_rng(9)
    for _ in range(15):
        tasks = _random_tasks(rng, max_tasks=4)
        fleet = _random_fleet(rng)
        rs = PADPSFRScheduler(fleet, engine="scalar").schedule(
            tasks, count_all_rejects=True
        )
        rn = PADPSFRScheduler(fleet, engine="numpy").schedule(
            tasks, count_all_rejects=True
        )
        _assert_results_identical(rs, rn)


def test_scalar_backend_block_verdicts_match_numpy():
    rng = np.random.default_rng(17)
    for _ in range(10):
        tasks = _random_tasks(rng, max_tasks=4)
        fleet = _random_fleet(rng)
        _, order, shares, iis = _full_tfs_block(tasks, fleet)
        if shares is None:
            continue
        bs = get_backend("scalar").place_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )
        bn = get_backend("numpy").place_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )
        _assert_blocks_identical(bs, bn, "scalar-vs-numpy")


def test_every_backend_exposes_the_full_dispatch_surface():
    """Runtime twin of repro-lint rule B101: every registered backend
    spells out the five surface methods and declares its pipelining via
    ``async_dispatch`` (the walk chooses depth from the flag, not from
    method presence — see ``_streaming_block_walk``)."""
    surface = (
        "place_block",
        "dispatch_block",
        "place_blocks",
        "dispatch_blocks",
        "dispatch_blocks_raw",
    )
    for name in available_backends():
        backend = get_backend(name)
        for meth in surface:
            assert callable(getattr(backend, meth, None)), (name, meth)
        assert isinstance(backend.async_dispatch, bool), name


def test_eager_backend_dispatch_matches_place():
    """The eager dispatch hooks added for contract completeness must be
    behaviorally invisible: resolver output equals the eager call."""
    rng = np.random.default_rng(20260808)
    fleet = example1_fleet()
    shares = rng.uniform(1.0, 30.0, size=(32, 4))
    iis = rng.uniform(0.0, 1.0, size=4)
    for name in ("scalar", "numpy"):
        backend = get_backend(name)
        assert backend.async_dispatch is False
        eager = backend.place_block(shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr)
        resolved = backend.dispatch_block(
            shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
        )()
        _assert_blocks_identical(eager, resolved, f"{name} dispatch parity")


@needs_jax
@pytest.mark.parametrize("preset", [None, "elsewhere"], ids=["checkout", "env"])
def test_compile_cache_dir(preset, tmp_path):
    """A preset cache directory (what ``JAX_COMPILATION_CACHE_DIR`` sets)
    is kept; without one the cache goes to ``.jax_cache/`` in the checkout."""
    import jax

    from repro.core.placement_backends import jax_runtime

    was = jax.config.jax_compilation_cache_dir
    want = str(tmp_path / preset) if preset else str(jax_runtime.CACHE_DIR)
    try:
        jax.config.update("jax_compilation_cache_dir", want if preset else None)
        assert jax_runtime.configure_compile_cache.__wrapped__() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert jax_runtime.CACHE_DIR.parent == Path(__file__).resolve().parents[1]
