"""Event-replay equivalence: the service's live plan is always
bit-identical to a cold ``schedule()`` of whatever task set survived.

This is the warm-start soundness property from ``repro.core.replan``
exercised end-to-end: random traces of arrivals / exits / device
failures flow through :class:`repro.service.SchedulerService` (plan
cache on and off, exhaustive recording on and off), and after every
trace the final plan — winner variants, power, rank, reject count, and
the scalar placement plan itself — must equal a from-scratch solve of
the final task tuple on the final fleet, across placement engines.
"""

import random

import pytest

from repro.core import FleetSpec, PADPSFRScheduler, Task, TaskVariant, WalkStats
from repro.core.placement_backends import available_backends
from repro.service import (
    DeviceFailure,
    SchedulerService,
    TaskArrival,
    TaskExit,
)

ENGINES = [e for e in ("scalar", "numpy", "jax") if e in available_backends()]


def _rand_task(rng, name, *, int_powers=False):
    variants = tuple(
        TaskVariant(
            cu=1,
            throughput=rng.uniform(1.0, 8.0),
            power=float(rng.randint(1, 8)) if int_powers else rng.uniform(1, 10),
        )
        for _ in range(rng.randint(1, 3))
    )
    return Task(
        name=name,
        period=rng.uniform(5, 20),
        data=rng.uniform(10, 60),
        init_interval=rng.uniform(0.0, 1.0),
        variants=variants,
    )


def _assert_matches_cold(svc):
    if not svc.tasks:
        assert svc.plan is None
        return
    cold = PADPSFRScheduler(svc.fleet, engine=svc.engine).schedule(
        svc.tasks, **svc.placement_kw
    )
    live = svc.plan
    assert live is not None
    assert live.feasible == cold.feasible
    assert live.chosen_rank == cold.chosen_rank
    assert live.n_placement_rejects == cold.n_placement_rejects
    assert live.total_power == cold.total_power
    if cold.feasible:
        assert live.combo.variant_idx == cold.combo.variant_idx
        assert str(live.plan) == str(cold.plan)


@pytest.mark.parametrize("engine", ENGINES)
def test_random_event_traces_bit_identical(engine):
    n_trials = 6 if engine == "scalar" else 10
    for seed in range(n_trials):
        rng = random.Random(1000 * ENGINES.index(engine) + seed)
        fleet = FleetSpec(
            n_f=rng.randint(2, 3),
            t_slr=rng.uniform(15, 40),
            t_cfg=rng.uniform(0.0, 1.5),
        )
        svc = SchedulerService(
            fleet,
            engine=engine,
            record_exhaustive=bool(seed % 2),
            cache_plans=bool(seed % 3),
        )
        counter = 0
        events = []
        for _ in range(rng.randint(3, 6)):
            roll = rng.random()
            if roll < 0.55 or not svc.tasks:
                counter += 1
                events.append(
                    TaskArrival(
                        _rand_task(rng, f"t{counter}", int_powers=seed % 2 == 0)
                    )
                )
            elif roll < 0.9:
                events.append(TaskExit(rng.choice(svc.tasks).name))
            elif svc.fleet.n_f > 1:
                events.append(DeviceFailure())
            svc.replay(events[-1:])
            _assert_matches_cold(svc)
        assert len(svc.telemetry) == len(events)


def test_warm_arrival_levels_match_cold():
    """Direct replan-level check, hammering the tie-break path with
    integer powers and both recording modes."""
    for seed in range(14):
        rng = random.Random(77 + seed)
        fleet = FleetSpec(
            n_f=rng.randint(1, 3),
            t_slr=rng.uniform(15, 40),
            t_cfg=rng.uniform(0.0, 1.5),
        )
        tasks = [
            _rand_task(rng, f"t{i}", int_powers=True)
            for i in range(rng.randint(2, 4))
        ]
        sch = PADPSFRScheduler(fleet, engine="numpy")
        rec = sch.schedule(
            tasks, record_state=True, record_exhaustive=seed % 2 == 0
        )
        extended = tasks + [_rand_task(rng, "new", int_powers=True)]
        warm = sch.replan(rec.plan_state, extended)
        cold = sch.schedule(extended)
        assert warm.feasible == cold.feasible
        assert warm.chosen_rank == cold.chosen_rank
        assert warm.n_placement_rejects == cold.n_placement_rejects
        assert warm.total_power == cold.total_power
        if cold.feasible:
            assert warm.combo.variant_idx == cold.combo.variant_idx
            assert str(warm.plan) == str(cold.plan)


def test_warm_exit_transfers_reject_depths_zero_dispatch():
    """Death-depth transfer, pinned directly: every recorded reject dies
    among the surviving tasks, so the warm exit re-finds the winner
    without dispatching a single placement row.

    Construction: 2 devices x 30 slots, t_cfg=0 (the eq-7 budget is
    then task-count independent, so the gap walk is empty).  The
    all-cheap combo's shares sum to 59 — inside the eq-7 budget of 60,
    but placing it needs two splits and each split re-pays II=2, so the
    primary sweep dies on the third task (depth 2).  A near-zero eps
    task appended *last* is exhaustively recorded; dropping it leaves
    the reject's death depth (2) strictly below the dropped position
    (3), and the winner's PLACEABLE verdict survives verbatim — the
    warm walk should consume only transferred verdicts.
    """
    fleet = FleetSpec(n_f=2, t_slr=30.0, t_cfg=0.0)
    # share = data * t_slr / (period * th) = 3 / th
    def task(name, shr_cheap, p_cheap, p_exp):
        return Task(name, period=10.0, data=1.0, init_interval=2.0,
                    variants=(TaskVariant(cu=1, throughput=3.0 / shr_cheap,
                                          power=p_cheap),
                              TaskVariant(cu=1, throughput=3.0 / 13.0,
                                          power=p_exp)))

    tasks = [task("a", 21.0, 1.0, 5.0), task("b", 21.0, 2.0, 6.0),
             task("c", 17.0, 3.0, 7.0)]
    eps = Task("eps", period=50.0, data=1.0, init_interval=1.0,
               variants=(TaskVariant(cu=1, throughput=30.0 / (50.0 * 1e-6),
                                     power=1e-6),))
    sched = PADPSFRScheduler(fleet, engine="numpy")

    rec = sched.schedule([*tasks, eps], record_state=True,
                         record_exhaustive=True)
    assert rec.feasible
    # the recording saw real placement rejects, all dying at depth 2
    depths = rec.plan_state.rec_depth
    n = len(tasks) + 1
    died = depths[(depths >= 0) & (depths < n)]
    assert died.size > 0 and died.max() == 2

    stats = WalkStats()
    warm = sched.replan(rec.plan_state, tasks, walk_stats=stats)
    cold = sched.schedule(tasks)
    assert cold.chosen_rank > 0  # the transferred rejects are load-bearing
    assert warm.feasible and cold.feasible
    assert warm.chosen_rank == cold.chosen_rank
    assert warm.n_placement_rejects == cold.n_placement_rejects
    assert warm.total_power == cold.total_power
    assert warm.combo.variant_idx == cold.combo.variant_idx
    assert str(warm.plan) == str(cold.plan)
    # the whole point: no placement row was probed or dispatched
    assert stats.rows == 0


def _v(th, pw):
    return TaskVariant(cu=1, throughput=th, power=pw)


def _abc():
    a = Task("a", period=10.0, data=20.0, init_interval=1.0,
             variants=(_v(2.0, 5.0), _v(4.0, 8.0)))
    b = Task("b", period=10.0, data=40.0, init_interval=1.0,
             variants=(_v(4.0, 4.0), _v(8.0, 6.0)))
    c = Task("c", period=10.0, data=30.0, init_interval=1.0,
             variants=(_v(6.0, 3.0), _v(12.0, 9.0)))
    return a, b, c


def test_admission_filter_and_rollback():
    a, b, c = _abc()
    svc = SchedulerService(FleetSpec(n_f=2, t_slr=30.0, t_cfg=1.0))
    assert svc.submit(a).admitted and svc.submit(b).admitted
    before = svc.plan

    dup = svc.submit(Task("a", period=9.0, data=5.0, init_interval=0.0,
                          variants=(_v(5.0, 1.0),)))
    assert not dup.admitted and dup.path == "admission"
    assert "duplicate" in dup.reason

    hopeless = svc.submit(Task("big", period=10.0, data=10000.0,
                               init_interval=1.0, variants=(_v(2.0, 1.0),)))
    assert not hopeless.admitted and hopeless.path == "admission"
    assert "eq-7" in hopeless.reason

    # passes the eq-7 filter (modest share) but can never place: its II
    # exceeds every device's usable window — rolled back after replan
    tight = svc.submit(Task("tight", period=10.0, data=48.0,
                            init_interval=29.0, variants=(_v(6.0, 1.0),)))
    assert not tight.admitted and tight.path in ("warm", "general")
    assert svc.tasks == (a, b)
    assert svc.plan is before  # untouched plan object

    _assert_matches_cold(svc)


def test_plan_cache_steady_state_churn():
    a, b, _ = _abc()
    svc = SchedulerService(FleetSpec(n_f=3, t_slr=30.0, t_cfg=1.0))
    svc.submit(a)
    svc.submit(b)
    svc.remove(b.name)
    back = svc.submit(b)  # same tuple (a, b) on the same fleet as before
    assert back.path == "cache"
    assert back.latency_s < 0.05
    _assert_matches_cold(svc)

    uncached = SchedulerService(
        FleetSpec(n_f=3, t_slr=30.0, t_cfg=1.0), cache_plans=False
    )
    uncached.submit(a)
    uncached.submit(b)
    uncached.remove(b.name)
    assert uncached.submit(b).path != "cache"
    _assert_matches_cold(uncached)


def test_device_failure_degrades_and_replans():
    a, b, c = _abc()
    svc = SchedulerService(FleetSpec(n_f=2, t_slr=30.0, t_cfg=1.0))
    svc.submit(a)
    svc.submit(b)
    tel = svc.fail_device()
    assert tel.admitted and svc.fleet.n_f == 1
    _assert_matches_cold(svc)

    last = svc.fail_device()
    assert not last.admitted and "last device" in last.reason
    assert svc.fleet.n_f == 1

    # heterogeneous failure drops the indexed profile
    from repro.core import DeviceProfile

    hsvc = SchedulerService(FleetSpec.heterogeneous(
        [DeviceProfile(t_slr=30.0, t_cfg=1.0),
         DeviceProfile(t_slr=20.0, t_cfg=0.1, klass="gpu")]))
    hsvc.submit(a)
    hsvc.fail_device(1)
    assert hsvc.fleet.n_f == 1 and hsvc.fleet.devices[0].klass == "fpga"
    _assert_matches_cold(hsvc)


def test_telemetry_trace_is_complete():
    a, b, _ = _abc()
    svc = SchedulerService(FleetSpec(n_f=2, t_slr=30.0, t_cfg=1.0))
    svc.replay([TaskArrival(a), TaskArrival(b), TaskExit("a")])
    assert [t.event for t in svc.telemetry] == [
        "arrival(a)", "arrival(b)", "exit(a)",
    ]
    assert all(t.latency_s >= 0 for t in svc.telemetry)
    assert svc.telemetry[-1].n_tasks == 1
    assert svc.telemetry[-1].feasible


def test_solve_path_telemetry_classifies_warm_and_general():
    """The telemetry label keys off :attr:`PlanState.origin`: the first
    arrival cold-solves (general) and every later arrival chains warm
    through the recorded root.  Regression for the
    ``record_exhaustive=True`` carry-over bug: the warm path used to emit
    a thin state that forced the *third* arrival cold — now two (and
    three) consecutive arrivals all take the warm path.  The live plan
    stays bit-identical to cold throughout."""
    fleet = FleetSpec(n_f=3, t_slr=30.0, t_cfg=1.0)

    def mk(name, power):
        return Task(
            name=name,
            period=10.0,
            data=20.0,
            init_interval=1.0,
            variants=(TaskVariant(cu=1, throughput=6.0, power=power),),
        )

    svc = SchedulerService(fleet, engine="numpy")
    rows = [svc.submit(mk("a", 2.0)), svc.submit(mk("b", 3.0)),
            svc.submit(mk("c", 1.0)), svc.submit(mk("d", 2.5))]
    assert all(r.admitted for r in rows)
    assert [r.path for r in rows] == ["general", "warm", "warm", "warm"]
    _assert_matches_cold(svc)


def test_warm_exit_and_failure_telemetry_paths():
    """Exits of root tasks classify as ``warm_exit`` and device failures
    as ``warm_failure``; both stay bit-identical to cold.  (An exit of a
    task the state *appended* legitimately rides the arrival projection
    and reports plain ``warm``.)  ``max_stale=1`` keeps the root fresh so
    every removal replans against a full exhaustive recording."""
    a, b, c = _abc()
    svc = SchedulerService(FleetSpec(n_f=3, t_slr=30.0, t_cfg=1.0), max_stale=1)
    svc.submit(a)
    svc.submit(b)
    svc.submit(c)
    assert svc.rerecord_count >= 1
    _assert_matches_cold(svc)

    tel = svc.remove("a")  # root task: projection path
    assert tel.path == "warm_exit"
    _assert_matches_cold(svc)

    tel = svc.fail_device()
    assert tel.path == "warm_failure"
    _assert_matches_cold(svc)


def _mixed_trace(rng, svc, n_events):
    """Drive ``svc`` through ``n_events`` mixed events, checking the live
    plan against a cold solve after every prefix."""
    counter = 0
    paths = []
    for _ in range(n_events):
        roll = rng.random()
        n_alive = len(svc.tasks)
        if (roll < 0.45 and n_alive < 4) or n_alive == 0:
            counter += 1
            tel = svc.submit(_rand_task(rng, f"t{counter}", int_powers=True))
        elif roll < 0.80 and n_alive:
            tel = svc.remove(rng.choice(svc.tasks).name)
        elif roll < 0.90 and svc.fleet.n_f > svc.resilience + 1:
            tel = svc.fail_device()
        else:
            tel = svc.recover_device()
        paths.append(tel.path)
        _assert_matches_cold(svc)
    return paths


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("resilience", [0, 1])
def test_churn_trace_prefix_equivalence(engine, resilience):
    """Every prefix of a 100+-event mixed arrival/exit/failure/recovery
    trace yields plans bit-identical to cold ``schedule()`` — per engine
    and for resilience k=0 and k=1.  The staleness-bounded re-record
    policy runs live inside the trace (it raises on any warm/cold
    divergence, so it doubles as an equivalence oracle)."""
    rng = random.Random(4242 + 17 * ENGINES.index(engine) + resilience)
    svc = SchedulerService(
        FleetSpec(n_f=3, t_slr=35.0, t_cfg=1.0),
        engine=engine,
        resilience=resilience,
        max_stale=5,
    )
    n_events = 60 if engine == "scalar" else 110
    paths = _mixed_trace(rng, svc, n_events)
    assert len(svc.telemetry) == n_events
    # the trace must actually exercise the warm machinery
    solved = [p for p in paths if p not in ("admission", "noop")]
    assert any(p in ("warm", "warm_exit", "warm_failure", "cache")
               for p in solved)


def test_rerecord_policy_fires_and_preserves_plan():
    """With a tight ``max_stale`` the re-record policy swaps in a fresh
    exhaustive root mid-trace; the plan is unchanged (the policy raises
    on any mismatch) and later arrivals keep hitting the warm path."""
    rng = random.Random(99)
    svc = SchedulerService(
        FleetSpec(n_f=3, t_slr=35.0, t_cfg=1.0), max_stale=2
    )
    _mixed_trace(rng, svc, 40)
    assert svc.rerecord_count >= 1
    _assert_matches_cold(svc)


def test_warm_arrival_keeps_incumbent_row_after_rerecord():
    """Seeded churn trace whose 19th event, an arrival after two warm
    exits and a re-record, once lost its incumbent row: the incumbent's
    total power came from Python's compensated ``sum`` while the warm
    candidates carried the enumerators' left fold, one ulp apart.  Every
    event's live plan must equal a cold solve of what survived it."""
    from benchmarks.scheduler_scale import _short_churn_trace

    svc = SchedulerService(FleetSpec(n_f=4, t_slr=35.0, t_cfg=1.0))
    for _ in _short_churn_trace(svc):
        _assert_matches_cold(svc)
    assert svc.rerecord_count >= 1
