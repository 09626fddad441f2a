"""Beyond-paper scheduler engineering: scaling benchmarks.

* vectorised Alg-1 (numpy outer-sum) vs the paper's nested-loop
  enumeration, at growing |TSS|;
* batched Alg-2 placement (vectorized TFS blocks) vs the scalar
  one-combo-at-a-time walk, at growing |TFS|;
* heterogeneous-fleet scheduling (mixed FPGA/GPU/CPU device classes)
  at growing fleet sizes;
* branch-and-bound streaming search (no TSS materialisation) on
  instances where the exhaustive product would not fit in memory;
* placement-backend sweep (numpy vs jax vs pallas block engines) at
  growing |TFS| block sizes, reporting per-backend rows/s and the
  numpy<->jax crossover point into the BENCH JSON;
* enumeration-throughput sweep: the PR-2 Python-heap streamer
  (``iter_feasible_pruned``) vs the block-native enumerator
  (``iter_feasible_pruned_blocks``), rows/s each;
* deep-rank streaming schedule: an instance whose winner sits >= 1e5
  rows into the TFS, walked end-to-end by the PR-2 path
  (heap + per-row combos through ``select_lowest_power_batched``) and
  by the block-native pipeline — with the per-phase WalkStats
  breakdown (enumerate / place / sync / materialize) and the adaptive
  block-ramp sizes recorded in the JSON artifact;
* delta replanning (service steady state): a task arrives on the
  deep-rank instance after an exhaustively recorded solve — warm
  ``replan()`` (recorded verdicts + resumable frontier) vs cold
  ``schedule()`` of the extended set, bit-identity asserted, cold/warm
  microseconds and the speedup recorded as ``replan_cold_*`` /
  ``replan_warm_*`` rows plus a ``replan`` JSON section;
* k-fault-tolerant scheduling: the crafted premium-ladder instance at
  ``resilience=0,1,2``, with each level's power premium over the
  unconstrained baseline recorded as ``resilience_k*`` rows plus a
  ``resilience`` JSON section, and the guarantee verified by replaying
  seeded failure traces through the fault-injection simulator
  (``repro.service.faultsim``);
* churn (warm removals + live state): the deep-rank instance's warm
  task-exit and device-failure replans vs cold ``schedule()`` of the
  post-event instance (bit-identity asserted, >= 4x target), plus a
  200-event seeded arrival/exit/failure/recovery trace through a live
  ``SchedulerService`` with the staleness-bounded re-record policy —
  warm-hit rate, per-event-kind latency, and solved-events/s vs an
  all-cold baseline, recorded as ``churn_*`` rows plus a ``churn``
  JSON section.

CLI (the CI benchmark-smoke job):

    PYTHONPATH=src python -m benchmarks.scheduler_scale --quick \
        --json BENCH_scheduler_scale.json

    # backend sweep only, explicit engines:
    PYTHONPATH=src python -m benchmarks.scheduler_scale --quick \
        --backends numpy,jax --json BENCH_scheduler_scale.json
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys

import numpy as np

from repro.core import (
    DeviceProfile,
    FleetSpec,
    PADPSFRScheduler,
    Task,
    TaskVariant,
    WalkStats,
    available_backends,
    get_backend,
    place_batch,
    place_combo,
    search_feasible,
)
from repro.core.feasibility import iter_feasible_pruned, iter_feasible_pruned_blocks
from repro.core.scheduler import select_lowest_power_batched
from repro.core.variants import make_hetero_fleet

from .util import Row, timeit

__all__ = [
    "bench_scheduler_scale",
    "bench_backend_sweep",
    "bench_enumeration_sweep",
    "bench_streaming_deep",
    "bench_replan",
    "bench_fleet_parallel",
    "bench_resilience",
    "bench_churn",
    "main",
]


def _synth_tasks(n_t: int, nv: int, seed: int = 0) -> list[Task]:
    rng = np.random.default_rng(seed)
    tasks = []
    for i in range(n_t):
        ths = np.sort(rng.uniform(0.5, 4.0, nv))
        pws = np.sort(rng.uniform(3.0, 9.0, nv))
        tasks.append(
            Task(
                name=f"S{i}",
                period=float(rng.uniform(50, 100)),
                data=float(rng.uniform(20, 60)),
                init_interval=float(rng.uniform(1, 5)),
                variants=tuple(
                    TaskVariant(cu=j + 1, throughput=float(t), power=float(p))
                    for j, (t, p) in enumerate(zip(ths, pws, strict=True))
                ),
            )
        )
    return tasks


def _loop_enumeration(tasks, fleet) -> int:
    """The paper's Alg-1 as written: nested loops over the product."""
    shares = [t.shares(fleet.t_slr) for t in tasks]
    budget = fleet.workable_budget(len(tasks))
    n_fit = 0
    for combo in itertools.product(*[range(t.nv) for t in tasks]):
        s = sum(shares[i][j] for i, j in enumerate(combo))
        if s <= budget + 1e-9:
            n_fit += 1
    return n_fit


def bench_alg2_batched_vs_scalar(quick: bool = False) -> list[Row]:
    """Batched TFS placement sweeps vs the scalar one-row-at-a-time walk.

    The acceptance target: >= 10x over the scalar walk at |TFS| >= 1e4.
    """
    rows = []
    fleet = FleetSpec(n_f=8, t_slr=80.0, t_cfg=4.0)
    sizes = [(6, 4), (7, 4)] if quick else [(6, 4), (7, 4), (8, 4)]
    for n_t, nv in sizes:  # |TSS| = 4k, 16k, 65k
        tasks = _synth_tasks(n_t, nv)
        feas = search_feasible(tasks, fleet)
        order = feas.tfs_indices_by_power()
        iis = [t.init_interval for t in tasks]
        shares = feas.shares_matrix(order)

        def batched_walk():
            return place_batch(shares, iis, fleet).n_feasible

        def scalar_walk():
            n = 0
            for fi in order:
                if place_combo(feas.combo_at(int(fi)), tasks, fleet).feasible:
                    n += 1
            return n

        n_placed = batched_walk()
        us_batched = timeit(batched_walk, repeat=3)
        us_scalar = timeit(scalar_walk, repeat=1, warmup=0)
        rows.append(
            Row(
                f"alg2_batched_tfs{order.size}",
                us_batched,
                f"scalar_us={us_scalar:.0f};speedup={us_scalar / us_batched:.0f}x"
                f";placed={n_placed}",
            )
        )
    return rows


def bench_backend_sweep(
    quick: bool = False, backends: list[str] | None = None
) -> tuple[list[Row], dict]:
    """Per-backend block-placement throughput at growing |TFS| block sizes.

    One synthetic (B, n_t) shares block per size (mixed feasible /
    infeasible rows around the fleet's capacity), handed whole to each
    backend's ``place_block``.  Returns CSV rows plus a JSON-able summary
    with per-backend rows/s and the numpy<->jax crossover block size (the
    smallest B where the jit'd jax sweep beats the numpy loop — below it
    the numpy engine's lower fixed overhead wins).
    """
    n_t, n_f = 8, 8
    fleet = FleetSpec(n_f=n_f, t_slr=80.0, t_cfg=4.0)
    rng = np.random.default_rng(3)
    iis = rng.uniform(1.0, 5.0, n_t)
    sizes = [1_000, 10_000, 100_000] if quick else [1_000, 10_000, 100_000, 1_000_000]
    if backends is None:
        # scalar is O(B) Python round-trips — pointless past a few 1e3 rows.
        backends = [b for b in available_backends() if b != "scalar"]
    rows: list[Row] = []
    us: dict[str, dict[int, float]] = {b: {} for b in backends}
    for B in sizes:
        base = rng.uniform(0.5, 1.5, (B, n_t))
        scale = rng.uniform(0.4, 1.3, (B, 1)) * fleet.capacity / n_t
        shares = base * scale
        for name in backends:
            backend = get_backend(name)

            def run():
                return backend.place_block(
                    shares, iis, fleet.t_slr_arr, fleet.t_cfg_arr
                )

            n_feasible = run().n_feasible  # warms jit/pallas caches too
            t_us = timeit(run, repeat=3)
            us[name][B] = t_us
            rows.append(
                Row(
                    f"backend_{name}_rows{B}",
                    t_us,
                    f"rows_per_s={B / t_us * 1e6:.0f};feasible={n_feasible}",
                )
            )
    crossover = None
    if "numpy" in us and "jax" in us:
        for B in sizes:
            if us["jax"][B] < us["numpy"][B]:
                crossover = B
                break
    sweep = {
        "n_t": n_t,
        "n_f": n_f,
        "sizes": sizes,
        "us": {b: {str(B): v for B, v in d.items()} for b, d in us.items()},
        "rows_per_s": {
            b: {str(B): B / v * 1e6 for B, v in d.items()} for b, d in us.items()
        },
        # Smallest block size where the jax sweep overtakes the numpy loop
        # (None: jax never won, or one of the two engines was not swept).
        "numpy_jax_crossover_rows": crossover,
    }
    return rows, sweep


def _band_tasks(
    n_t: int,
    nv: int,
    seed: int = 7,
    base: float = 86.0,
    slope: float = 5.0,
    noise: float = 1.0,
    ii: tuple[float, float] = (8.0, 16.0),
) -> list[Task]:
    """Tasks whose shares decrease near-affinely with power.

    Low power => low throughput => high share (the paper's CU scaling),
    made near-deterministic: total share crosses the fleet capacity as
    total power rises, so the power-sorted TFS opens with a long band of
    rows that pass eq. 7 but fail placement (fragmentation: t_cfg=0
    fleets waste capacity on II repayments and leftovers).  The winner
    lands 1e5+ rows deep — the streaming-walk stress regime.
    """
    rng = np.random.default_rng(seed)
    tasks = []
    for i in range(n_t):
        pws = np.sort(rng.uniform(3.0, 9.0, nv))
        shr = np.maximum(base - slope * pws + rng.uniform(0, noise, nv), 0.5)
        period, data, t_slr = 50.0, 1.0, 100.0
        ths = data * t_slr / (period * shr)
        tasks.append(
            Task(
                name=f"B{i}",
                period=period,
                data=data,
                init_interval=float(rng.uniform(*ii)),
                variants=tuple(
                    TaskVariant(cu=j + 1, throughput=float(t), power=float(p))
                    for j, (t, p) in enumerate(zip(ths, pws, strict=True))
                ),
            )
        )
    return tasks


def _deep_instance(quick: bool) -> tuple[list[Task], FleetSpec]:
    n_t = 9 if quick else 10
    tasks = _band_tasks(n_t, 4, base=86.0 if not quick else 78.0)
    fleet = FleetSpec(n_f=6 if not quick else 5, t_slr=100.0, t_cfg=0.0)
    return tasks, fleet


def bench_enumeration_sweep(quick: bool = False) -> tuple[list[Row], dict]:
    """TFS enumeration throughput: Python heap vs block-native arrays.

    Streams the first N power-ordered TFS rows of the deep-band instance
    through ``iter_feasible_pruned`` (one TaskSetCombo per row, PR-2) and
    ``iter_feasible_pruned_blocks`` (whole (B, n_t) array blocks), and
    reports rows/s for both plus the speedup — the Python-object churn
    the block-native walk removed from the scheduler's hot path.
    """
    tasks, fleet = _deep_instance(quick)
    target = 50_000 if quick else 200_000

    def heap_rows() -> int:
        n = 0
        for _ in iter_feasible_pruned(tasks, fleet):
            n += 1
            if n >= target:
                break
        return n

    def block_rows() -> int:
        n = 0
        for blk in iter_feasible_pruned_blocks(tasks, fleet, 65536):
            n += len(blk)
            if n >= target:
                break
        return n

    # Both engines warmed once by the row-count calls, then median-of-3
    # each — symmetric methodology so the speedup compares like with like.
    n_heap = heap_rows()
    n_block = block_rows()
    us_heap = timeit(heap_rows, repeat=3, warmup=0)
    us_block = timeit(block_rows, repeat=3, warmup=0)
    heap_rps = n_heap / us_heap * 1e6
    block_rps = n_block / us_block * 1e6
    rows = [
        Row(
            f"enum_python_heap_rows{target}",
            us_heap,
            f"rows_per_s={heap_rps:.0f}",
        ),
        Row(
            f"enum_block_native_rows{target}",
            us_block,
            f"rows_per_s={block_rps:.0f};speedup={us_heap / us_block:.1f}x",
        ),
    ]
    sweep = {
        "target_rows": target,
        "heap_us": us_heap,
        "block_us": us_block,
        "heap_rows_per_s": heap_rps,
        "block_rows_per_s": block_rps,
        "speedup": us_heap / us_block,
    }
    return rows, sweep


def bench_streaming_deep(quick: bool = False) -> tuple[list[Row], dict]:
    """End-to-end deep-rank streaming schedule: PR-2 path vs block-native.

    Both walks use the same numpy placement backend and produce the
    identical winner/rank (asserted); the PR-2 baseline pays the Python
    heap + per-row combo materialisation, the block-native path streams
    ComboBlock arrays on the adaptive ramp with pipelined dispatch.  The
    JSON gets the per-phase WalkStats breakdown and the ramp sizes.
    """
    tasks, fleet = _deep_instance(quick)
    sched = PADPSFRScheduler(fleet, exhaustive=False)

    stats = WalkStats()
    res = sched.schedule(tasks, walk_stats=stats)

    def block_native():
        return sched.schedule(tasks)

    def pr2_path():
        return select_lowest_power_batched(
            iter_feasible_pruned(tasks, fleet), tasks, fleet, block_size=4096
        )

    # The parity assertions above warm both walks once; both are then
    # median-of-3 so the published speedup is symmetrically measured.
    combo_old, _, rank_old, _ = pr2_path()
    assert res.feasible and rank_old == res.chosen_rank and combo_old == res.combo
    us_new = timeit(block_native, repeat=3, warmup=0)
    us_old = timeit(pr2_path, repeat=3, warmup=0)
    tag = f"{len(tasks)}t{tasks[0].nv}v_rank{res.chosen_rank}"
    rows = [
        Row(
            f"padpsfr_stream_pr2path_{tag}",
            us_old,
            f"rank={rank_old};python-heap + per-row combos",
        ),
        Row(
            f"padpsfr_stream_blocknative_{tag}",
            us_new,
            f"rank={res.chosen_rank};speedup={us_old / us_new:.1f}x",
        ),
    ]
    streaming = {
        "instance": tag,
        "chosen_rank": res.chosen_rank,
        "rows_walked": stats.rows,
        "pr2_us": us_old,
        "blocknative_us": us_new,
        "speedup": us_old / us_new,
        "phase_breakdown": stats.as_dict(),
    }
    return rows, streaming


def bench_replan(quick: bool = False) -> tuple[list[Row], dict]:
    """Service steady state: warm delta replan vs cold ``schedule()``.

    The deep-rank streaming instance is solved once with exhaustive
    recording (``record_state=True, record_exhaustive=True`` — the
    service layer's first solve, every TFS row gets a placement
    verdict), then a light task arrives.  The warm replan reuses the
    recorded verdicts (reject monotonicity skips dispatch for every
    recorded reject) and must produce a plan bit-identical to a cold
    ``schedule()`` of the extended set — asserted here, not just
    claimed.  Acceptance: warm ≥ 10x under cold on the full instance.
    """
    tasks, fleet = _deep_instance(quick)
    sched = PADPSFRScheduler(fleet, exhaustive=False)

    def record():
        return sched.schedule(tasks, record_state=True, record_exhaustive=True)

    rec = record()
    state = rec.plan_state
    arrival = Task(
        name="arrival",
        period=10.0,
        data=25.0,
        init_interval=0.5,
        variants=(
            TaskVariant(cu=1, throughput=5.0, power=1.0),
            TaskVariant(cu=2, throughput=10.0, power=2.5),
        ),
    )
    extended = list(tasks) + [arrival]

    warm_res = sched.replan(state, extended)
    cold_res = sched.schedule(extended)
    identical = (
        warm_res.feasible == cold_res.feasible
        and warm_res.chosen_rank == cold_res.chosen_rank
        and warm_res.n_placement_rejects == cold_res.n_placement_rejects
        and warm_res.total_power == cold_res.total_power
        and (
            not cold_res.feasible
            or (
                warm_res.combo.variant_idx == cold_res.combo.variant_idx
                and str(warm_res.plan) == str(cold_res.plan)
            )
        )
    )
    assert identical, "warm replan diverged from cold schedule"

    us_record = timeit(record, repeat=1, warmup=0)
    us_warm = timeit(lambda: sched.replan(state, extended), repeat=3, warmup=0)
    us_cold = timeit(lambda: sched.schedule(extended), repeat=3, warmup=0)
    tag = f"{len(extended)}t_arrival_rank{cold_res.chosen_rank}"
    speedup = us_cold / us_warm
    rows = [
        Row(
            f"replan_cold_{tag}",
            us_cold,
            f"rank={cold_res.chosen_rank};from-scratch schedule()",
        ),
        Row(
            f"replan_warm_{tag}",
            us_warm,
            f"rank={warm_res.chosen_rank};speedup={speedup:.1f}x"
            f";bit_identical={identical}",
        ),
    ]
    replan_summary = {
        "instance": tag,
        "chosen_rank": cold_res.chosen_rank,
        "record_us": us_record,
        "cold_us": us_cold,
        "warm_us": us_warm,
        "speedup": speedup,
        "bit_identical": identical,
        "recorded_rows": state.n_recorded,
    }
    return rows, replan_summary


def bench_fleet_parallel(
    quick: bool = False, backends: list[str] | None = None
) -> tuple[list[Row], dict]:
    """Fleet-parallel batch scheduling: ``schedule_many`` vs a schedule() loop.

    B independent deep-band instances (each winner ~1e3-1e4 rows into its
    power-ordered TFS) are solved two ways per backend: a Python loop of
    solo ``schedule()`` calls, and one ``schedule_many(instances)`` batched
    lockstep walk.  Per-instance results are asserted bit-identical
    (feasibility, winning rank, total power) before anything is timed, and
    both legs get one full untimed pass first so jit compilation for every
    block shape lands outside the measurement.

    Acceptance target: the vmapped jax backend >= 5x instances/s over the
    solo loop at B=64 identically-shaped instances.

    A third ``shard="auto"`` leg (jax backend, largest B) times the
    ``shard_map`` device layout when the host has >1 jax device; on a
    single-device host it degrades to the plain vmap, so the leg is
    recorded as skipped with a note instead of timing a duplicate.
    """
    from repro.core.scheduler import ScheduleInstance

    fleet = FleetSpec(n_f=4, t_slr=100.0, t_cfg=0.0)
    notes: dict[str, str] = {"scalar": "no batched dispatch surface; excluded"}
    if backends is None:
        backends = [b for b in available_backends() if b != "scalar"]
    else:
        backends = [b for b in backends if b != "scalar"]
    if "pallas" in backends:
        from repro.kernels.ops import on_tpu

        if not on_tpu():
            backends = [b for b in backends if b != "pallas"]
            notes["pallas"] = (
                "interpret mode off-TPU: parity-tested, not a throughput engine"
            )
    sizes = [64] if quick else [8, 64]
    points = [
        (name, B)
        for name in backends
        for B in sizes
        # numpy's solo loop at B=64 costs ~15 s in the smoke job; its
        # batched win is still visible at B=8 there.
        if not (quick and name == "numpy" and B > 8)
    ]
    if quick and "numpy" in backends:
        points = [("numpy", 8)] + points

    rows: list[Row] = []
    summary: dict = {
        "n_t": 7,
        "nv": 4,
        "fleet_n_f": fleet.n_f,
        "block_size": 16,
        "points": {},
        "notes": notes,
    }
    for name, B in points:
        insts = [
            ScheduleInstance(
                tasks=_band_tasks(
                    7, 4, seed=100 + s, base=84.0, slope=5.0, ii=(8.0, 16.0)
                )
            )
            for s in range(B)
        ]
        sched = PADPSFRScheduler(fleet, engine=name, block_size=16)

        def loop():
            return [sched.schedule(list(i.tasks)) for i in insts]

        def many():
            return sched.schedule_many(insts)

        # Full warmup pass of BOTH legs: compiles every block shape the
        # walks reach (including partial tails), and doubles as the
        # bit-identity reference.
        ref = loop()
        got = many()
        _assert_instancewise_identical(ref, got, f"{name} B={B}")
        us_loop = timeit(loop, repeat=1 if quick else 2, warmup=0)
        us_many = timeit(many, repeat=3, warmup=0)
        speedup = us_loop / us_many
        n_feas = sum(r.feasible for r in ref)
        rows.append(
            Row(
                f"fleet_parallel_{name}_B{B}_loop",
                us_loop,
                f"inst_per_s={B / us_loop * 1e6:.1f};solo schedule() x{B}",
            )
        )
        rows.append(
            Row(
                f"fleet_parallel_{name}_B{B}_many",
                us_many,
                f"inst_per_s={B / us_many * 1e6:.1f};speedup={speedup:.2f}x"
                f";feasible={n_feas};bit_identical=True",
            )
        )
        summary["points"][f"{name}_B{B}"] = {
            "backend": name,
            "B": B,
            "loop_us": us_loop,
            "many_us": us_many,
            "speedup": speedup,
            "inst_per_s_loop": B / us_loop * 1e6,
            "inst_per_s_many": B / us_many * 1e6,
            "n_feasible": n_feas,
            "bit_identical": True,
        }
        if name == "jax" and B == max(sizes):
            from repro.core.placement_backends.jax_backend import resolve_shard

            n_shards = resolve_shard("auto", B)
            if n_shards <= 1:
                summary["shard"] = {
                    "n_shards": 1,
                    "skipped": True,
                    "note": "single jax device: shard='auto' degrades to "
                    "the plain vmap, so the leg would duplicate _many",
                }
            else:

                def many_shard():
                    return sched.schedule_many(insts, shard="auto")

                got_shard = many_shard()
                _assert_instancewise_identical(
                    ref, got_shard, f"{name} B={B} shard=auto"
                )
                us_shard = timeit(many_shard, repeat=3, warmup=0)
                rows.append(
                    Row(
                        f"fleet_parallel_{name}_B{B}_shard{n_shards}",
                        us_shard,
                        f"inst_per_s={B / us_shard * 1e6:.1f}"
                        f";speedup={us_loop / us_shard:.2f}x"
                        f";devices={n_shards};bit_identical=True",
                    )
                )
                summary["shard"] = {
                    "n_shards": n_shards,
                    "skipped": False,
                    "us": us_shard,
                    "speedup": us_loop / us_shard,
                    "bit_identical": True,
                }
    return rows, summary


def bench_resilience(quick: bool = False) -> tuple[list[Row], dict]:
    """The power premium of k-fault tolerance, verified by fault injection.

    One crafted homogeneous instance is scheduled at ``resilience=0,1,2``;
    each level's winning power and its premium over the unconstrained
    baseline land as ``resilience_k*`` rows.  The guarantee is then
    checked empirically, not just claimed: ``run_fault_injection``
    replays seeded ``DeviceFailure`` traces through a live
    ``SchedulerService`` and asserts that the k=1 / k=2 plans record
    zero replan-window deadline misses under any k failures while the
    k=0 plan misses on the very same trace.
    """
    from repro.service.faultsim import run_fault_injection

    fleet = FleetSpec(n_f=4, t_slr=30.0, t_cfg=1.0)
    # Two variants per task: cheap-but-wide (share 25, 2 W) and
    # fast-but-hot (share 10, 8 W).  Four share-25 tasks fill four
    # devices exactly, so every survivor level forces hot upgrades —
    # the premium ladder is structural, not noise.
    tasks = [
        Task(
            name=f"R{i}",
            period=10.0,
            data=20.0,
            init_interval=1.0,
            variants=(
                TaskVariant(cu=1, throughput=2.4, power=2.0),
                TaskVariant(cu=2, throughput=6.0, power=8.0),
            ),
        )
        for i in range(4)
    ]
    sched = PADPSFRScheduler(fleet)
    tag = f"{len(tasks)}t{fleet.n_f}f"
    rows: list[Row] = []
    points: dict[str, dict] = {}
    base: float | None = None
    for k in (0, 1, 2):
        res = sched.schedule(tasks, resilience=k)
        us = timeit(lambda: sched.schedule(tasks, resilience=k), repeat=3)
        power = float(res.total_power) if res.feasible else None
        if k == 0:
            base = power
        premium = (
            (power - base) / base * 100.0
            if power is not None and base
            else None
        )
        premium_s = f"{premium:.0f}" if premium is not None else "n/a"
        rows.append(
            Row(
                f"resilience_k{k}_{tag}",
                us,
                f"feasible={res.feasible};power={power};"
                f"premium_pct={premium_s};rank={res.chosen_rank}",
            )
        )
        points[f"k{k}"] = {
            "feasible": bool(res.feasible),
            "power": power,
            "premium_pct": premium,
            "chosen_rank": int(res.chosen_rank),
            "us": us,
        }
    # Empirical verification: the analytic guarantee must hold on every
    # seeded trace, and must be non-vacuous (k=0 demonstrably misses).
    n_seeds = 3 if quick else 8
    k1_ok = all(
        run_fault_injection(
            fleet, tasks, resilience=1, n_failures=1, seed=s
        ).survived
        for s in range(n_seeds)
    )
    k2_ok = all(
        run_fault_injection(
            fleet, tasks, resilience=2, n_failures=2, seed=s
        ).survived
        for s in range(n_seeds)
    )
    k0 = run_fault_injection(fleet, tasks, resilience=0, n_failures=1, seed=0)
    assert k1_ok and k2_ok, "resilient plan missed a deadline under injection"
    assert not k0.survived, "k=0 baseline survived; premium would be vacuous"
    summary = {
        "instance": tag,
        "n_f": fleet.n_f,
        "n_t": len(tasks),
        "points": points,
        "faultsim": {
            "seeds": n_seeds,
            "k1_survives_all_seeds": k1_ok,
            "k2_survives_all_seeds": k2_ok,
            "k0_misses_on_failure": not k0.survived,
            "k0_misses": k0.total_misses,
        },
    }
    return rows, summary


def _churn_identical(a, b) -> bool:
    """Bit-identity between one warm and one cold schedule result."""
    return (
        a.feasible == b.feasible
        and a.chosen_rank == b.chosen_rank
        and a.n_placement_rejects == b.n_placement_rejects
        and a.total_power == b.total_power
        and (
            not b.feasible
            or (
                a.combo.variant_idx == b.combo.variant_idx
                and str(a.plan) == str(b.plan)
            )
        )
    )


def _churn_task(rng, name: str) -> Task:
    """A small random arrival for the churn trace.

    Shares fall near-affinely with power (the ``_band_tasks`` recipe,
    scaled to the trace fleet's t_slr=35): cheap variants are tempting
    but tight, so once several tasks are alive the cold walk rejects a
    band of low-power combos before its first placeable rank — exactly
    the regime where a warm re-rank of recorded rows pays off.
    """
    nv = int(rng.integers(2, 5))
    pws = np.sort(rng.uniform(3.0, 9.0, nv))
    shr = np.maximum(31.0 - 2.8 * pws + rng.uniform(0.0, 1.5, nv), 4.0)
    period = float(rng.uniform(20, 60))
    data = 1.0
    ths = data * 35.0 / (period * shr)
    return Task(
        name=name,
        period=period,
        data=data,
        init_interval=float(rng.uniform(2.0, 8.0)),
        variants=tuple(
            TaskVariant(cu=j + 1, throughput=float(t), power=float(p))
            for j, (t, p) in enumerate(zip(ths, pws, strict=True))
        ),
    )


def _short_churn_trace(svc, n_events: int = 20, seed: int = 11):
    """Drive ``svc`` through a short seeded trace, yielding each event's
    telemetry once the event has been applied.

    Arrivals and exits, with one device failure (the 10th event) and one
    recovery (the 16th): every kind of event ``SchedulerService`` takes.
    """
    rng = np.random.default_rng(seed)
    for i in range(n_events):
        n_alive = len(svc.tasks)
        roll = float(rng.random())
        if i == 9:
            yield svc.fail_device()
        elif i == 15:
            yield svc.recover_device()
        elif (roll < 0.6 and n_alive < 6) or n_alive < 2:
            yield svc.submit(_churn_task(rng, f"c{i}"))
        else:
            yield svc.remove(svc.tasks[int(rng.integers(0, n_alive))].name)


def _eps_task(t_slr: float, name: str = "eps") -> Task:
    """One-variant task with negligible share and power.

    Appended *last* and exhaustively recorded, it makes every recorded
    reject die among the real tasks (primary-sweep depth < n-1), so a
    warm exit that drops it transfers every reject verdict and re-finds
    the deep-rank winner without dispatching a single placement.
    """
    period, share = 50.0, 1e-6
    th = t_slr / (period * share)
    return Task(
        name=name,
        period=period,
        data=1.0,
        init_interval=1.0,
        variants=(TaskVariant(cu=1, throughput=th, power=1e-6),),
    )


def _churn_deep_instance(quick: bool) -> tuple[list[Task], FleetSpec]:
    """The churn legs' deep-rank instance.

    Quick mode reuses :func:`_deep_instance`; full mode widens the band
    (``base=83``) so the winner lands ~58k rows deep — still inside the
    warm exit's phase-1 parent cap, so both removal legs measure the
    steady-state warm path rather than the full-band fallback.
    """
    if quick:
        return _deep_instance(True)
    return (
        _band_tasks(10, 4, base=83.0),
        FleetSpec(n_f=6, t_slr=100.0, t_cfg=0.0),
    )


def bench_churn(quick: bool = False) -> tuple[list[Row], dict]:
    """Warm removals + a long churn trace vs all-cold solving.

    Two measurements land in the ``churn`` JSON section:

    * **deep removals** — the deep-rank instance is exhaustively
      recorded once, then (a) an appended epsilon task exits, leaving
      exactly the deep instance, and (b) the last device of a fleet
      extended by one tiny device fails, leaving the deep fleet; each
      warm ``replan()`` is asserted bit-identical to a cold
      ``schedule()`` of the post-event instance and timed against it
      (acceptance: >= 4x).  Both legs transfer every recorded reject
      (prefix-death depths for the exit, survivor-prefix monotonicity
      for the failure), so the warm path is pure projection;
    * **churn trace** — a 200-event seeded arrival/exit/failure/recovery
      mix replayed through a live ``SchedulerService`` (numpy engine,
      staleness-bounded re-record policy on), reporting the warm-hit
      rate over solved events (acceptance: >= 0.80), mean latency per
      event kind, and solved-events/s against an all-cold baseline that
      re-solves every post-event task set from scratch.
    """
    from repro.service import SchedulerService

    rows: list[Row] = []

    # --- deep-instance warm removals -------------------------------------
    tasks, fleet = _churn_deep_instance(quick)
    sched = PADPSFRScheduler(fleet, exhaustive=False)

    # Exit leg: record tasks + eps exhaustively, then eps exits and the
    # survivors are the deep instance itself — the warm projection must
    # re-find its deep-rank winner from transferred verdicts alone.
    eps = _eps_task(fleet.t_slr)
    state = sched.schedule(
        [*tasks, eps], record_state=True, record_exhaustive=True
    ).plan_state
    warm_exit = sched.replan(state, tasks)
    cold_exit = sched.schedule(tasks)
    assert _churn_identical(warm_exit, cold_exit), "warm exit diverged"
    us_exit_warm = timeit(
        lambda: sched.replan(state, tasks), repeat=3, warmup=0
    )
    us_exit_cold = timeit(lambda: sched.schedule(tasks), repeat=3, warmup=0)

    # Failure leg: record on the deep fleet extended by one tiny device
    # (heterogeneous form, so the drop is a survivor-prefix: recorded
    # rejects transfer), then the tiny device dies and the survivor
    # fleet is the deep fleet — warm re-rank vs the deep cold walk.
    dev = DeviceProfile(t_slr=fleet.t_slr, t_cfg=fleet.t_cfg)
    tiny = DeviceProfile(t_slr=0.5, t_cfg=fleet.t_cfg)
    big_fleet = FleetSpec.heterogeneous(
        [dev] * fleet.n_f + [tiny], name="churn-het"
    )
    small_fleet = FleetSpec.heterogeneous([dev] * fleet.n_f, name="churn-het")
    big_sched = PADPSFRScheduler(big_fleet, exhaustive=False)
    small_sched = PADPSFRScheduler(small_fleet, exhaustive=False)
    big_state = big_sched.schedule(
        tasks, record_state=True, record_exhaustive=True
    ).plan_state
    warm_fail = big_sched.replan(big_state, tasks, fleet=small_fleet)
    cold_fail = small_sched.schedule(tasks)
    assert _churn_identical(warm_fail, cold_fail), "warm failure diverged"
    us_fail_warm = timeit(
        lambda: big_sched.replan(big_state, tasks, fleet=small_fleet),
        repeat=3, warmup=0,
    )
    us_fail_cold = timeit(
        lambda: small_sched.schedule(tasks), repeat=3, warmup=0
    )

    tag = f"{len(tasks)}t{fleet.n_f}f"
    rows.append(
        Row(
            f"churn_exit_cold_{tag}",
            us_exit_cold,
            f"rank={cold_exit.chosen_rank};from-scratch schedule()",
        )
    )
    rows.append(
        Row(
            f"churn_exit_warm_{tag}",
            us_exit_warm,
            f"rank={warm_exit.chosen_rank}"
            f";speedup={us_exit_cold / us_exit_warm:.1f}x;bit_identical=True",
        )
    )
    rows.append(
        Row(
            f"churn_failure_cold_{tag}",
            us_fail_cold,
            f"rank={cold_fail.chosen_rank};from-scratch schedule()",
        )
    )
    rows.append(
        Row(
            f"churn_failure_warm_{tag}",
            us_fail_warm,
            f"rank={warm_fail.chosen_rank}"
            f";speedup={us_fail_cold / us_fail_warm:.1f}x;bit_identical=True",
        )
    )

    # --- 200-event churn trace -------------------------------------------
    n_events = 200
    rng = np.random.default_rng(11)
    svc = SchedulerService(
        FleetSpec(n_f=4, t_slr=35.0, t_cfg=1.0), engine="numpy", max_stale=5
    )
    solved: list[tuple] = []  # (kind, tasks, fleet) per solved event
    kinds: list[str] = []
    counter = 0
    for _ in range(n_events):
        roll = float(rng.random())
        n_alive = len(svc.tasks)
        # Exits only fire at >= 2 alive tasks: draining the service to
        # empty would force a cold arrival-from-nothing on the next
        # submit, which measures restart cost rather than churn.
        if (roll < 0.55 and n_alive < 8) or n_alive < 2:
            kind = "arrival"
            counter += 1
            tel = svc.submit(_churn_task(rng, f"c{counter}"))
        elif roll < 0.80 and n_alive:
            kind = "exit"
            victim = svc.tasks[int(rng.integers(0, n_alive))]
            tel = svc.remove(victim.name)
        elif roll < 0.90 and svc.fleet.n_f > 1:
            kind = "failure"
            tel = svc.fail_device()
        else:
            kind = "recovery"
            tel = svc.recover_device()
        kinds.append(kind)
        if tel.path not in ("admission", "noop") and svc.tasks:
            solved.append((kind, svc.tasks, svc.fleet, tel))
    warm_hits = [
        tel
        for _, _, _, tel in solved
        if tel.path in ("cache", "warm", "warm_exit", "warm_failure")
    ]
    hit_rate = len(warm_hits) / max(1, len(solved))
    per_kind_us: dict[str, float] = {}
    per_kind_n: dict[str, int] = {}
    for kind, _, _, tel in solved:
        per_kind_us[kind] = per_kind_us.get(kind, 0.0) + tel.latency_s * 1e6
        per_kind_n[kind] = per_kind_n.get(kind, 0) + 1
    per_kind_us = {
        k: v / per_kind_n[k] for k, v in sorted(per_kind_us.items())
    }
    warm_total_us = sum(tel.latency_s for _, _, _, tel in solved) * 1e6

    # All-cold baseline: one from-scratch schedule() per solved event's
    # post-event instance (what the pre-warm service had to pay).
    cold_scheds: dict = {}
    def cold_loop() -> None:
        for _, ts, fl, _ in solved:
            if fl not in cold_scheds:
                cold_scheds[fl] = PADPSFRScheduler(fl, engine="numpy")
            cold_scheds[fl].schedule(ts)

    cold_total_us = timeit(cold_loop, repeat=1, warmup=1)
    rows.append(
        Row(
            f"churn_trace_{n_events}ev",
            warm_total_us,
            f"solved={len(solved)};warm_hit_rate={hit_rate:.2f}"
            f";rerecords={svc.rerecord_count}"
            f";cold_us={cold_total_us:.0f}"
            f";speedup={cold_total_us / warm_total_us:.1f}x",
        )
    )

    churn = {
        "deep_instance": tag,
        "exit": {
            "chosen_rank": int(cold_exit.chosen_rank),
            "cold_us": us_exit_cold,
            "warm_us": us_exit_warm,
            "speedup": us_exit_cold / us_exit_warm,
            "bit_identical": True,
        },
        "failure": {
            "chosen_rank": int(cold_fail.chosen_rank),
            "cold_us": us_fail_cold,
            "warm_us": us_fail_warm,
            "speedup": us_fail_cold / us_fail_warm,
            "bit_identical": True,
        },
        "trace": {
            "n_events": n_events,
            "n_solved": len(solved),
            "event_mix": {k: kinds.count(k) for k in sorted(set(kinds))},
            "warm_hit_rate": hit_rate,
            "rerecords": svc.rerecord_count,
            "per_kind_mean_us": per_kind_us,
            "warm_total_us": warm_total_us,
            "cold_total_us": cold_total_us,
            "events_per_s_warm": len(solved) / warm_total_us * 1e6,
            "events_per_s_cold": len(solved) / cold_total_us * 1e6,
            "speedup": cold_total_us / warm_total_us,
        },
    }
    return rows, churn


def _assert_instancewise_identical(ref, got, what: str) -> None:
    """Per-instance bit-identity between two lists of schedule results."""
    assert len(ref) == len(got), f"{what}: result count mismatch"
    for i, (a, b) in enumerate(zip(ref, got, strict=True)):
        same = (
            a.feasible == b.feasible
            and a.chosen_rank == b.chosen_rank
            and a.n_placement_rejects == b.n_placement_rejects
            and (not a.feasible or a.total_power == b.total_power)
        )
        assert same, f"{what}: instance {i} diverged from the solo loop"


def bench_hetero_fleet(quick: bool = False) -> list[Row]:
    """End-to-end PADPS-FR on mixed FPGA/GPU/CPU fleets at growing sizes."""
    rows = []
    tasks = _synth_tasks(8 if quick else 10, 4, seed=2)
    scales = [1, 2] if quick else [1, 2, 4]
    for scale in scales:
        fleet = make_hetero_fleet(
            {"fpga": 4 * scale, "gpu": 2 * scale, "cpu": 2 * scale},
            t_slr=80.0,
            name=f"mix-x{scale}",
        )
        sched = PADPSFRScheduler(fleet)
        res = sched.schedule(tasks)
        us = timeit(lambda: sched.schedule(tasks), repeat=3)
        rows.append(
            Row(
                f"padpsfr_hetero_{fleet.n_f}dev",
                us,
                f"feasible={res.feasible};power={res.total_power:.1f}"
                f";rank={res.chosen_rank}",
            )
        )
    return rows


def bench_scheduler_scale(quick: bool = False) -> list[Row]:
    rows = []
    fleet = FleetSpec(n_f=8, t_slr=80.0, t_cfg=4.0)

    sizes = [(6, 4), (8, 4)] if quick else [(6, 4), (8, 4), (10, 4)]
    for n_t, nv in sizes:  # |TSS| = 4k, 65k, 1M
        tasks = _synth_tasks(n_t, nv)
        us_vec = timeit(lambda: search_feasible(tasks, fleet), repeat=3)
        if nv**n_t <= 70_000 and not quick:
            us_loop = timeit(lambda: _loop_enumeration(tasks, fleet), repeat=1)
            speedup = f"{us_loop / us_vec:.0f}x"
        else:
            us_loop, speedup = float("nan"), "loop-skipped"
        rows.append(
            Row(
                f"alg1_vectorized_tss{nv**n_t}", us_vec,
                f"paper_loop_us={us_loop:.0f};speedup={speedup}",
            )
        )

    rows.extend(bench_alg2_batched_vs_scalar(quick))
    rows.extend(bench_hetero_fleet(quick))

    # streaming engine on an instance with |TSS| = 8^12 ≈ 6.9e10 (cannot
    # materialise): time-to-first-feasible in power order
    big = _synth_tasks(8 if quick else 12, 4 if quick else 8, seed=1)
    big_fleet = FleetSpec(n_f=16, t_slr=120.0, t_cfg=3.0)

    def first_feasible():
        return next(iter(iter_feasible_pruned(big, big_fleet)))

    us = timeit(first_feasible, repeat=3)
    rows.append(
        Row("alg1_branch_and_bound_streaming", us,
            "streams lowest-power TFS without materialising TSS")
    )

    # end-to-end schedule at scale (streaming engine, batched blocks)
    sched = PADPSFRScheduler(big_fleet, exhaustive=False)
    us = timeit(lambda: sched.schedule(big), repeat=3)
    res = sched.schedule(big)
    rows.append(
        Row(f"padpsfr_schedule_{len(big)}tasks_{big[0].nv}variants", us,
            f"feasible={res.feasible};power={res.total_power:.1f}")
    )
    return rows


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small |TSS| sizes for the CI smoke job")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as a JSON benchmark artifact")
    ap.add_argument("--backends", metavar="CSV", default=None,
                    help="comma-separated placement backends for the sweep "
                         "(default: every available backend except scalar)")
    ap.add_argument("--sweep-only", action="store_true",
                    help="run only the placement-backend sweep")
    args = ap.parse_args(argv)
    backends = (
        [b.strip() for b in args.backends.split(",") if b.strip()]
        if args.backends
        else None
    )
    enum_sweep: dict = {}
    streaming: dict = {}
    replan_summary: dict = {}
    fleet_parallel: dict = {}
    resilience_summary: dict = {}
    churn_summary: dict = {}
    if args.sweep_only:
        rows = []
    else:
        rows = bench_scheduler_scale(quick=args.quick)
        enum_rows, enum_sweep = bench_enumeration_sweep(quick=args.quick)
        rows.extend(enum_rows)
        stream_rows, streaming = bench_streaming_deep(quick=args.quick)
        rows.extend(stream_rows)
        replan_rows, replan_summary = bench_replan(quick=args.quick)
        rows.extend(replan_rows)
        fleet_rows, fleet_parallel = bench_fleet_parallel(
            quick=args.quick, backends=backends
        )
        rows.extend(fleet_rows)
        res_rows, resilience_summary = bench_resilience(quick=args.quick)
        rows.extend(res_rows)
        churn_rows, churn_summary = bench_churn(quick=args.quick)
        rows.extend(churn_rows)
    sweep_rows, sweep = bench_backend_sweep(quick=args.quick, backends=backends)
    rows.extend(sweep_rows)
    for row in rows:
        print(row.csv())
    if args.json:
        payload = [
            {"name": r.name, "us": r.us, "derived": r.derived} for r in rows
        ]
        with open(args.json, "w") as fh:
            json.dump(
                {
                    "benchmark": "scheduler_scale",
                    "rows": payload,
                    "backend_sweep": sweep,
                    "enumeration_sweep": enum_sweep,
                    "streaming": streaming,
                    "replan": replan_summary,
                    "fleet_parallel": fleet_parallel,
                    "resilience": resilience_summary,
                    "churn": churn_summary,
                },
                fh,
                indent=2,
            )
        print(f"wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
