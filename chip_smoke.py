#!/usr/bin/env python3
"""Chip smoke: the scheduler's main path on a TPU, held to the oracle.

    python chip_smoke.py               # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips  # four chips: phase (d) sharded

Every phase drives the public entry points (``PADPSFRScheduler.schedule``
/ ``schedule_many`` and ``SchedulerService``) with a device engine and
compares each plan with the numpy float64 engine and, where the row count
is small enough, the scalar Alg-2 oracle: feasibility, winning
combination, rank, rejects, total power and the placement itself must be
identical.

* (a) paper Example 1 (|TSS|=1024 |TFS|=620 rejects=146 rank=4 power=31.5);
* (b) the deep-rank instance: 10 tasks x 4 variants on 6 devices, winner
  425,399 rows deep, block ramp up to 65,536-row sweeps;
* (c) the same solve at ``resilience=1`` (second, survivor-fleet sweep);
* (d) ``schedule_many`` over 64 band instances (one batched sweep a round);
* (e) a ``SchedulerService`` replaying a seeded 20-event trace (arrivals,
  exits, one device failure, one recovery), each event's live plan
  against a cold schedule of the instance after it.

Phases (b)-(d) run on the ``jax`` and ``pallas`` engines; ``auto`` runs
(a)-(e).  ``--four-chips`` runs only phase (d) on the ``jax`` engine with
``shard="auto"``, checks that the instance axis is laid over four devices,
and compares with ``shard=None`` and numpy.

The script needs a TPU: on any other platform it exits non-zero before
any phase.  Compile time (JAX's backend-compile events) is reported apart
from each phase's wall time.  The last line of standard output is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

DEVICE_ENGINES = ("jax", "pallas")
N_BATCH = 64
SCALAR_BATCH = 16  # instances of phase (d) also solved by the scalar oracle
N_EVENTS = 20

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _timed(fn):
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, _COMPILE_S[0] - c0


# -- phases: each returns (results, WalkStats | None) for one engine ---------


def _example1(engine):
    from repro.configs.paper_examples import example1_fleet, example1_tasks
    from repro.core import PADPSFRScheduler, WalkStats

    ws = WalkStats()
    sched = PADPSFRScheduler(example1_fleet(), engine=engine)
    # count_all_rejects: the paper's reject count covers the whole TFS.
    res = sched.schedule(example1_tasks(), walk_stats=ws, count_all_rejects=True)
    return [res], ws


def _deep(engine, resilience=0):
    from benchmarks.scheduler_scale import _deep_instance
    from repro.core import PADPSFRScheduler, WalkStats

    tasks, fleet = _deep_instance(False)
    ws = WalkStats()
    sched = PADPSFRScheduler(fleet, engine=engine)
    return [sched.schedule(tasks, walk_stats=ws, resilience=resilience)], ws


@functools.cache
def _band_instances():
    from benchmarks.scheduler_scale import _band_tasks
    from repro.core.scheduler import ScheduleInstance

    return tuple(
        ScheduleInstance(
            tasks=_band_tasks(7, 4, seed=100 + s, base=84.0, slope=5.0, ii=(8.0, 16.0))
        )
        for s in range(N_BATCH)
    )


def _many(engine, shard=None, n=N_BATCH):
    from repro.core import FleetSpec, PADPSFRScheduler, WalkStats

    ws = WalkStats()
    sched = PADPSFRScheduler(
        FleetSpec(n_f=4, t_slr=100.0, t_cfg=0.0), engine=engine, block_size=16
    )
    return sched.schedule_many(_band_instances()[:n], shard=shard, walk_stats=ws), ws


def _service_trace(engine):
    """Replay the seeded trace; returns one snapshot per solved event."""
    from benchmarks.scheduler_scale import _short_churn_trace
    from repro.core import FleetSpec
    from repro.service import SchedulerService

    svc = SchedulerService(FleetSpec(n_f=4, t_slr=35.0, t_cfg=1.0), engine=engine)
    return [
        (tel, svc.tasks, svc.fleet, svc.plan)
        for tel in _short_churn_trace(svc, N_EVENTS)
        if svc.tasks
    ]


# -- references --------------------------------------------------------------


def _check(phase, engine, got, refs, ws, wall, comp):
    """Print one phase line; exit unless ``got`` matches every reference.

    A reference may cover only the first instances of ``got``.
    """
    from benchmarks.scheduler_scale import _churn_identical as _identical

    agree = {name: len(ref) <= len(got) and all(map(_identical, got, ref))
             for name, ref in refs.items()}
    print(
        f"phase={phase} engine={engine} "
        + " ".join(f"agree_{k}[{len(refs[k])}]={v}" for k, v in agree.items())
        + f" instances={len(got)} feasible={sum(r.feasible for r in got)}"
        + (f" rows={ws.rows} blocks={len(ws.block_sizes)}"
           f" max_block={max(ws.block_sizes, default=0)}" if ws is not None else "")
        + f" wall_s={wall} compile_s={comp}",
        flush=True,
    )
    for name, ref in refs.items():
        for i, (a, b) in enumerate(zip(got, ref, strict=False)):
            if not _identical(a, b):
                raise SystemExit(
                    f"phase {phase} engine {engine}: instance {i} differs from "
                    f"{name}: got rank={a.chosen_rank} power={a.total_power}, "
                    f"want rank={b.chosen_rank} power={b.total_power}"
                )
    if not all(agree.values()):
        raise SystemExit(f"phase {phase} engine {engine}: result count differs")


def _paper_line(res) -> str:
    return (f"|TSS|={res.n_tss} |TFS|={res.n_tfs} rejects={res.n_placement_rejects} "
            f"rank={res.chosen_rank} power={res.total_power:g}")


def _lowered_pallas_text() -> str:
    """StableHLO of the pallas engine's sweep at one 1024-row block."""
    import jax
    import numpy as np

    from repro.kernels.ops import placement_sweep

    fn = jax.jit(functools.partial(placement_sweep, block_rows=1024))
    z = np.zeros
    return fn.lower(
        z((1024, 10), np.float32), z(10, np.float32), z(6, np.float32), z(6, np.float32)
    ).as_text()


def one_chip(auto: str) -> None:
    from repro.core import PADPSFRScheduler

    print(f"auto_engine={auto}", flush=True)
    has_kernel = "tpu_custom_call" in _lowered_pallas_text()
    print(f"pallas_lowered_tpu_custom_call={has_kernel}", flush=True)
    if not has_kernel:
        raise SystemExit("the Pallas sweep did not lower to a TPU kernel")

    deep_k1 = functools.partial(_deep, resilience=1)
    # phase: (solve, engines, scalar-oracle solve or None where too long)
    phases = {
        "a": (_example1, ("auto",), _example1),
        # (b)'s 425k-row scalar walk is left to the numpy engine's tests.
        "b": (_deep, (*DEVICE_ENGINES, "auto"), None),
        "c": (deep_k1, (*DEVICE_ENGINES, "auto"), deep_k1),
        "d": (_many, (*DEVICE_ENGINES, "auto"),
              functools.partial(_many, n=SCALAR_BATCH)),
    }
    for phase, (solve, engines, scalar_solve) in phases.items():
        refs = {"numpy": solve("numpy")[0]}
        if scalar_solve is not None:
            refs["scalar"] = scalar_solve("scalar")[0]
        for engine in engines:
            (got, ws), wall, comp = _timed(lambda: solve(engine))  # noqa: B023
            _check(phase, engine, got, refs, ws, wall, comp)
            if phase == "a":
                line = _paper_line(got[0])
                print(f"  example1 {line}", flush=True)
                if line != "|TSS|=1024 |TFS|=620 rejects=146 rank=4 power=31.5":
                    raise SystemExit(f"Example 1 reads {line}")

    snaps, wall, comp = _timed(lambda: _service_trace("auto"))
    got = [plan for _, _, _, plan in snaps]
    refs = {
        name: [PADPSFRScheduler(fleet, engine=name).schedule(tasks)
               for _, tasks, fleet, _ in snaps]
        for name in ("numpy", "scalar")
    }
    paths = {}
    for tel, *_ in snaps:
        paths[tel.path] = paths.get(tel.path, 0) + 1
    print(f"  service paths={paths} events={N_EVENTS}", flush=True)
    _check("e", "auto", got, refs, None, wall, comp)


def four_chips() -> None:
    import numpy as np

    from repro.core.placement_backends.jax_backend import (
        _jitted_batch_sweep,
        resolve_shard,
    )
    from repro.core.placement_backends.jax_runtime import x64

    n_shards = resolve_shard("auto", N_BATCH)
    print(f"resolve_shard(auto, {N_BATCH})={n_shards}", flush=True)
    if n_shards != 4:
        raise SystemExit(f"shard='auto' resolved to {n_shards} devices, not 4")

    rng = np.random.default_rng(0)
    B, R, n_t, n_f = N_BATCH, 1024, 7, 4
    args = (
        rng.uniform(5.0, 60.0, (B, R, n_t)),
        rng.uniform(8.0, 16.0, (B, n_t)),
        np.full((B, n_f), 100.0),
        np.zeros((B, n_f)),
        np.full(B, n_t),
        np.full(B, n_f),
        np.float64(0.0),
    )
    with x64():
        sharded = _jitted_batch_sweep(4)(*args, repay_init=True)
        plain = _jitted_batch_sweep(1)(*args, repay_init=True)
    devices = {d for out in sharded for d in out.sharding.device_set}
    print(f"sharded_output_devices={len(devices)}", flush=True)
    if len(devices) != 4:
        raise SystemExit(f"sweep outputs live on {len(devices)} devices, not 4")
    for a, b in zip(sharded, plain, strict=True):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise SystemExit("sharded sweep differs from the unsharded sweep")

    refs = {"numpy": _many("numpy")[0]}
    (plain_res, ws), wall, comp = _timed(lambda: _many("jax"))
    _check("d", "jax shard=None", plain_res, refs, ws, wall, comp)
    refs["shard=None"] = plain_res
    (got, ws), wall, comp = _timed(lambda: _many("jax", shard="auto"))
    _check("d", "jax shard=auto", got, refs, ws, wall, comp)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase (d) on the jax engine, sharded over 4 chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    from jax import monitoring

    from repro.core import resolve_engine
    from repro.core.placement_backends.jax_runtime import configure_compile_cache

    cache = configure_compile_cache()
    monitoring.register_event_duration_secs_listener(_on_duration)
    count = len(jax.devices())
    print(f"device platform={dev.platform} kind={dev.device_kind} count={count} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    if args.four_chips:
        if count != 4:
            raise SystemExit(f"--four-chips needs 4 devices, found {count}")
        four_chips()
    else:
        auto = resolve_engine("auto")
        if auto not in DEVICE_ENGINES:
            raise SystemExit(f"auto resolved to {auto} on a TPU")
        one_chip(auto)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
