"""The walk's spans and counters (``repro.trace``, ``WalkStats``), on the CPU.

The new ``WalkStats`` fields are parts of the old ones, never carved out
of them; the counters count what the engines hand over; the spans reach
the profiler's trace with their arguments; and none of it needs jax.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.paper_examples import example1_fleet, example1_tasks
from repro.core import PADPSFRScheduler, WalkStats

ROOT = Path(__file__).resolve().parents[1]
ENGINES = ("numpy", "jax", "pallas")


def _walk(engine: str, many: bool, **kw) -> WalkStats:
    ws = WalkStats()
    sched = PADPSFRScheduler(example1_fleet(), engine=engine, **kw)
    if many:
        sched.schedule_many([example1_tasks()] * 3, walk_stats=ws)
    else:
        sched.schedule(example1_tasks(), walk_stats=ws)
    return ws


@pytest.mark.parametrize("many", [False, True], ids=["schedule", "schedule_many"])
@pytest.mark.parametrize("engine", ENGINES)
def test_new_fields_are_parts_of_the_old(engine, many):
    ws = _walk(engine, many)
    assert ws.search_us > 0
    assert 0 < ws.sort_us + ws.gather_us <= ws.enumerate_us
    assert ws.prepare_us + ws.launch_us + ws.unbatch_us <= ws.place_us
    d = ws.as_dict()
    for key in ("search_us", "sort_us", "gather_us", "prepare_us", "launch_us", "unbatch_us",
                "h2d_bytes", "d2h_bytes", "launches", "abandoned_rows"):
        assert d[key] == getattr(ws, key)
    # The old fields keep their meaning: the walk's four phases.
    assert ws.total_us == ws.enumerate_us + ws.place_us + ws.sync_us + ws.materialize_us
    if engine == "numpy":
        assert ws.prepare_us == ws.launch_us == ws.unbatch_us == 0
        assert ws.h2d_bytes == ws.d2h_bytes == ws.launches == 0
    else:
        assert ws.prepare_us > 0 and ws.launch_us > 0
        # Only the solo Pallas entry has an instance axis to drop.
        assert (ws.unbatch_us > 0) == (engine == "pallas" and not many)
        assert ws.h2d_bytes > 0 and ws.d2h_bytes > 0
        # One sweep program a solo block; one a round of the batched walk.
        assert 0 < ws.launches <= len(ws.block_sizes)


def test_no_abandoned_rows_on_the_eager_engine():
    ws = _walk("numpy", False)
    # Depth 1: the walk stops at the winner's block (Example 1: rank 4).
    assert ws.block_sizes == [64]
    assert ws.abandoned_rows == 0


@pytest.mark.parametrize("many", [False, True], ids=["schedule", "schedule_many"])
def test_blocks_past_the_winner_are_abandoned_on_the_pipelined_engine(many):
    ws = _walk("jax", many)
    # The winner lies in the first block; the block enqueued behind it is
    # never read.
    assert ws.abandoned_rows > 0
    assert ws.abandoned_rows == ws.rows - ws.block_sizes[0] * (3 if many else 1)


@pytest.mark.parametrize("engine,rows_p,eff_bytes", [("jax", 1024, 0), ("pallas", 1024, 8)])
def test_h2d_bytes_of_one_pinned_block(engine, rows_p, eff_bytes):
    """One block of all 620 TFS rows: padded rows x n_t x 8 bytes (float64
    off-TPU) plus the task and device tables (and the Pallas kernel's two
    int32 effective counts)."""
    ws = WalkStats()
    sched = PADPSFRScheduler(example1_fleet(), engine=engine, block_size=1024)
    sched.schedule(example1_tasks(), walk_stats=ws)
    n_t, n_f = 6, 4
    assert ws.block_sizes == [620]
    assert ws.launches == 1
    assert ws.h2d_bytes == rows_p * n_t * 8 + (n_t + 2 * n_f) * 8 + eff_bytes


def test_resilience_enqueues_a_second_pallas_sweep():
    ws = WalkStats()
    sched = PADPSFRScheduler(example1_fleet(), engine="pallas", block_size=1024)
    sched.schedule(example1_tasks(), walk_stats=ws, resilience=1)
    assert ws.launches == 2 * len(ws.block_sizes)


def _traced_schedule(engine: str, tmp_path) -> str:
    """A profiler trace of one Example 1 solve; its ``xplane.pb`` path."""
    import jax

    sys.path.insert(0, str(ROOT))
    from bench import devtrace

    sched = PADPSFRScheduler(example1_fleet(), engine=engine)
    sched.schedule(example1_tasks())  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.schedule(example1_tasks())
    finally:
        jax.profiler.stop_trace()
    return devtrace.find(str(tmp_path))


def test_spans_reach_the_profiler_nested_with_one_call_number(tmp_path):
    from jax.profiler import ProfileData

    from bench import devtrace

    path = _traced_schedule("jax", tmp_path)
    names = {"sched.schedule", "sched.eq7_search", "sched.tfs_sort", "sched.gather",
             "sched.enumerate", "sched.dispatch", "sched.prepare", "sched.launch",
             "sched.sync", "sched.materialize"}
    host = devtrace.load(path, names)["host"]
    assert {n for n, _, _ in host} == names
    (outer,) = [(s, s + d) for n, s, d in host if n == "sched.schedule"]
    for n, s, d in host:
        assert outer[0] <= s and s + d <= outer[1], n

    stats = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("sched."):
                        stats.setdefault(e.name, []).append(dict(e.stats))
    calls = {s["call"] for evs in stats.values() for s in evs}
    assert len(calls) == 1
    dispatch = stats["sched.dispatch"]
    assert [s["block"] for s in dispatch] == [0, 1]
    assert [s["rows"] for s in dispatch] == [64, 512]
    assert [s["padded_rows"] for s in dispatch] == [64, 512]
    assert stats["sched.schedule"][0]["engine"] == "jax"



def test_pallas_dispatch_splits_into_prepare_launch_and_unbatch(tmp_path):
    """Inside each solo Pallas block's dispatch: the backend's cast and
    the kernel's pad (two ``sched.prepare``), then the device call
    (``sched.launch``), then the instance axis dropped (``sched.unbatch``),
    in that order."""
    from bench import devtrace

    path = _traced_schedule("pallas", tmp_path)
    names = {"sched.dispatch", "sched.prepare", "sched.launch", "sched.unbatch"}
    host = sorted(devtrace.load(path, names)["host"], key=lambda e: e[1])
    dispatch = [(s, s + d) for n, s, d in host if n == "sched.dispatch"]
    assert len(dispatch) == 2
    for lo, hi in dispatch:
        inside = [n for n, s, d in host if n != "sched.dispatch" and lo <= s and s + d <= hi]
        assert inside == ["sched.prepare", "sched.prepare", "sched.launch", "sched.unbatch"]


def _service():
    from repro.service import SchedulerService

    tasks = example1_tasks()
    svc = SchedulerService(example1_fleet(), engine="numpy")
    for t in tasks[:-1]:
        assert svc.submit(t).admitted
    return svc, tasks[-1]


def test_what_if_many_fills_the_walk_stats_it_is_given():
    svc, arrival = _service()
    ws = WalkStats()
    (res,) = svc.what_if_many([arrival], walk_stats=ws)
    assert res.feasible
    assert ws.rows > 0 and ws.search_us > 0 and ws.enumerate_us > 0


def test_what_if_many_passes_no_walk_stats_unless_given():
    svc, arrival = _service()
    seen = []
    inner = svc._sched.schedule_many

    def schedule_many(instances, **kw):
        seen.append(set(kw))
        return inner(instances, **kw)

    svc._sched.schedule_many = schedule_many
    svc.what_if_many([arrival])
    ws = WalkStats()
    svc.what_if_many([arrival], walk_stats=ws)
    assert "walk_stats" not in seen[0]
    assert "walk_stats" in seen[1]


def test_core_imports_and_schedules_without_jax():
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro.configs.paper_examples import example1_fleet, example1_tasks\n"
        "from repro.core import PADPSFRScheduler, WalkStats\n"
        "ws = WalkStats()\n"
        "r = PADPSFRScheduler(example1_fleet()).schedule(example1_tasks(), walk_stats=ws)\n"
        "assert r.chosen_rank == 4 and ws.search_us > 0 and sys.modules['jax'] is None\n"
        "print('ok')\n"
    )
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, cwd=ROOT, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def _lower_sweep(kind: str):
    """One jitted sweep program, lowered at a small shape."""
    from repro.core.placement_backends import jax_backend as jb
    from repro.kernels.placement_step import _placement_sweep_batch_padded

    B, R, n_t, n_f = 2, 8, 3, 2
    shares = np.ones((B, R, n_t))
    iis, slr, cfg = np.ones((B, n_t)), np.full((B, n_f), 30.0), np.ones((B, n_f))
    eff_t, eff_f = np.full(B, n_t), np.full(B, n_f)
    f32 = np.float32
    if kind == "pallas":
        return _placement_sweep_batch_padded.lower(
            np.ones((1, 128, n_t), f32), iis[:1].astype(f32), slr[:1].astype(f32),
            cfg[:1].astype(f32), np.array([[n_t, n_f]], np.int32), 0.0,
            repay_init=True, block_rows=128, interpret=True)
    if kind == "solo":
        return jb._jitted_sweep().lower(shares[0], iis[0], slr[0], cfg[0], 0.0,
                                        repay_init=True)
    if kind == "solo_resilient":
        return jb._jitted_resilient_sweep().lower(
            shares[0], iis[0], slr[0], cfg[0], slr[0, :1], cfg[0, :1], 0.0, repay_init=True)
    if kind == "batch":
        return jb._jitted_batch_sweep(1).lower(
            shares, iis, slr, cfg, eff_t, eff_f, 0.0, repay_init=True)
    if kind == "batch_resilient":
        return jb._jitted_batch_resilient_sweep(1).lower(
            shares, iis, slr, cfg, eff_t, eff_f, slr, cfg, eff_f - 1, 0.0, repay_init=True)
    # The shard_map'd program, built uncached so that no mesh outlives the test.
    return jb._jitted_batch_sweep.__wrapped__(2).lower(
        shares, iis, slr, cfg, eff_t, eff_f, 0.0, repay_init=True)


@pytest.mark.parametrize(
    "kind", ["pallas", "solo", "solo_resilient", "batch", "batch_resilient", "batch_sharded"])
def test_sweep_programs_keep_sweep_in_their_names(kind):
    """The device-trace readers (``kernel_ms``, ``placement_sweep_roofline``)
    find the sweep by the substring ``sweep`` in its program's name."""
    from repro.core.placement_backends.jax_runtime import x64

    with x64():
        text = _lower_sweep(kind).as_text()
    name = re.search(r"module @(\S+)", text).group(1)
    assert "sweep" in name, name
