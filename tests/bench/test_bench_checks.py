"""The correctness check: the plain reference agrees with the program, and
the control and every fault a cell can have come out as not correct.

Faults are planted underneath a run with its chip check skipped:

* an answer altered where it is produced: one placement segment of a
  solve, or the total power of one what-if answer, moved by one ulp;
* half of the batch left out: ``what_if_many`` returns the first half;
* the exchange between chips left out: every shard but the first keeps
  its verdicts to itself (zeros arrive), on four virtual CPU devices.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import ml_dtypes
import numpy as np
import pytest

import benchtest_util as util
from bench import control, generate, program, reference, run


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return util.toy_root(tmp_path_factory.mktemp("toy"))


def _run(root, workload, plant=None, seed=util.SEEDS[4]):
    """A run of ``workload`` with ``plant()`` breaking its timed path once
    set-up has ended."""
    return run.run(["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
                    "--trace", "0"], root=root, allow_cpu=True, after_setup=plant)


@pytest.mark.parametrize("seed", util.SEEDS[:3])
def test_reference_agrees_with_the_program(seed):
    from repro.core import PADPSFRScheduler

    config = json.loads((util.ROOT / "bench/configs/fpga_table1.json").read_text())
    fleet = generate.fleet(config)
    rows = generate.mix_tasks(config, generate.rng(seed))
    got = program.plain(PADPSFRScheduler(program.fleet(*fleet), engine="numpy")
                        .schedule(program.tasks(rows)))
    assert program.differs(got, reference.solve(rows, *fleet)) == []


@pytest.mark.parametrize("workload", ["toy.toy_solve", "toy.toy_whatif"])
def test_the_program_passes_and_the_bfloat16_control_fails(toy, workload):
    seen = control.readings(workload, util.SEEDS[5], ml_dtypes.bfloat16, root=toy,
                            allow_cpu=True)
    assert seen["program_differing"] == 0
    assert seen["control_differing"] >= 1


def test_a_sound_run_is_correct(toy):
    out = _run(toy, "toy.toy_solve")
    assert out["correct"] is True
    assert out["checks"] == {"answers_wrong": {"value": 0, "limit": 0}}


def _one_ulp_later(plan):
    seg = plan.scripts[0].segments[-1]
    plan.scripts[0].segments[-1] = dataclasses.replace(seg, end=np.nextafter(seg.end, np.inf))
    return plan


def test_an_altered_solve_is_caught(toy, monkeypatch):
    from repro.core import PADPSFRScheduler

    real = PADPSFRScheduler.schedule

    def altered(self, tasks, **kw):
        res = real(self, tasks, **kw)
        if res.feasible:
            res = dataclasses.replace(res, plan=_one_ulp_later(res.plan))
        return res

    out = _run(toy, "toy.toy_solve",
               lambda: monkeypatch.setattr(PADPSFRScheduler, "schedule", altered))
    assert out["correct"] is False
    assert out["checks"]["answers_wrong"]["value"] == out["attempted"]


def test_an_altered_what_if_answer_is_caught(toy, monkeypatch):
    from repro.service import SchedulerService

    real = SchedulerService.what_if_many

    def altered(self, arrivals, **kw):
        res = real(self, arrivals, **kw)
        res[-1] = dataclasses.replace(res[-1], total_power=np.nextafter(
            res[-1].total_power, np.inf))
        return res

    out = _run(toy, "toy.toy_whatif",
               lambda: monkeypatch.setattr(SchedulerService, "what_if_many", altered))
    assert out["correct"] is False
    assert out["checks"]["answers_wrong"]["value"] == out["attempted"] // 4


def test_half_of_the_batch_left_out_is_caught(toy, monkeypatch):
    from repro.service import SchedulerService

    real = SchedulerService.what_if_many

    def half(self, arrivals, **kw):
        res = real(self, arrivals, **kw)
        return res[: len(res) // 2]

    out = _run(toy, "toy.toy_whatif",
               lambda: monkeypatch.setattr(SchedulerService, "what_if_many", half))
    assert out["correct"] is False
    assert out["checks"]["answers_wrong"]["value"] == out["attempted"] // 2


MESH = r"""
import json, sys
from pathlib import Path
sys.path[:0] = [sys.argv[2] + "/tests/bench", sys.argv[2], sys.argv[2] + "/src"]
import benchtest_util as util
from bench import run
import jax, jax.numpy as jnp

root = util.toy_root(Path(sys.argv[1]))
args = ["--workload", "toy.toy_whatif_mesh", "--seed", str(util.SEEDS[6]),
        "--seconds", "0.3", "--trace", "0"]
sound = run.run(args, root=root, allow_cpu=True)
real, calls = jax.shard_map, []


def no_exchange(f, *a, mesh, **kw):
    def local(*xs):
        first = jax.lax.axis_index(mesh.axis_names[0]) == 0
        return tuple(jnp.where(first, o, jnp.zeros_like(o)) for o in f(*xs))
    calls.append(1)
    return real(local, *a, mesh=mesh, **kw)


def plant():
    jax.shard_map = no_exchange
    jax.clear_caches()


broken = run.run(args, root=root, allow_cpu=True, after_setup=plant)
print(json.dumps({"sound": sound, "broken": broken, "planted": len(calls)}))
"""


def test_the_exchange_between_chips_left_out_is_caught(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", MESH, str(tmp_path), str(util.ROOT)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    # Both runs found the instance axis over four devices (or raised).
    assert got["sound"]["correct"] is True
    assert got["planted"] > 0
    assert got["broken"]["correct"] is False
    assert got["broken"]["checks"]["answers_wrong"]["value"] > 0


def test_a_mesh_cell_on_fewer_devices_is_refused(toy):
    # One CPU device: shard="auto" cannot lay the instances over four.
    with pytest.raises(RuntimeError, match="not 4"):
        _run(toy, "toy.toy_whatif_mesh")
