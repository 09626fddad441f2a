"""The harness finds a cell's configuration, mix and metrics by name, and
refuses a machine without the cell's chips."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchtest_util as util
from bench import run


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return util.toy_root(tmp_path_factory.mktemp("toy"))


def _run(root, workload, trace, seed=util.SEEDS[0]):
    return run.run(["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
                    "--trace", str(trace)], root=root, allow_cpu=True)


@pytest.mark.parametrize("workload", ["toy.toy_solve", "toy.toy_whatif"])
def test_a_cell_added_as_files_is_found_by_name(toy, workload):
    out = _run(toy, workload, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    # The toy metric is a file of its own, read in the per-layer run.
    assert out["metrics"]["toy_answers"]["value"] == out["attempted"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload,metric", [("toy.toy_solve", "solve_ms"),
                                             ("toy.toy_whatif", "instances_per_s")])
def test_the_end_to_end_run_reports_the_cells_metrics(toy, workload, metric):
    out = _run(toy, workload, trace=0)
    assert set(out["metrics"]) == {metric, "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}


def test_the_same_seed_draws_the_same_panel(toy):
    a, _ = util.setup_cell("toy.toy_solve", util.SEEDS[1], root=toy)
    b, _ = util.setup_cell("toy.toy_solve", util.SEEDS[1], root=toy)
    c, _ = util.setup_cell("toy.toy_solve", util.SEEDS[2], root=toy)
    assert a.instances == b.instances != c.instances


def test_the_what_if_live_fleet_is_the_same_for_every_seed(toy):
    a, _ = util.setup_cell("toy.toy_whatif", util.SEEDS[1], root=toy)
    b, _ = util.setup_cell("toy.toy_whatif", util.SEEDS[2], root=toy)
    assert [i[:-1] for i in a.instances] == [i[:-1] for i in b.instances]
    assert [i[-1] for i in a.instances] != [i[-1] for i in b.instances]


def test_a_suffixed_metric_falls_back_to_its_stems_reader():
    assert run.reader(util.ROOT / "bench", "enumerate_ms.solve").__file__.endswith(
        "enumerate_ms.py")


def test_every_metric_and_mix_named_in_the_benchmark_has_its_file():
    spec = json.loads((util.ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(run.reader(util.ROOT / "bench", m["name"]), "read"), m["name"]
    for w in spec["workloads"]:
        mix = json.loads((util.ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (util.ROOT / "bench" / "drivers" / f"{mix['driver']}.py").is_file()
    for c in spec["configs"]:
        assert json.loads((util.ROOT / c["file"]).read_text())["name"] == c["name"]


def test_no_result_without_a_chip(tmp_path):
    # Only BENCHMARK.json and the benchmark's own files, and no TPU.
    shutil.copytree(util.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(util.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fpga_table1.solve", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
