"""CLI entry-point smoke tests (subprocess)."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=300):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run(
        [sys.executable, "-m"] + args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=_ROOT,
    )


def test_schedule_cli():
    proc = _run([
        "repro.launch.schedule",
        "--slices", "4", "--slice-chips", "64",
        "--t-slr", "3600", "--t-cfg", "45",
        "--job", "yi-34b:train_4k:1800:250",
        "--job", "smollm-135m:decode_32k:600:5000",
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "chosen-rank" in proc.stdout
    assert "time slice" in proc.stdout  # Gantt rendered


def test_train_cli(tmp_path):
    proc = _run([
        "repro.launch.train", "--arch", "mamba2-130m",
        "--steps", "3", "--seq-len", "32", "--batch", "2",
        "--ckpt-dir", str(tmp_path / "ck"),
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "done: step=3" in proc.stdout


def test_serve_cli():
    proc = _run([
        "repro.launch.serve", "--arch", "recurrentgemma-2b",
        "--batch", "2", "--prompt-len", "24", "--new-tokens", "4",
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "generated" in proc.stdout


@pytest.mark.slow
def test_dryrun_cli_single_cell(tmp_path):
    proc = _run([
        "repro.launch.dryrun", "--arch", "mamba2-130m",
        "--shape", "decode_32k", "--mesh", "single",
        "--out", str(tmp_path / "d.json"),
    ], timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
