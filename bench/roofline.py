"""Operations and bytes of the Alg-2 placement sweep, and its roofline.

Counted from the rows the walk reports as swept, at one element width
for every engine, so a share reads the same work whatever implements the
sweep (the numpy, jax and Pallas engines, at whatever dtype and padding):

* bytes: each row's ``n_t`` shares in, and four verdict words out
  (feasible, placed tasks, splits, devices used), at ``WIDTH`` bytes;
* operations: at most ``n_t + n_f`` placement steps a row (each step
  places a task or moves to the next device), ``STEP_OPS`` arithmetic
  operations and comparisons each (``reference._verdicts``: the
  remaining share, the available capacity, the start, split and close
  tests).

The peaks come from ``peaks.json``, keyed by the device kind JAX reports;
a device missing there is an error.  The sweep moves a few bytes per
operation, so on a TPU its bound is the memory's.
"""

from __future__ import annotations

import json
from pathlib import Path

WIDTH = 4
OUT_WORDS = 4
STEP_OPS = 13


def sweep_cost(rows: int, n_t: int, n_f: int) -> tuple[float, float]:
    """(bytes, operations) of sweeping ``rows`` rows."""
    return float(rows * (n_t + OUT_WORDS) * WIDTH), float(rows * (n_t + n_f) * STEP_OPS)


def peak(device_kind: str) -> dict:
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def least_time(nbytes: float, ops: float, pk: dict) -> tuple[float, str]:
    """The least seconds one chip could take, and which bound sets it."""
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    t_ops = ops / pk["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
