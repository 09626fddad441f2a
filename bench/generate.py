"""The one generator: instances drawn from a configuration, a mix and a seed.

A configuration (``bench/configs/<name>.json``) fixes the deployment: the
published task rows, how many copies of each the fleet serves, the fleet
(``n_f``, ``t_slr``, ``t_cfg``) and the band by which the seed may move
each task's data volume.  A mix (``bench/traffic/<name>.json``) fixes what
is asked of it.  The seed decides only the data volumes (for a what-if
mix, only the candidate arrivals'); so the task mix (and with it |TSS|)
and the fleet are the same for every seed.

Tasks are plain dicts (``name``, ``period``, ``ii``, ``data``,
``throughput``, ``power``), read by the reference as they are and turned
into the program's objects by the drivers.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """The seed's generator; ``stream`` separates independent draws."""
    return np.random.default_rng([int(seed), int(stream)])


def fleet(config: dict) -> tuple[int, float, float]:
    return int(config["n_f"]), float(config["t_slr"]), float(config["t_cfg"])


def jittered(row: dict, name: str, gen: np.random.Generator, band: float) -> dict:
    """One published task row with its data volume moved within ``band``."""
    task = dict(row)
    task["name"] = name
    task["data"] = float(row["data"]) * (1.0 + float(gen.uniform(-band, band)))
    return task


def mix_tasks(config: dict, gen: np.random.Generator, *, leave_out: str | None = None,
              jitter: float | None = None) -> list[dict]:
    """The configuration's task mix: every published row ``copies`` times,
    copy by copy, each with its own data volume, moved within ``jitter``
    (the configuration's ``data_jitter`` unless given; 0 keeps the
    published volumes).  ``leave_out`` drops the last copy of the named row
    (the seat a what-if candidate takes)."""
    copies = int(config["copies"])
    band = float(config["data_jitter"] if jitter is None else jitter)
    out = []
    for c in range(copies):
        for row in config["tasks"]:
            if c == copies - 1 and row["name"] == leave_out:
                continue
            out.append(jittered(row, f"{row['name']}.{c}", gen, band))
    return out


def row_named(config: dict, name: str) -> dict:
    for row in config["tasks"]:
        if row["name"] == name:
            return row
    raise KeyError(f"configuration {config['name']} has no task row {name!r}")


def in_band(rank: int, band: list[int]) -> bool:
    """Whether a winner's rank lies in the half-open band ``[lo, hi)``."""
    return band[0] <= rank < band[1]
