"""Shared helpers of the benchmark's tests: cells set up on the CPU, and
toy checkouts that add a configuration, a mix and a metric as files."""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

# Twelve seeds of the size the benchmark's runs get (above 2**31).
SEEDS = [2**31 + 17 * i + 5 for i in range(12)]


def setup_cell(workload: str, seed: int, root: Path = ROOT, **mix_overrides):
    """A cell set up as a run sets it up, on the CPU; ``mix_overrides``
    replace keys of its mix (the engine, say)."""
    cell_def, config, mix, _, _ = run.cell_spec(root, workload)
    mix = {**mix, **mix_overrides}
    ctx = run.Context(config, mix, seed, lambda _: contextlib.nullcontext(),
                      int(cell_def["chips"]))
    driver = run.load_module(root / "bench" / "drivers" / f"{mix['driver']}.py")
    return driver.setup(ctx), ctx


TOY_CONFIG = {
    "name": "toy_u50",
    "source": "arXiv:2311.11015 Table II",
    "tasks": [
        {"name": "LZ-4", "period": 600.0, "ii": 2.0, "data": 107375.0,
         "throughput": [129.37, 165.29, 198.84], "power": [6.38, 6.55, 6.64]},
        {"name": "ZSTD", "period": 600.0, "ii": 2.0, "data": 107375.0,
         "throughput": [244.03, 255.65], "power": [6.89, 7.06]},
        {"name": "VAdd", "period": 600.0, "ii": 2.0, "data": 19.0,
         "throughput": [0.12, 0.16, 0.18, 0.2], "power": [6.12, 6.21, 6.38, 6.55]},
    ],
    "n_f": 4, "t_slr": 600.0, "t_cfg": 21.0, "copies": 2, "data_jitter": 0.05,
}

TOY_MIXES = {
    "toy_solve": {"driver": "solve", "engine": "jax", "panel": 3, "rank_band": [0, 512],
                  "draws": 4, "max_draws": 50},
    "toy_whatif": {"driver": "whatif", "engine": "jax", "batch": 4, "arrival": "VAdd",
                   "rank_band": [0, 512], "max_draws": 16, "shard": None},
    "toy_whatif_mesh": {"driver": "whatif", "engine": "jax", "batch": 8, "arrival": "VAdd",
                        "rank_band": [0, 512], "max_draws": 32, "shard": "auto"},
}

TOY_METRIC = '''"""toy_answers: answers in the window (a toy reader)."""


def read(rec):
    return float(rec["answers"])
'''


def toy_root(tmp: Path) -> Path:
    """A checkout with the benchmark as committed plus a toy configuration,
    three toy mixes and a toy metric, each added as a file of its own and
    an entry in ``BENCHMARK.json``; no existing file is edited."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs" / "toy_u50.json").write_text(json.dumps(TOY_CONFIG))
    spec["configs"].append({"name": "toy_u50", "source": TOY_CONFIG["source"],
                            "file": "bench/configs/toy_u50.json", "reduced": [],
                            "why": "toy"})
    for name, mix in TOY_MIXES.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        chips = 4 if mix.get("shard") else 1
        spec["workloads"].append({"name": f"toy.{name}", "config": "toy_u50",
                                  "traffic": name, "chips": chips, "why": "toy"})
    (tmp / "bench" / "metrics" / "toy_answers.py").write_text(TOY_METRIC)
    names = [w["name"] for w in spec["workloads"] if w["name"].startswith("toy.")]
    for m in spec["end_to_end"]:
        if m["name"] in ("solve_ms", "instances_per_s"):
            m["workloads"] = m["workloads"] + [n for n in names if
                                               ("solve" in n) == (m["name"] == "solve_ms")]
    spec["per_layer"].append({"name": "toy_answers", "unit": "1", "better": "higher",
                              "source": "host_clock", "layer": "toy", "moves": "setup_s",
                              "workloads": names})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


# What one pass of each cell's panel does on the CPU's numpy engine (one
# block in flight): rows swept and blocks are exact, |TSS| is fixed by the
# mix, and |TFS| lies in the range PERF.md records for these seeds.  On a
# chip the device engines keep a second block in flight, which adds one
# block a solve (or a round a batch) to the rows.
WORK = {
    "fpga_table1.solve": {"rows": 24 * 37_440, "blocks": 24 * 4,
                          "tss": 24 * 1_048_576, "tfs": (16_207_733, 16_491_868)},
    "u50_table2.solve": {"rows": 24 * 64, "blocks": 24 * 1,
                         "tss": 24 * 331_776, "tfs": (7_962_060, 7_962_573)},
    "fpga_table1.whatif": {"rows": 16 * 4_608, "blocks": 16 * 2,
                           "tss": 16 * 1_048_576, "tfs": (10_945_665, 11_024_840)},
}


def check_panel(workload: str, seed: int) -> None:
    """Every instance of the seed's panel lies in its mix's rank band (by
    the reference), and the pass's work is what ``WORK`` records."""
    from bench import generate, reference

    cell, ctx = setup_cell(workload, seed, engine="numpy")
    band = ctx.traffic["rank_band"]
    refs = [reference.solve(inst, *cell.fleet) for inst in cell.instances]
    assert len(refs) == cell.per_pass
    assert all(r["feasible"] and generate.in_band(r["rank"], band) for r in refs), (
        [r["rank"] for r in refs], band)
    work = WORK[workload]
    # Set-up's work is the same for every seed: a fixed count of draws.
    assert ctx.parts["draws"] == ctx.traffic.get("draws", ctx.traffic.get("batch"))
    assert ctx.parts["panel_rows"] == work["rows"]
    assert ctx.parts["panel_blocks"] == work["blocks"]
    assert sum(r["n_tss"] for r in refs) == work["tss"]
    lo, hi = work["tfs"]
    assert lo <= sum(r["n_tfs"] for r in refs) <= hi
