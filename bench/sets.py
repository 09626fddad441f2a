#!/usr/bin/env python3
"""Run one cell several times, one process a run, and summarise the spread.

    python3 bench/sets.py --workload <cell> --seeds 11,12,13 --seconds 20 \
        [--trace 0|1] [--out <file>.jsonl]

Each run is ``bench/run.py`` as the benchmark's command runs it, one after
another (a chip belongs to one process at a time; this parent never
touches JAX).  Every run's result line, its exit code, its earlier lines
and the last lines of its standard error go to ``--out``; the summary
gives each metric's median and its spread, the distance between the
first and the third quartile (``statistics.quantiles(n=4)``) as a share
of the median.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().with_name("run.py")


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=1200.0)
    args = ap.parse_args(argv)
    runs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, str(RUN), "--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", str(args.trace)],
            capture_output=True, text=True, timeout=args.timeout,
        )
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        run = {"workload": args.workload, "seed": seed, "trace": args.trace, "rc": proc.returncode,
               "wall_s": time.perf_counter() - t0, "result": result, "lines": lines[:-1],
               "stderr_tail": proc.stderr[-3000:]}
        runs.append(run)
        brief = {k: v["value"] for k, v in (result or {}).get("metrics", {}).items()}
        print(f"seed={seed} rc={proc.returncode} wall_s={run['wall_s']:.1f} "
              f"correct={(result or {}).get('correct')} {brief}", flush=True)
        if proc.returncode != 0 or result is None:
            print(proc.stderr[-3000:], flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(run) + "\n")
    names = sorted({k for r in runs for k in ((r["result"] or {}).get("metrics") or {})})
    for name in names:
        vals = [r["result"]["metrics"][name]["value"] for r in runs
                if r["result"] and name in r["result"]["metrics"]]
        print(f"summary {args.workload} {name} n={len(vals)} median={statistics.median(vals)} "
              f"spread={spread(vals)} values={vals}", flush=True)
    return 0 if all(r["rc"] == 0 and r["result"] for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
