#!/usr/bin/env python3
"""The control of the correctness check, and the program's readings beside it.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--dtype bfloat16]

For each seed the cell is set up as a run sets it up (the same panel, on
the same chips), one pass of the program is compared with the float64
reference, and so is the reference computed at ``--dtype`` (Alg 1 and
Alg 2 below float64) in the program's place.  Each line gives, per seed,
the plans that differ: the program's are the check's lower reading, the
control's its upper one.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import program, reference, run  # noqa: E402


def readings(workload: str, seed: int, dtype, *, root=run.ROOT, allow_cpu=False) -> dict:
    cell_def, config, mix, _, _ = run.cell_spec(root, workload)
    run._runtime(root, int(cell_def["chips"]), allow_cpu)
    import contextlib

    ctx = run.Context(config, mix, seed, lambda _: contextlib.nullcontext(),
                      int(cell_def["chips"]))
    cell = run.load_module(root / "bench" / "drivers" / f"{mix['driver']}.py").setup(ctx)
    got = [program.plain(r) for r in cell.run_pass()]
    cell.close()
    t0 = time.perf_counter()
    refs = [reference.solve(inst, *cell.fleet) for inst in cell.instances]
    ref_s = time.perf_counter() - t0
    ctl = [reference.solve(inst, *cell.fleet, dtype=dtype) for inst in cell.instances]
    return {
        "workload": workload, "seed": seed, "instances": len(refs), "reference_s": ref_s,
        "program_differing": sum(bool(program.differs(g, w)) for g, w in zip(got, refs,
                                                                             strict=True)),
        "control_differing": sum(bool(program.differs(c, w)) for c, w in zip(ctl, refs,
                                                                             strict=True)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    import ml_dtypes
    import numpy as np

    dtype = getattr(ml_dtypes, args.dtype, None) or np.dtype(args.dtype).type
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"dtype": args.dtype, **readings(args.workload, seed, dtype)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
