#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its metrics come from ``BENCHMARK.json``
at the root of the checkout; everything else is found by name:

* ``bench/configs/<config>.json``: the deployment (``file`` in
  ``BENCHMARK.json``);
* ``bench/traffic/<traffic>.json``: the mix, whose ``driver`` names
  ``bench/drivers/<driver>.py``, the code that sets the cell up and runs
  one pass of it;
* ``bench/metrics/<metric>.py``: each metric's reader; a name with a
  suffix (``enumerate_ms.solve``) falls back to the reader of its stem
  (``enumerate_ms.py``) where it has none of its own.

A run sets the cell up from the seed (that is the warm-up: every shape
the window uses is compiled there), freezes the garbage collector's
survivors, then runs the panel in whole passes until ``--seconds`` have
passed.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer ones, read from a profiler trace of the window.  After
the window every answer is compared with the plain reference
(``bench/reference.py``).  The last line of standard output is the
result; the numbers compared, each beside its limit, are the last lines
of standard error and the ``checks`` key of the result.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, the run prints no result and exits 1.
"""

from __future__ import annotations

import argparse
import contextlib
import ctypes
import gc
import importlib.util
import json
import resource
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
SPANS = {"bench_window", "solve", "what_if_many", "select", "build", "warmup"}


class NoChip(RuntimeError):
    """The machine lacks what the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(bench: Path, name: str):
    """The reader of metric ``name``: its own file, else its stem's."""
    own = bench / "metrics" / f"{name}.py"
    return load_module(own if own.is_file() else bench / "metrics" / f"{name.split('.')[0]}.py")


def cell_spec(root: Path, workload: str) -> tuple[dict, dict, dict, list[dict], list[dict]]:
    """(cell, configuration, mix, end-to-end metrics, per-layer metrics)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in spec["per_layer"] if mine(m) and m["moves"] in e2e_names]
    return cell, config, mix, e2e, layers


class Context:
    """What a driver gets to set a cell up."""

    def __init__(self, config, traffic, seed, span, chips: int = 1) -> None:
        self.config, self.traffic, self.seed = config, traffic, seed
        self.span, self.chips = span, chips
        self.parts: dict = {}


class Counters:
    """Compiles, traces and persistent-cache reads, from JAX's monitoring."""

    def __init__(self) -> None:
        self.n = {COMPILE: 0, TRACE: 0, CACHE_HIT: 0}
        self.compile_s = 0.0

    def event(self, event: str, **_) -> None:
        if event in self.n:
            self.n[event] += 1

    def duration(self, event: str, duration: float, **_) -> None:
        if event in self.n:
            self.n[event] += 1
        if event == COMPILE:
            self.compile_s += duration

    def snapshot(self) -> dict:
        return {"compiles": self.n[COMPILE], "traces": self.n[TRACE],
                "cache_reads": self.n[CACHE_HIT]}


def _runtime(root: Path, chips: int, allow_cpu: bool):
    """Import JAX with the checkout's compile cache; refuse a machine
    without the cell's chips."""
    sys.path[:0] = [str(root / "src"), str(root)]
    import jax

    # A fixed directory inside the checkout, whatever the environment
    # says, and every program in it: later runs compile nothing.
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not allow_cpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return jax, devices


def _peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _compare(cell, passes: list[list]) -> tuple[int, int, str]:
    """Every answer of the window against the reference's plan: the
    answers whose plan differs, the answers missing, and the first
    difference."""
    from bench import program, reference

    answers = [[program.plain(r) for r in p] for p in passes]
    cell.close()
    gc.unfreeze()
    gc.collect()
    refs = [reference.solve(inst, *cell.fleet) for inst in cell.instances]
    differing = missing = 0
    first = ""
    for p in answers:
        missing += max(len(refs) - len(p), 0)
        for i, (got, want) in enumerate(zip(p, refs, strict=False)):
            bad = program.differs(got, want)
            if bad:
                differing += 1
                if not first:
                    first = (f"instance {i}: {', '.join(bad)} differ "
                             f"(rank {got['rank']} vs {want['rank']})")
    return differing, missing, first


def run(argv=None, *, root: Path = ROOT, allow_cpu: bool = False, t_start: float | None = None,
        after_setup=None) -> dict:
    """One run; returns the result line's object (without printing it).

    ``allow_cpu`` skips the look for the cell's chips and ``after_setup``
    is called once set-up has ended: the tests drive a run on the CPU
    with that, and plant faults in the timed path with this.
    """
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter() if t_start is None else t_start
    cell_def, config, mix, e2e, layers = cell_spec(root, args.workload)
    chips = int(cell_def["chips"])
    jax, devices = _runtime(root, chips, allow_cpu)
    from jax import monitoring

    counters = Counters()
    monitoring.register_event_listener(counters.event)
    monitoring.register_event_duration_secs_listener(counters.duration)
    tracing = bool(args.trace)

    def span(name: str):
        return jax.profiler.TraceAnnotation(name) if tracing else contextlib.nullcontext()

    ctx = Context(config, mix, args.seed, span, chips)
    driver = load_module(root / "bench" / "drivers" / f"{mix['driver']}.py")
    ctx.parts["runtime_s"] = time.perf_counter() - t0
    cell = driver.setup(ctx)
    cell.require()
    if after_setup is not None:
        after_setup()
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    before = counters.snapshot()
    compile_setup_s = counters.compile_s

    tmp = tempfile.TemporaryDirectory() if tracing else contextlib.nullcontext()
    with tmp as log_dir:
        if tracing:
            # Host spans and device activity; no tracing of Python calls.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        passes: list[list] = []
        pass_s: list[float] = []
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        with span("bench_window"):
            w0 = time.perf_counter()
            while True:
                p0 = time.perf_counter()
                passes.append(cell.run_pass())
                pass_s.append(time.perf_counter() - p0)
                window_s = time.perf_counter() - w0
                if window_s >= args.seconds:
                    break
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        trace = None
        if tracing:
            jax.profiler.stop_trace()
            from bench import devtrace

            tr = devtrace.load(devtrace.find(log_dir), SPANS)
            for line in tr["layout"]:
                print(f"trace_plane {line}", flush=True)
            trace = devtrace.reduce(tr)
    in_window = {k: v - before[k] for k, v in counters.snapshot().items()}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": _peak_bytes(devices[:chips])}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    answers = sum(len(p) for p in passes)
    walk = cell.stats.as_dict()
    rec = {"unit": cell.unit, "answers": answers, "window_s": window_s, "setup_s": setup_s,
           "walk": walk, "call_s": cell.call_s, "n_t": cell.n_t, "n_f": cell.n_f,
           "trace": trace, "device_kind": dev.device_kind}
    metrics = {}
    for m in (layers if tracing else e2e):
        value = reader(root / "bench", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    n = len(passes)
    print(f"cell {args.workload} seed={args.seed} trace={args.trace} engine={mix['engine']} "
          f"device={dev.device_kind} count={len(devices)}", flush=True)
    print("setup " + " ".join(f"{k}={v}" for k, v in ctx.parts.items())
          + f" setup_s={setup_s} compile_s={compile_setup_s} "
          + " ".join(f"setup_{k}={v}" for k, v in before.items()), flush=True)
    print("walk " + " ".join(f"{k}={v}" for k, v in walk.items() if k != "block_sizes"),
          flush=True)
    print(f"work_per_pass answers={answers / n} rows={walk['rows'] / n} "
          f"blocks={walk['n_blocks'] / n} "
          f"tss={sum(r.n_tss for r in passes[0])} tfs={sum(r.n_tfs for r in passes[0])}",
          flush=True)
    print(f"window passes={n} answers={answers} window_s={window_s} call_s={cell.call_s} "
          + " ".join(f"window_{k}={v}" for k, v in in_window.items())
          + f" page_faults={ru1.ru_minflt - ru0.ru_minflt}"
          + f" ctx_switches={ru1.ru_nvcsw - ru0.ru_nvcsw}+{ru1.ru_nivcsw - ru0.ru_nivcsw}",
          flush=True)
    print("pass_s " + " ".join(str(t) for t in pass_s), flush=True)
    if trace is not None:
        print(f"trace kernel_s={trace['kernel_s']} busy_s={trace['busy_s']} "
              f"window_s={trace['window_s']} devices={trace['devices']} "
              f"devices_with_kernel={trace['devices_with_kernel']}", flush=True)

    differing, missing, first = _compare(cell, passes)
    print(f"answers differing={differing} missing={missing}", flush=True)
    # One number: an answer that never comes is as wrong as a wrong one.
    wrong = differing + missing
    out = {"correct": wrong == 0, "attempted": n * cell.per_pass, "failed": wrong,
           "metrics": metrics, "device": device}
    if trace is not None:
        out["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    out["checks"] = {"answers_wrong": {"value": wrong, "limit": 0}}
    if first:
        print(f"first difference: {first}", file=sys.stderr)
    print(f"check answers_wrong={wrong} limit=0", file=sys.stderr, flush=True)
    return out


def steady_malloc() -> bool:
    """Serve every allocation from glibc's heap and never hand it back.

    The program allocates and frees temporaries of several MB in every
    solve.  Left to glibc, each may be mmap'd and unmapped again or served
    from the heap, by a threshold that moves with what the process freed
    before, so on a TPU v5e host two processes on the same
    seed ran the same solves 25% apart.  With no mmap and no trimming,
    every process reuses the heap it grew during set-up.
    """
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return False
    m_trim_threshold, m_mmap_max = -1, -4
    return bool(libc.mallopt(m_mmap_max, 0)) and bool(
        libc.mallopt(m_trim_threshold, 2**31 - 1))


def main(argv=None) -> int:
    malloc = steady_malloc()
    try:
        out = run(argv, t_start=T_START)
        print(f"steady_malloc={malloc}", flush=True)
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
