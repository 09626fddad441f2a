"""From a profiler trace to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists; ``reduce`` turns them into the numbers the per-layer readers use:

* busy time: per device, the union of the intervals in which an
  operation ran inside the traced window, averaged over the devices;
* kernel time: per device, the summed durations of the programs whose
  name contains ``KERNEL`` (the placement sweep's jitted programs),
  averaged over the devices, and summed over them;
* the device operations that took most time, and the longest idle gaps,
  each named by the innermost host span of the benchmark that covers it.

Times in the reduced form are seconds.
"""

from __future__ import annotations

import glob
import os

# The placement sweep's jitted programs: jit__placement_sweep_batch_padded
# (Pallas), jit_placement_sweep_*ref and the shard_map'd jit_sweep (jax).
KERNEL = "sweep"
DEVICE = "/device:TPU:"
WINDOW = "bench_window"
TOP = 10


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, spans: set[str]) -> dict:
    """Device planes' ops and programs, and the host spans named in ``spans``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    host: list[tuple[str, int, int]] = []
    layout: list[str] = []
    for plane in data.planes:
        lines = list(plane.lines)
        layout.append(f"{plane.name}: " + ", ".join(
            f"{ln.name}[{sum(1 for _ in ln.events)}]" for ln in lines))
        if plane.name.startswith(DEVICE):
            names = {ln.name for ln in lines}
            ops_line = "XLA Ops" if "XLA Ops" in names else None
            dev = {"ops": [], "modules": []}
            for ln in lines:
                evs = [(short(e.name), int(e.start_ns), int(e.duration_ns)) for e in ln.events]
                if ln.name == "XLA Modules":
                    dev["modules"] += evs
                elif ln.name == ops_line or (ops_line is None and ln.name != "Steps"):
                    dev["ops"] += evs
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in ln.events if e.name in spans]
    return {"devices": devices, "host": host, "layout": layout}


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%copy.1 = s32[...] copy(...)``
    becomes ``copy.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events, lo: int, hi: int):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def reduce(tr: dict) -> dict:
    """Busy, kernel and window seconds, top ops and idle gaps of a trace."""
    win = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = win[0]
    inner = [(n, s, s + d) for n, s, d in tr["host"] if n != WINDOW]

    def cover(t: int) -> str:
        best = None
        for n, a, b in inner:
            if a <= t < b and (best is None or b - a < best[1]):
                best = (n, b - a)
        return best[0] if best else "harness"

    busy, kernel, used = [], [], 0
    op_time: dict[str, int] = {}
    gaps: list[tuple[str, int]] = []
    for dev in tr["devices"].values():
        ops = list(_clip(dev["ops"], lo, hi))
        mods = list(_clip(dev["modules"], lo, hi))
        k = sum(b - a for n, a, b in mods if KERNEL in n)
        used += k > 0
        spans = _union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in spans))
        kernel.append(k)
        for n, a, b in ops:
            op_time[n] = op_time.get(n, 0) + (b - a)
        edges = [lo] + [t for ab in spans for t in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2], strict=True):
            if b > a:
                gaps.append((cover((a + b) // 2), b - a))
    n_dev = max(len(tr["devices"]), 1)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "kernel_s": sum(kernel) / n_dev * 1e-9,
        "kernel_total_s": sum(kernel) * 1e-9,
        "devices": len(tr["devices"]),
        "devices_with_kernel": used,
        "device_ops": [[n, t * 1e-9] for n, t in top],
        "idle_gaps": [[n, t * 1e-9] for n, t in gaps[:TOP]],
    }
