"""placement_sweep_roofline: the least time one chip could sweep the
window's rows in (bytes and operations from ``bench.roofline``, peaks
from ``bench/peaks.json``) over the sweep's device time summed over the
devices, in %.  Nothing without a trace, or where no sweep ran on a
device."""

from bench import roofline


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or tr["kernel_s"] <= 0:
        return None
    nbytes, ops = roofline.sweep_cost(rec["walk"]["rows"], rec["n_t"], rec["n_f"])
    least, _ = roofline.least_time(nbytes, ops, roofline.peak(rec["device_kind"]))
    return 100.0 * least / tr["kernel_total_s"]
