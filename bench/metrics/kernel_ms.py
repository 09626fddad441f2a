"""kernel_ms: per answer, device time of the placement sweep's programs
(profiler trace, averaged over the devices).  Nothing without a trace,
or where no sweep ran on a device."""


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or tr["kernel_s"] <= 0:
        return None
    return tr["kernel_s"] * 1e3 / rec["answers"]
