"""device_idle_share: the share of the traced window in which no operation
ran on a device (profiler trace, averaged over the devices), in %.
Nothing where the trace holds no device."""


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
