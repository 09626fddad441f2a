"""launch_ms: per answer, the jitted sweep's call and its output slicing,
a part of dispatch_ms (WalkStats.launch_us, span ``sched.launch``), on
the host clock.  Nothing where the program records no such span."""


def read(rec: dict) -> float | None:
    us = rec["walk"].get("launch_us")
    return None if us is None else us * 1e-3 / rec["answers"]
