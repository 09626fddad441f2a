"""unbatch_ms: per answer, the solo Pallas entry's dropping of the
instance axis from the sweep's outputs, a part of dispatch_ms
(WalkStats.unbatch_us, span ``sched.unbatch``), on the host clock.
Nothing where the program records no such span."""


def read(rec: dict) -> float | None:
    us = rec["walk"].get("unbatch_us")
    return None if us is None else us * 1e-3 / rec["answers"]
