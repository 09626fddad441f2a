"""prepare_ms: per answer, the host pad and cast of the blocks handed to
the sweep, a part of dispatch_ms (WalkStats.prepare_us, span
``sched.prepare``), on the host clock.  Nothing where the program records
no such span."""


def read(rec: dict) -> float | None:
    us = rec["walk"].get("prepare_us")
    return None if us is None else us * 1e-3 / rec["answers"]
