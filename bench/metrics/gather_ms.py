"""gather_ms: per answer, the gathers of the sorted TFS rows' shares, a
part of enumerate_ms (WalkStats.gather_us, span ``sched.gather``), on the
host clock.  Nothing where the program records no such span."""


def read(rec: dict) -> float | None:
    us = rec["walk"].get("gather_us")
    return None if us is None else us * 1e-3 / rec["answers"]
