"""sort_ms: per answer, the stable sort of the TFS by power, a part of
enumerate_ms (WalkStats.sort_us, span ``sched.tfs_sort``), on the host
clock.  Nothing where the program records no such span."""


def read(rec: dict) -> float | None:
    us = rec["walk"].get("sort_us")
    return None if us is None else us * 1e-3 / rec["answers"]
