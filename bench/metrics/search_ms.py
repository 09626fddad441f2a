"""search_ms: per answer, the entry call's eq-7 search over the whole TSS
(WalkStats.search_us, span ``sched.eq7_search``), on the host clock.
Nothing where the program records no such span."""


def read(rec: dict) -> float | None:
    us = rec["walk"].get("search_us")
    return None if us is None else us * 1e-3 / rec["answers"]
