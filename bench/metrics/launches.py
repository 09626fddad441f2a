"""launches: per answer, sweep programs enqueued on the device
(WalkStats.launches).  Nothing where the program counts no launches."""


def read(rec: dict) -> float | None:
    n = rec["walk"].get("launches")
    return None if n is None else n / rec["answers"]
