"""sync_ms: per answer, the walk's time waiting for placement verdicts
(WalkStats.sync_us), on the host clock."""


def read(rec: dict) -> float:
    return rec["walk"]["sync_us"] * 1e-3 / rec["answers"]
