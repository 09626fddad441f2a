"""enumerate_ms: per answer, the walk's time producing blocks of TFS rows
(WalkStats.enumerate_us), on the host clock."""


def read(rec: dict) -> float:
    return rec["walk"]["enumerate_us"] * 1e-3 / rec["answers"]
