"""dispatch_ms: per answer, the walk's time handing blocks to the placement
backend (WalkStats.place_us: padding, casting and enqueueing the sweep),
on the host clock."""


def read(rec: dict) -> float:
    return rec["walk"]["place_us"] * 1e-3 / rec["answers"]
