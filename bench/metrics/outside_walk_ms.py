"""outside_walk_ms: per answer, host time inside the program's entry call
that the walk's WalkStats do not cover (the eq-7 search, building the
result), on the host clock."""


def read(rec: dict) -> float:
    w = rec["walk"]
    walk_s = (w["enumerate_us"] + w["place_us"] + w["sync_us"] + w["materialize_us"]) * 1e-6
    return (rec["call_s"] - walk_s) * 1e3 / rec["answers"]
