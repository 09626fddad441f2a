"""solve_ms: the window's host-clock length over the solves it completed."""


def read(rec: dict) -> float | None:
    if rec["unit"] != "solve":
        return None
    return rec["window_s"] * 1e3 / rec["answers"]
