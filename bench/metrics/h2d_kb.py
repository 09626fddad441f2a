"""h2d_kb: per answer, KiB of host arrays handed to the sweep's device
calls after padding and casting (WalkStats.h2d_bytes).  Nothing where the
program counts no such bytes."""


def read(rec: dict) -> float | None:
    n = rec["walk"].get("h2d_bytes")
    return None if n is None else n / 1024 / rec["answers"]
