"""d2h_kb: per answer, KiB of verdict arrays brought back from the
sweep's device calls (WalkStats.d2h_bytes).  Nothing where the program
counts no such bytes."""


def read(rec: dict) -> float | None:
    n = rec["walk"].get("d2h_bytes")
    return None if n is None else n / 1024 / rec["answers"]
