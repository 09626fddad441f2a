"""abandoned_row_share: of the rows the walk dispatched, the share past
the winner's block, whose verdicts the walk never used
(WalkStats.abandoned_rows over WalkStats.rows), in %.  Nothing where the
program counts no such rows."""


def read(rec: dict) -> float | None:
    w = rec["walk"]
    n = w.get("abandoned_rows")
    if n is None or not w["rows"]:
        return None
    return 100.0 * n / w["rows"]
