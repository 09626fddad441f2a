"""sorted_row_share: of the TFS rows the exhaustive walks held, the share
they put in power order (WalkStats.sorted_rows over WalkStats.tfs_rows),
in %: how far the lazy sort (span ``sched.tfs_sort``) reaches.  Nothing
where the program counts no such rows."""


def read(rec: dict) -> float | None:
    w = rec["walk"]
    n, total = w.get("sorted_rows"), w.get("tfs_rows")
    if n is None or not total:
        return None
    return 100.0 * n / total
