"""setup_s: seconds from the start of the run to the start of the window
(runtime start, instance selection, warm-up), on the host clock."""


def read(rec: dict) -> float:
    return rec["setup_s"]
