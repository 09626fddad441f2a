"""instances_per_s: what-if instances answered over the window's host-clock length."""


def read(rec: dict) -> float | None:
    if rec["unit"] != "instance":
        return None
    return rec["answers"] / rec["window_s"]
