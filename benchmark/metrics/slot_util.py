"""Slots in use over slots, mean over the window's steps (the batcher's
``slot_util_mean``, differenced across the window)."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    n = r1["steps"] - r0["steps"]
    if not n:
        return None
    return 100.0 * (r1["slot_util_mean"] * r1["steps"]
                    - r0["slot_util_mean"] * r0["steps"]) / n
