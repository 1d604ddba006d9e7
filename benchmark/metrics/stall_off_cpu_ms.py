"""Of the window's stalled time, the part the scheduler thread was not on a
CPU beyond its kind's habit, in ms: ``report()``'s ``stall_off_cpu_s``
differenced over the window. A stalled step's wall less its
``time.thread_time`` seconds, less the median of the same over its kind's
last nine steps (a sound step waits for the device too). Beside
``stall_ms_in_window``: near the whole of it, the thread was blocked or
descheduled while the wall ran; near 0 of it, it was computing. 0 in a window
with no stall. None where the program keeps no such clock."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "stall_off_cpu_s" not in r0 or "stall_off_cpu_s" not in r1:
        return None
    return 1e3 * (r1["stall_off_cpu_s"] - r0["stall_off_cpu_s"])
