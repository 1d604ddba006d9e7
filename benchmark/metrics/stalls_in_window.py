"""Stalls of the window: launched ``step()`` calls whose wall lay 50 ms or
more over the median of their kind's last nine (the launched steps with the
same admissions, prefill tokens and "did it evict"), and waits of the caller
of 50 ms or more between two launched steps: ``report()``'s ``stalls``,
differenced over the window. Each is one record of ``report()["stall_log"]``
and one warning on the run's stderr, with what the scheduler thread and the
host did meanwhile. None where the program keeps no such count."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "stalls" not in r0 or "stalls" not in r1:
        return None
    return r1["stalls"] - r0["stalls"]
