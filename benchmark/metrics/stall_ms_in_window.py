"""What the window's stalls took over their kinds' expectation, in ms:
``report()``'s ``stall_excess_s`` differenced over the window (a stalled
step's wall less the median of its kind's last nine; the whole of a caller's
wait of 50 ms or more between two launched steps). Over the window's length
it is the share of the window the stalls cost. None where the program keeps
no such clock."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "stall_excess_s" not in r0 or "stall_excess_s" not in r1:
        return None
    return 1e3 * (r1["stall_excess_s"] - r0["stall_excess_s"])
