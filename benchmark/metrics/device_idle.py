"""Share of the traced window in which no operation ran on the device
(1 - union of operation intervals / window; mean over the cell's chips)."""


def read(record: dict):
    tr = record.get("trace")
    return None if not tr else 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
