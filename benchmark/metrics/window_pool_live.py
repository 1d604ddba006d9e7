"""Ring rows inside some stream's window over the rows the rings hold, a
sliding layer: ``report()``'s ``window_rows_live`` / ``window_rows_capacity``
(min(length, sliding_window) summed over the live streams, against
``max_slots x window_pages x page_size``), the mean of the window's two
edges. None where the program has no such counter."""
from benchmark.rooflines_mellum import window_rows


def read(record: dict):
    rows = window_rows(record)
    return None if rows is None else 100.0 * rows[0] / rows[1]
