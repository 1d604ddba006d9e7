"""Latent rows of live streams over the rows the pages hold, a latent layer:
``report()``'s ``latent_rows_live`` / ``latent_rows_capacity`` (the live
streams' lengths summed, against ``(num_pages - 1) x page_size``), the mean
of the window's two edges. None where the program has no such counter."""
from benchmark.rooflines_mistral4 import latent_rows


def read(record: dict):
    rows = latent_rows(record)
    return None if rows is None else 100.0 * rows[0] / rows[1]
