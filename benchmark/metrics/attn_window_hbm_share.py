"""The least time the sliding layers' live ring rows need at the HBM peak,
each read once (``rooflines_mellum.window_rows_bytes``: min(length, window)
rows a stream a layer), as a share of the ``attn.window`` scope's device time
per step. Reads low by design: the gather moves every ring whole, writes the
gathered copy and the attend reads it again, and the scope holds the
projections too."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import hbm_share, scope_ms
from benchmark.rooflines_mellum import window_rows, window_rows_bytes


def read(record: dict):
    ms = scope_ms(record, ("attn.window",), STEP_SPAN)
    rows = window_rows(record)
    if ms is None or rows is None:
        return None
    return hbm_share(record, window_rows_bytes(record["config"], rows[0]), ms)
