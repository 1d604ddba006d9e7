"""Of the rows the sparse layers' slots held live, those their queries
attended, in percent: ``report()``'s ``sparse_rows_attended`` (``min(length,
topk)`` a rider a step) / ``sparse_rows_live`` differenced over the window.
Lower is sparser: what the mix really offered the mechanism (100: no slot
had passed ``topk``, and the cell measured plain attention). None where the
program has no such counters."""
from benchmark.rooflines_keye_vl2 import rows_a_step


def read(record: dict):
    rows = rows_a_step(record)
    if rows is None or not rows[2]:
        return None
    return 100.0 * rows[1] / rows[2]
