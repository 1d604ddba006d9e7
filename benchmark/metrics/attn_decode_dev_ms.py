"""Device time per decode step under the ``attn.decode`` scope: every K/V
layer's q/k/v projections, the fetch of each slot's K/V out of the paged pool
and the attend (the row's write is ``paged_kv.write``'s, the layer scan's own
pool copies are unscoped). A chip's mean, as ``step_dev_ms``: on the four-chip
cell the sum over its chips' layers / 4, not the sum. From the program
table's scope sums / ``batch.step`` spans; None where the executable was
compiled from a source without the scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("attn.decode",), STEP_SPAN)
