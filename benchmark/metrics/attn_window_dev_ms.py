"""Device time per decode step under the ``attn.window`` scope: every sliding
layer's q/k/v projections and rotation, the gather of each slot's ring of
pages out of the window pool and the attend over it (the row's write is
``paged_kv.write``'s). From the program table's scope sums / ``batch.step``
spans; None where the program has no such scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("attn.window",), STEP_SPAN)
