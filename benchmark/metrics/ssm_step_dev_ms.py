"""Device time per decode step under the ``ssm.step`` scope: the nine Mamba-2
layers' window update, recurrence and gated norm (the per-slot state read and
written). From the program table's scope sums / ``batch.step`` spans."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("ssm.step",), STEP_SPAN)
