"""Device time per decode step under the ``ssm.proj`` scope: the Mamba-2
layers' in and out projections (the traced window's prefills run under the
same scope and are in the sum)."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("ssm.proj",), STEP_SPAN)
