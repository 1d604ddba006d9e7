"""Device time per decode step under the ``mlp`` scope of a stack walked by
layer kinds: the leading dense layers' SwiGLU and the norm after it (the
traced window's prefills run under the same scope and are in the sum). From
the program table's scope sums / ``batch.step`` spans; None where the program
has no such scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("mlp",), STEP_SPAN)
