"""Device time per admission under the ``moe.experts.grouped`` scope: the
three grouped products gate / up / down of a prefill's expert layers (one
tiled kernel on a TPU), per ``batch.admit`` span of the traced window. A
program without the scope reads nothing."""
from benchmark.program_trace import ADMIT_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("moe.experts.grouped",), ADMIT_SPAN)
