"""Device time per admission under the ``ssm.scan`` scope: a prefill's
convolution and chunked scan over the Mamba-2 layers, per ``batch.admit``
span of the traced window."""
from benchmark.program_trace import ADMIT_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("ssm.scan",), ADMIT_SPAN)
