"""Device time per decode step under ``moe.route`` + ``moe.experts`` +
``moe.shared``: the router, the held experts and the shared expert of every
layer. The traced window's prefills run under the same scopes and their
routing, sorting and shared expert are in the sum; their grouped products are
``ragged-dot`` custom calls, which carry no scope path on this libtpu, and are
not (PERF.md section 5)."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import MOE_SCOPES, scope_ms


def read(record: dict):
    return scope_ms(record, MOE_SCOPES, STEP_SPAN)
