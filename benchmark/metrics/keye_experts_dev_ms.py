"""Device time per decode step under the ``moe.route`` and ``moe.experts``
scopes INSIDE the step executable (``rooflines_lfm2_moe.step_scope_ms``): the
router and the held experts of every layer over the step's 32 tokens, without
the window's prefills, which run under the same scopes and which
``moe_experts_dev_ms`` (per ``batch.step`` span) carries. It is the
denominator of ``keye_experts_hbm_share``. None where the program has no such
scope."""
from benchmark.rooflines_keye_vl2 import MOE_SCOPES
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    return step_scope_ms(record, MOE_SCOPES)
