"""The least time the expert layers' routers, selection biases and held
experts need at the HBM peak, read once
(``rooflines_lfm2_moe.experts_step_bytes``), as a share of the ``moe.route``
and ``moe.experts`` scopes' device time inside the step executable (the
window's prefills, which run under the same scopes, are not in it). None
where the program has no such scope."""
from benchmark.rooflines_granitemoehybrid import hbm_share
from benchmark.rooflines_lfm2_moe import (MOE_SCOPES, experts_step_bytes,
                                          step_scope_ms)


def read(record: dict):
    ms = step_scope_ms(record, MOE_SCOPES)
    if ms is None:
        return None
    return hbm_share(record, experts_step_bytes(record["config"]), ms)
