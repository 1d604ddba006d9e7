"""The least time the step's routers, held experts and shared experts need
INSIDE their own scopes (every expert layer's once, less what the chip's
vector memory can hold ahead of a layer's expert products:
``rooflines_dots3_note.experts_in_scope_bytes``; at the HBM peak) as a share
of the ``moe.route``, ``moe.experts`` and ``moe.shared`` scopes' device time
inside the step executable. A floor: it cannot pass 100%. None where the
program has no such scope."""
from benchmark.rooflines_dots3_note import MOE_SCOPES, experts_in_scope_bytes
from benchmark.rooflines_granitemoehybrid import hbm_share
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    ms = step_scope_ms(record, MOE_SCOPES)
    if ms is None:
        return None
    return hbm_share(record, experts_in_scope_bytes(
        record["config"], record["device_kind"]), ms)
