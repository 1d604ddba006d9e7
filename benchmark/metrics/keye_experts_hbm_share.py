"""The least time the step's routers and held experts need INSIDE their own
scopes (every layer's router and experts once, less what the chip's vector
memory can hold ahead of a layer's expert products:
``rooflines_keye_vl2.experts_in_scope_bytes``; at the HBM peak) as a share of
the ``moe.route`` and ``moe.experts`` scopes' device time inside the step
executable (``keye_experts_dev_ms``). Written as the six older families'
readers are, every byte over the scopes' time, it read 125.5% (1.815 GB in
1.77 ms; my chip run, PR 47): the compiled step copies each layer's
``w_gate``, a third of the layer's expert bytes, into vector memory while the
sparse attention ahead of it runs, so a third of the bytes cross HBM outside
the scopes. A floor: it cannot pass 100%. None where the program has no such
scope."""
from benchmark.rooflines_granitemoehybrid import hbm_share
from benchmark.rooflines_keye_vl2 import MOE_SCOPES, experts_in_scope_bytes
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    ms = step_scope_ms(record, MOE_SCOPES)
    if ms is None:
        return None
    return hbm_share(record, experts_in_scope_bytes(
        record["config"], record["device_kind"]), ms)
