"""Device time per decode step under the ``shortconv.proj`` and
``shortconv.conv`` scopes: every short-convolution layer's two projections,
gates and taps, and its window's read and write in the per-slot state store.
Only operations that ran inside the step executable count
(``rooflines_lfm2_moe.step_scope_ms``): the window's prefills run under the
same scopes. None where the program has no such scope."""
from benchmark.rooflines_lfm2_moe import SHORTCONV_SCOPES, step_scope_ms


def read(record: dict):
    return step_scope_ms(record, SHORTCONV_SCOPES)
