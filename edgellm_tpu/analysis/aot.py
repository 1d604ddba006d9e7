"""Shared AOT compile-and-measure driver.

Both the window-batch preflight (``tools/wb_preflight.py``) and the
config-lattice verifier (``lint/lattice.py``) need the same primitive:
lower a jitted entry point, compile it WITHOUT allocating device memory,
and read XLA's ``memory_analysis()`` — argument, output and temp bytes —
plus whether the compiler itself proved the program over-HBM. This module
is that primitive, extracted so the two callers cannot drift.

Nothing here runs model math: ``.lower()`` traces, ``.compile()`` builds
the executable, and ``memory_analysis()`` is a static read — "compile
first, run only what fits" costs no device memory (see the wb_preflight
module docstring).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional


def is_over_hbm(e: BaseException) -> bool:
    """True when a compile failed because the program provably exceeds HBM
    ('Program hbm requirement ...G' dump) — extends the runtime-OOM
    vocabulary of :func:`edgellm_tpu.eval.harness.is_oom_error` to compile
    time."""
    from ..eval.harness import is_oom_error

    msg = str(e)
    return ("hbm requirement" in msg or "allocations in hbm" in msg
            or is_oom_error(e))


@dataclasses.dataclass(frozen=True)
class AOTCost:
    """Static memory footprint of one compiled executable, in bytes."""

    argument_bytes: int
    output_bytes: int
    temp_bytes: int

    @property
    def total(self) -> int:
        """argument + output + temp — the peak one call keeps live."""
        return self.argument_bytes + self.output_bytes + self.temp_bytes

    def as_dict(self) -> dict:
        return {"argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "total_bytes": self.total}


def lowered_cost(lowered: Any) -> Optional[AOTCost]:
    """Compile a ``.lower()`` result and read its memory analysis.

    Returns ``None`` when the backend compiler rejects the program as
    provably over-HBM — a doesn't-fit verdict reached with zero device
    allocation. Any other compile failure propagates: a program that fails
    to compile for a non-memory reason is a bug, not a budget miss."""
    try:
        compiled = lowered.compile()
    except Exception as e:
        if is_over_hbm(e):
            return None
        raise
    ma = compiled.memory_analysis()
    return AOTCost(argument_bytes=int(ma.argument_size_in_bytes),
                   output_bytes=int(ma.output_size_in_bytes),
                   temp_bytes=int(ma.temp_size_in_bytes))


def aot_cost(jitted_fn: Callable, *args: Any, **kwargs: Any) -> Optional[AOTCost]:
    """Lower + compile ``jitted_fn(*args)`` and return its
    :class:`AOTCost` (``None`` when provably over-HBM)."""
    return lowered_cost(jitted_fn.lower(*args, **kwargs))


def call_total_bytes(lowered: Any) -> Optional[int]:
    """argument+output+temp bytes of one lowered call, or ``None`` when the
    compiler rejects it as over-HBM — the wb_preflight convention."""
    cost = lowered_cost(lowered)
    return None if cost is None else cost.total
