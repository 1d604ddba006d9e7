"""The arithmetic from stamps to latencies (a copy, on a real clock, of what
``bench.py``'s ``_open_loop_summary`` did on a virtual one).

A record's ``requests`` carry ``due`` and one stamp per served token, all in
seconds from the window's zero; the window is ``(t0, t1]``.
"""
from __future__ import annotations

import numpy as np


def ttfts_ms(record: dict) -> list:
    """Due time to first token, for every request due in the window that got
    one."""
    return [(r.stamps[0] - r.due) * 1e3 for r in record["requests"]
            if r.counted and r.stamps and r.due >= 0.0]


def gaps_ms(record: dict) -> list:
    """Time between consecutive tokens of a stream, for every token stamped
    in the window that has a predecessor."""
    t0, t1 = record["t0"], record["t1"]
    out = []
    for r in record["requests"] + record["preload"]:
        s = r.stamps
        out.extend((s[i] - s[i - 1]) * 1e3 for i in range(1, len(s))
                   if t0 < s[i] <= t1)
    return out


def tokens_in_window(record: dict) -> int:
    t0, t1 = record["t0"], record["t1"]
    return sum(1 for r in record["requests"] + record["preload"]
               for t in r.stamps if t0 < t <= t1)


def late_ms(record: dict) -> list:
    """How late the generator submitted each request due in the window."""
    return [(r.submit_t - r.due) * 1e3 for r in record["requests"]
            if r.counted and r.submit_t is not None and r.due >= 0.0]


def mean(xs: list):
    return float(np.mean(xs)) if xs else None


def pct(xs: list, q: float):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def delta(record: dict, key: str) -> float:
    return record["report1"][key] - record["report0"][key]
