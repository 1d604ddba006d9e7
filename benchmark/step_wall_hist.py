"""The window's part of the batcher's step-wall table, and what its readers
take from it.

``ContinuousBatcher.report()`` holds ``step_wall_hist``: one row a bucket of
``step_wall_s``, ``[steps, admit_s, grow_s, build_s, launch_s, sync_s,
commit_s]`` of the launched steps whose wall fell in it, additive entry by
entry, and ``step_wall_edges_s``, the buckets' edges (row ``i`` is
``[edge i-1, edge i)``, row 0 what lies under the first edge, the last row
what lies at or over the last). A row's wall is its six phase columns summed:
they tile ``step_wall_s``. A program without the table (one older than it)
gives every function here nothing, and its readers return None.
"""
from __future__ import annotations


def window(record: dict):
    """``(rows, edges)``: the table differenced over the window; None where
    either report lacks it or the window launched no step."""
    r0, r1 = record["report0"], record["report1"]
    if "step_wall_hist" not in r0 or "step_wall_hist" not in r1:
        return None
    rows = [[b - a for a, b in zip(row0, row1)]
            for row0, row1 in zip(r0["step_wall_hist"], r1["step_wall_hist"])]
    if not steps(rows):
        return None
    return rows, r1["step_wall_edges_s"]


def steps(rows: list) -> float:
    return sum(row[0] for row in rows)


def wall_s(rows: list) -> float:
    return sum(sum(row[1:]) for row in rows)


def percentile_s(rows: list, edges: list, q: float) -> float:
    """The wall under which the share ``q`` of the steps lies, interpolated
    inside the bucket it falls in (an end row has one edge only and gives its
    steps' mean wall)."""
    want, below = q * steps(rows), 0.0
    for i, row in enumerate(rows):
        if row[0] and below + row[0] >= want:
            if i == 0 or i == len(edges):
                return sum(row[1:]) / row[0]
            lo, hi = edges[i - 1], edges[i]
            return lo + (hi - lo) * (want - below) / row[0]
        below += row[0]
    raise ValueError("a table without steps has no percentile")


def tail(rows: list, share: float) -> tuple:
    """``(steps, wall seconds, admit seconds)`` of the slowest ``share`` of
    the steps: whole rows from the top down, the row the boundary falls in
    pro rata."""
    want = share * steps(rows)
    left, wall, admit = want, 0.0, 0.0
    for row in reversed(rows):
        if left <= 0:
            break
        if row[0]:
            part = min(row[0], left) / row[0]
            wall += part * sum(row[1:])
            admit += part * row[1]
            left -= part * row[0]
    return want, wall, admit
