"""From a profiler trace to numbers: device busy time, time per executable,
self time per operation, and each idle gap attributed to what the host was
doing. The yardstick's own: every PR's trace is reduced by this code.

Two steps. ``load_events`` turns an ``.xplane.pb`` into plain events (the only
part that needs JAX); ``reduce_events`` does the arithmetic and is checked on
the recorded events in ``benchmark/testdata/``.

Events are ``{"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
"modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``. A device
plane's "XLA Ops" line holds one event per executed operation (a ``while``
encloses its body's operations, so time per operation is *self* time); its
"XLA Modules" line one event per run of a compiled executable. Host events are
the benchmark's own ``bench.*`` annotations, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])?[^ ]* ?([\w\-]+)\(")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO instruction; keep its name,
    its (first) result shape and its opcode: ``fusion.222 bf16[393216,2,64]
    fusion``."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return " ".join(x for x in m.groups() if x)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(xplane_path: str, device_prefix: str = "/device:TPU:") -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    key = "ops" if line.name == OPS_LINE else "modules"
                    lines[key] = [[short_name(ev.name), int(ev.start_ns),
                                   int(ev.duration_ns)] for ev in line.events]
            if lines.get("ops"):
                devices[plane.name] = lines
        else:
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events: list, lo: int, hi: int) -> list:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _self_times(events: list) -> dict:
    """Self time per operation name on one line: an event's duration minus
    what the events it encloses cover."""
    total: dict = {}
    stack = []  # [name, end, child_time, start]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            total[name] = total.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][2] += end - start

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        close(s)
        stack.append([name, e, 0, s])
    close(float("inf"))
    return total


def _overlap(a: list, b: list) -> int:
    """Total overlap of two sorted merged interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce_events(events: dict, span_names=("bench.step", "bench.submit")
                  ) -> dict:
    """Busy and idle seconds, per-executable and per-operation seconds, and
    idle seconds by the host span that was open. The window is the
    ``bench.window`` host span; device events are clipped to it. Means are
    over the device planes."""
    host = events["host"]
    win = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    planes = events["devices"]
    if not planes:
        raise ValueError("the trace holds no device plane with operations")
    n = len(planes)
    busy_ns, ops, modules, gaps = 0.0, {}, {}, {}
    spans = {name: _union([(s, s + d) for nm, s, d in host if nm == name])
             for name in span_names}
    for plane in planes.values():
        clipped = _clip(plane["ops"], lo, hi)
        busy = _union([(a, b) for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in busy) / n
        for name, t in _self_times(clipped).items():
            ops[name] = ops.get(name, 0.0) + t / n
        for name, a, b in _clip(plane.get("modules", []), lo, hi):
            cnt, tot = modules.get(name, (0, 0.0))
            modules[name] = (cnt + 1 / n, tot + (b - a) / n)
        idle, edge = [], lo
        for a, b in busy:
            if a > edge:
                idle.append([edge, a])
            edge = b
        if hi > edge:
            idle.append([edge, hi])
        inside = 0
        for name, iv in spans.items():
            t = _overlap(idle, iv)
            gaps["inside_" + name] = gaps.get("inside_" + name, 0.0) + t / n
            inside += t
        total_idle = sum(b - a for a, b in idle)
        gaps["between_spans"] = (gaps.get("between_spans", 0.0)
                                 + (total_idle - inside) / n)
    s = 1e-9
    return {
        "window_s": (hi - lo) * s, "busy_s": busy_ns * s, "chips": n,
        "ops": {k: v * s for k, v in ops.items()},
        "modules": {k: {"runs": c, "seconds": t * s}
                    for k, (c, t) in modules.items()},
        "idle_gaps": {k: v * s for k, v in gaps.items()},
    }


def step_runs_seconds(record: dict):
    """(runs, total device seconds) of the decode step's executable in a run's
    reduced trace, or None without a trace or a run of it. The configuration
    file names the executable (``program.step_module``)."""
    tr = record.get("trace")
    if not tr:
        return None
    key = record["config"]["program"]["step_module"]
    hits = [m for name, m in tr["modules"].items() if key in name]
    runs = sum(m["runs"] for m in hits)
    return (runs, sum(m["seconds"] for m in hits)) if runs else None


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and the idle gaps by what the host was doing."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
