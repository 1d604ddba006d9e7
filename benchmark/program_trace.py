"""The program's own spans and scopes, read out of a traced run's profile.

``trace_reduce`` keeps only the benchmark's ``bench.*`` annotations, so its
``breakdown`` can say no more than "idle inside ``bench.step``". The program
now annotates itself: ``ContinuousBatcher.step`` and its phases are
``batch.*`` host events on the trace's clock (``obs.tracing.phase``, written
with no one arming ``obs``), and the device operations of the decode step, the
adopt scatter and the split hops carry a ``jax.named_scope`` path
(``edgellm_tpu/obs/names.py`` ``SCOPE_NAMES``). This file loads the run's
``.xplane.pb`` a second time and reduces it to one table:

- per span name: how many ran in the traced window (a span cut by the window's
  edge counts by the share of it inside), total and self seconds, and the
  device-idle seconds during which it was the INNERMOST open program span (so
  a second is never counted under a span and its child; idle outside every
  ``batch.*`` span is ``outside``);
- per scope: device self seconds (a ``while`` encloses its body) of the
  operations whose innermost registered scope it is;
- per executable: runs and seconds from the "XLA Modules" line.

Means are over the cell's device planes, as in ``trace_reduce``. The table is
written to ``benchmark_out/<cell>/program_spans.json`` and printed; the
readers ``benchmark/metrics/{idle_in_sync_ms,idle_host_ms,admit_dev_ms}.py``
read it through :func:`table_for`.

A reader is handed only the run's ``record``, which holds neither the cell's
name nor the trace's path. ``run.py`` clears the cell's trace directory before
a traced run and one process runs one cell, so :func:`table_for` takes the
newest ``.xplane.pb`` under ``benchmark_out/*/trace``, refuses one written
before this module was imported (``load_cell`` imports the readers before the
run starts), and reduces it once per process however many readers ask.

``reduce_program`` is a pure function of events, checked on the recorded
events in ``benchmark/testdata/program_events_v5e.json``. A profile of a
program without these spans and scopes (the parent of the PR that added them)
reduces to a table with no ``batch.*`` rows, and the readers return nothing.

Where the scope path comes from (TPU v5e, libtpu 0.0.34, PR 24's chip runs):
an "XLA Ops" event's name is its HLO instruction's text WITHOUT its
``metadata={op_name=...}``, and the event's own stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``. The path is the
``tf_op`` stat (``jit(_prefill_impl)/while/body/closed_call/mlp/dot_general:``)
of the operation's event METADATA (``XEventMetadata.stats``), which
``jax.profiler.ProfileData`` does not hand out. :func:`op_scope_paths` reads
just that map out of the file with a few lines of protobuf wire format. The
metadata is the compiled executable's: one that came out of the persistent
compile cache names the scopes of the source it was FIRST compiled from
(named scopes are not part of the cache key), so an executable cached before
the scopes existed shows none until its HLO changes. That is why no metric in
``BENCHMARK.json`` reads the scope table: it is a table for the reader of
``program_spans.json``, not a number a PR is held to.
"""
from __future__ import annotations

import glob
import json
import os
import time
from fnmatch import fnmatchcase

from benchmark.cell import ROOT
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, WINDOW_SPAN,
                                    _clip, _overlap, _self_times, _union)

OUT_DIR = os.path.join(ROOT, "benchmark_out")
SPAN_PREFIX = "batch."
STEP_SPAN, SYNC_SPAN, ADMIT_SPAN = ("batch.step", "batch.step.sync",
                                    "batch.admit")
OUTSIDE, UNSCOPED = "outside", "(no scope)"
#: the event-metadata stat that carries an operation's scope path
SCOPE_STAT = "tf_op"
_IMPORTED_AT = time.time()
_TABLES: dict = {}


def program_scopes() -> tuple:
    """The scope names and templates the program registers, or none for a
    program older than them."""
    try:
        from edgellm_tpu.obs import names
    except ImportError:
        return ()
    return (tuple(sorted(getattr(names, "SCOPE_NAMES", ())))
            + tuple(getattr(names, "SCOPE_TEMPLATES", ())))


def innermost_scope(path: str, scopes: tuple) -> str:
    """The last segment of an operation's ``a/b/c`` scope path that is a
    registered scope, or ``""``."""
    for seg in reversed(path.split("/")):
        if any(fnmatchcase(seg, pat) for pat in scopes):
            return seg
    return ""


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_scope_paths(xplane_path: str, device_prefix: str = "/device:TPU:"
                   ) -> dict:
    """``{plane name: {operation's event name: scope path}}`` from the
    ``tf_op`` stat of every event metadata of the device planes (xplane.proto:
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5, map
    entries key=1 value=2; XEventMetadata.name=2, .stats=5; XStat.metadata_id=1,
    .str_value=5, .ref_value=7; XStatMetadata.name=2). The events themselves
    (XPlane.lines=3) are skipped unread."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry[1]] = bytes(
                    dict(_fields(entry[2])).get(2, b"")).decode()
        if not name.startswith(device_prefix):
            continue
        paths = out[name] = {}
        for entry in metas:
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            op, path = "", ""
            for f, v in _fields(meta):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == SCOPE_STAT:
                        path = (bytes(stat[5]).decode() if 5 in stat
                                else stat_names.get(stat.get(7), ""))
            if path:
                paths[op] = path
    return out


def load_program_events(xplane_path: str, scopes: tuple,
                        device_prefix: str = "/device:TPU:") -> dict:
    """``{"devices": {plane: {"ops": [[scope, start_ns, dur_ns], ...],
    "modules": [[name, start_ns, dur_ns], ...]}}, "host": [[name, start_ns,
    dur_ns], ...]}``: an operation is kept as its innermost registered scope
    (``""`` for none), host events are the program's ``batch.*`` spans and the
    benchmark's window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    paths = op_scope_paths(xplane_path, device_prefix) if scopes else {}
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = {}
            scope_of = {op: innermost_scope(path, scopes)
                        for op, path in paths.get(plane.name, {}).items()}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines["ops"] = [[scope_of.get(ev.name, ""),
                                     int(ev.start_ns), int(ev.duration_ns)]
                                    for ev in line.events]
                elif line.name == MODULES_LINE:
                    lines["modules"] = [[ev.name, int(ev.start_ns),
                                         int(ev.duration_ns)]
                                        for ev in line.events]
            if lines.get("ops"):
                devices[plane.name] = lines
        else:
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(SPAN_PREFIX)
                            or ev.name == WINDOW_SPAN)
    return {"devices": devices, "host": host}


def _span_rows(host: list, lo: int, hi: int):
    """Per span name ``{"count", "total_ns", "self_ns"}`` inside the window,
    and the disjoint intervals in which each name was the innermost open span.
    Spans nest (one scheduler thread), so a stack sweep splits every span into
    the parts its children do not cover."""
    rows, own = {}, {}
    stack = []  # [name, end, cursor]: cursor = where its uncovered part resumes

    def emit(name, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            own.setdefault(name, []).append([a, b])
            rows[name]["self_ns"] += b - a

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            emit(name, cursor, end)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    spans = sorted(((n, s, s + d) for n, s, d in host
                    if n.startswith(SPAN_PREFIX) and d > 0
                    and s < hi and s + d > lo),
                   key=lambda ev: (ev[1], -ev[2]))
    for name, s, e in spans:
        close(s)
        row = rows.setdefault(name, {"count": 0.0, "total_ns": 0,
                                     "self_ns": 0})
        inside = min(e, hi) - max(s, lo)
        row["count"] += inside / (e - s)
        row["total_ns"] += inside
        if stack:
            emit(stack[-1][0], stack[-1][2], s)
            stack[-1][2] = s
        stack.append([name, e, s])
    close(float("inf"))
    return rows, {name: _union(iv) for name, iv in own.items()}


def reduce_program(events: dict) -> dict:
    """The table described at the top, from plain events."""
    host = events["host"]
    win = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    planes = events["devices"]
    if not planes:
        raise ValueError("the trace holds no device plane with operations")
    n = len(planes)
    rows, own = _span_rows(host, lo, hi)
    idle_by = dict.fromkeys(own, 0.0)
    idle_by[OUTSIDE] = 0.0
    idle_ns, scopes, modules = 0.0, {}, {}
    for plane in planes.values():
        clipped = _clip(plane["ops"], lo, hi)
        idle, edge = [], lo
        for a, b in _union([(a, b) for _, a, b in clipped]):
            if a > edge:
                idle.append([edge, a])
            edge = b
        if hi > edge:
            idle.append([edge, hi])
        total = sum(b - a for a, b in idle)
        inside = 0
        for name, iv in own.items():
            t = _overlap(idle, iv)
            idle_by[name] += t / n
            inside += t
        idle_by[OUTSIDE] += (total - inside) / n
        idle_ns += total / n
        for scope, t in _self_times(clipped).items():
            key = scope or UNSCOPED
            scopes[key] = scopes.get(key, 0.0) + t / n
        for name, a, b in _clip(plane.get("modules", []), lo, hi):
            cnt, tot = modules.get(name, (0.0, 0.0))
            modules[name] = (cnt + 1 / n, tot + (b - a) / n)
    s = 1e-9
    return {
        "window_s": (hi - lo) * s, "idle_s": idle_ns * s, "chips": n,
        "spans": {name: {"count": r["count"], "total_s": r["total_ns"] * s,
                         "self_s": r["self_ns"] * s,
                         "idle_s": idle_by[name] * s}
                  for name, r in sorted(rows.items())},
        "idle_outside_s": idle_by[OUTSIDE] * s,
        "scopes": {k: v * s for k, v in sorted(scopes.items())},
        "modules": {k: {"runs": c, "seconds": t * s}
                    for k, (c, t) in sorted(modules.items())},
    }


def newest_xplane(out_dir: str = OUT_DIR, not_before: float = 0.0):
    """The newest ``.xplane.pb`` under ``<out_dir>/*/trace`` and the cell
    directory it lies in, or None when there is none or it is older than
    ``not_before`` (a file some earlier process left)."""
    found = glob.glob(os.path.join(out_dir, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    if os.path.getmtime(path) < not_before:
        return None
    cell_dir = path[:path.index(os.sep + "trace" + os.sep, len(out_dir))]
    return path, cell_dir


def table_for(record: dict):
    """The program table of this process's traced run, reduced on the first
    call and kept; None for an untraced run or when no profile of this
    process is found."""
    if not record.get("trace"):
        return None
    if "table" not in _TABLES:
        found = newest_xplane(not_before=_IMPORTED_AT)
        table = None
        if found is not None:
            path, cell_dir = found
            table = reduce_program(
                load_program_events(path, program_scopes()))
            table["step_module"] = record["config"]["program"]["step_module"]
            with open(os.path.join(cell_dir, "program_spans.json"), "w") as f:
                json.dump(table, f, indent=1)
            print("program spans: " + json.dumps(table), flush=True)
        _TABLES["table"] = table
    return _TABLES["table"]


def span_count(table: dict, name: str) -> float:
    return table["spans"].get(name, {}).get("count", 0.0)


def idle_ms_per_step(record: dict):
    """(device-idle ms a decode step under ``batch.step.sync``, the host
    waiting for the device; idle ms a step everywhere else, the device
    waiting for the host) of the traced window, or None without a table or a
    ``batch.step`` span in it."""
    table = table_for(record)
    steps = span_count(table, STEP_SPAN) if table else 0
    if not steps:
        return None
    in_sync = table["spans"].get(SYNC_SPAN, {}).get("idle_s", 0.0)
    return 1e3 * in_sync / steps, 1e3 * (table["idle_s"] - in_sync) / steps


def admit_dev_ms(record: dict):
    """Device ms of every executable that is not the decode step (the
    prefills, the adopt scatters with the pool's layout copies, the per-slot
    key executables) per ``batch.admit`` span of the traced window."""
    table = table_for(record)
    admits = span_count(table, ADMIT_SPAN) if table else 0
    if not admits:
        return None
    other = sum(m["seconds"] for name, m in table["modules"].items()
                if table["step_module"] not in name)
    return 1e3 * other / admits


def ms_per(record: dict, key: str, per: str):
    """A host clock of the batcher's ``report()`` over the window, in ms per
    unit of its counter ``per`` (``steps``, ``admitted``); None where the
    program has no such clock (a program older than it) or nothing was
    counted."""
    r0, r1 = record["report0"], record["report1"]
    if key not in r0 or key not in r1 or not r1[per] - r0[per]:
        return None
    return 1e3 * (r1[key] - r0[key]) / (r1[per] - r0[per])
