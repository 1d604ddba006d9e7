"""Host-side span tracing: nested spans on a monotonic clock, exported as
Chrome trace-event JSON (load the file at https://ui.perfetto.dev), bridged
into ``jax.profiler`` so host spans line up with the device timeline.

Two entry points, for two kinds of call site:

- :func:`span` — a span that matters only to someone who armed the tracer
  (``obs.enable()``). Disabled it hands back ONE shared ``nullcontext``: no
  allocation, no clock read, no lock, and nothing reaches a profiler
  capture. Enabled it records a :class:`Span` and enters a
  ``jax.profiler.TraceAnnotation``.
- :class:`phase` — a phase of a hot loop that the program itself keeps a
  clock on (``ContinuousBatcher.step``'s admit/grow/build/launch/sync/
  commit). It ALWAYS enters a ``jax.profiler.TraceAnnotation`` — that object
  does nothing unless a profiler session is recording, so any capture
  (:func:`trace_capture`, a bare ``jax.profiler.start_trace``, an
  operator's) shows the program's phases on the device trace's clock with
  nobody arming ``obs`` — and ALWAYS adds its duration to the caller's
  accumulator. What that costs with the tracer disabled and no capture
  running: a ``time.monotonic`` reading or two and one annotation object,
  about two microseconds a phase. With the tracer enabled it also records the
  :class:`Span`, nested under its parent by the same per-thread stack. Where
  its caller names a second key (``cpu_key``) it keeps the calling thread's
  CPU seconds (``time.thread_time``) over the same interval beside the wall:
  what the thread computed, as against what it waited or was descheduled.

Design points:

- **Thread-safe, nesting-aware.** Each thread keeps its own open-span stack
  (``threading.local``); finished spans append to one locked list. Chrome's
  viewer infers nesting from ``ts``/``dur`` on the same ``tid``, which the
  per-thread stack discipline guarantees.
- **The device bridge degrades silently** when jax or its profiler is
  unavailable — tracing must work in a bare-stdlib process.
- **Compile counter.** :func:`compile_totals` is the process's running count
  and seconds of JAX backend compiles, fed by ONE ``jax.monitoring``
  listener registered on first use; callers difference it around their own
  work (the batcher's ``compiles``/``compile_s``).
- **What the thread and the host paid.** :func:`thread_usage` is the calling
  thread's ``getrusage`` (CPU seconds user and system, context switches,
  page faults) and :func:`host_counters` the machine's own monotone counters
  (the container's CPU throttling, the pressure totals): running totals like
  ``compile_totals``, which a caller differences around its own work.
- **trace_capture** wraps ``jax.profiler.trace`` (the XLA-level profiler
  dump).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from . import context as _context
from ..utils.concurrency import guarded_by

__all__ = [
    "THREAD_USAGE", "Span", "Tracer", "compile_totals", "configure",
    "get_tracer", "host_counters", "phase", "span", "thread_usage",
    "trace_capture", "tracing_enabled",
]


class Span:
    """One finished (or open) span: name, µs timestamps, attributes."""

    __slots__ = ("name", "ts_us", "dur_us", "tid", "args")

    def __init__(self, name: str, ts_us: float, tid: int,
                 args: Optional[Dict[str, Any]] = None) -> None:
        self.name = name
        self.ts_us = ts_us
        self.dur_us: float = 0.0
        self.tid = tid
        self.args: Dict[str, Any] = dict(args) if args else {}

    def to_event(self) -> Dict[str, Any]:
        """Chrome trace-event 'X' (complete) event."""
        ev: Dict[str, Any] = {"name": self.name, "ph": "X",
                              "ts": self.ts_us, "dur": self.dur_us,
                              "pid": os.getpid(), "tid": self.tid}
        if self.args:
            ev["args"] = {k: (v if isinstance(v, (int, float, str, bool))
                              else repr(v)) for k, v in self.args.items()}
        return ev


def _jax_annotation(name: str, **attrs: Any
                    ) -> contextlib.AbstractContextManager:
    try:  # bridge is best-effort: bare-stdlib processes still trace
        import jax.profiler as _prof
        return _prof.TraceAnnotation(name, **attrs)
    except Exception:
        return contextlib.nullcontext()


@guarded_by("_lock", fields=["_spans"])
class Tracer:
    """Collects spans process-wide; one instance behind :func:`get_tracer`."""

    def __init__(self, enabled: bool = False) -> None:
        self.enabled = enabled
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        self._stack = threading.local()
        self._t0 = time.monotonic()
        #: closed-span hook (the flight recorder's ring feed); exceptions
        #: are swallowed — observation must never take down serving
        self._sink: Optional[Callable[[Span], None]] = None

    def _now_us(self) -> float:
        return (time.monotonic() - self._t0) * 1e6

    def set_sink(self, sink: Optional[Callable[[Span], None]]) -> None:
        self._sink = sink

    def _open(self, name: str, attrs: Dict[str, Any]) -> Span:
        """Push a new open span on this thread's stack (tracer enabled)."""
        stack = getattr(self._stack, "open", None)
        if stack is None:
            stack = self._stack.open = []
        labels = _context.current_labels()
        if labels:  # ambient request labels; explicit span kwargs win
            labels.update(attrs)
            attrs = labels
        s = Span(name, self._now_us(), threading.get_ident(), attrs)
        stack.append(s)
        return s

    def _close(self, s: Span) -> None:
        s.dur_us = self._now_us() - s.ts_us
        self._stack.open.pop()
        with self._lock:
            self._spans.append(s)
        sink = self._sink
        if sink is not None:
            try:
                sink(s)
            except Exception:  # pragma: no cover - defensive
                pass

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Optional[Span]]:
        if not self.enabled:
            yield None
            return
        s = self._open(name, attrs)
        try:
            with _jax_annotation(name):
                yield s
        finally:
            self._close(s)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Perfetto-loadable trace object."""
        with self._lock:
            events = [s.to_event() for s in self._spans]
        events.sort(key=lambda e: (e["tid"], e["ts"]))
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        """Write the Chrome trace-event JSON atomically (.part → rename)."""
        tmp = path + ".part"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)
        os.replace(tmp, path)


_TRACER = Tracer()
_NULL = contextlib.nullcontext()  # shared: span() when disabled allocates nothing


def get_tracer() -> Tracer:
    return _TRACER


def configure(*, enabled: bool) -> None:
    _TRACER.enabled = enabled


def tracing_enabled() -> bool:
    return _TRACER.enabled


def span(name: str, **attrs: Any) -> contextlib.AbstractContextManager:
    """Module-level span on the global tracer; the form call sites use:

        with obs.span("decode.checkpoint", step=k):
            ...
    """
    if not _TRACER.enabled:
        return _NULL
    return _TRACER.span(name, **attrs)


class phase:
    """One phase of a hot loop, as a context manager::

        with phase("batch.step", acc, "step_wall_s", step=n) as whole:
            with phase("batch.step.admit", acc, "admit_s", after=whole) as ph:
                ...
            with phase("batch.step.launch", acc, "launch_s", after=ph):
                ...

    Always on the profiler's clock and always on the caller's: see the module
    docstring. ``acc`` is the caller's own ``{key: seconds}`` dict, which
    gains the duration under ``key`` (the caller takes its lock once, when
    it folds the dict into its counters, not once a phase); without ``acc``
    only the span is kept.

    ``after`` chains the caller's clock: this phase's seconds count from
    where ``after`` stopped — or, while ``after`` is still open (the
    enclosing phase), from where it started — so a run of phases tiles its
    parent with one clock reading per edge and the statements between two
    phases belong to the later one. The spans themselves start where they
    are entered.

    ``cpu_key`` names a second key of ``acc`` that gains the calling
    thread's CPU seconds (``time.thread_time``) between the same two edges:
    read inside the wall's readings where the phase reads its own, taken
    from ``after`` where the wall is (``cpu_start`` / ``cpu_end``, beside
    ``start`` / ``end``). A clock reading or two more where it is named,
    none where it is not.

    ``attrs`` given here reach the profiler capture (as the event's stats)
    and the recorded :class:`Span`; :meth:`set` adds what is known only at
    the end (how many were admitted) to the recorded Span alone — a
    ``TraceAnnotation``'s attributes are fixed when it is entered.
    """

    __slots__ = ("name", "attrs", "start", "end", "cpu_start", "cpu_end",
                 "_acc", "_key", "_cpu_key", "_after", "_ann", "_span")

    def __init__(self, name: str, acc: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None, after: Optional["phase"] = None,
                 cpu_key: Optional[str] = None, **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs
        self.end: Optional[float] = None
        self.cpu_start: Optional[float] = None
        self.cpu_end: Optional[float] = None
        self._acc = acc
        self._key = key
        self._cpu_key = cpu_key
        self._after = after
        self._span: Optional[Span] = None

    def __enter__(self) -> "phase":
        after = self._after
        if after is None:
            self.start = time.monotonic()
        else:
            self.start = after.start if after.end is None else after.end
        if self._cpu_key is not None:
            # the thread's clock at the edge the wall counts from: the
            # reading ``after`` took there, where it took one
            if after is not None:
                self.cpu_start = (after.cpu_start if after.end is None
                                  else after.cpu_end)
            if self.cpu_start is None:
                self.cpu_start = time.thread_time()
        self._ann = _jax_annotation(self.name, **self.attrs)
        self._ann.__enter__()
        if _TRACER.enabled:
            self._span = _TRACER._open(self.name, self.attrs)
        return self

    def set(self, **attrs: Any) -> None:
        if self._span is not None:
            self._span.args.update(attrs)

    def __exit__(self, *exc: Any) -> bool:
        if self._span is not None:
            _TRACER._close(self._span)
        self._ann.__exit__(*exc)
        if self._cpu_key is not None:
            self.cpu_end = time.thread_time()
        self.end = time.monotonic()
        acc = self._acc
        if acc is not None:
            acc[self._key] = acc.get(self._key, 0.0) + self.end - self.start
            if self._cpu_key is not None:
                acc[self._cpu_key] = (acc.get(self._cpu_key, 0.0)
                                      + self.cpu_end - self.cpu_start)
        return False


_COMPILE_EVENT_SUFFIX = "backend_compile_duration"
_compiles = [0, 0.0]     # count, seconds; written by the one listener below
_compile_listener_on = False
_compile_lock = threading.Lock()


def _on_compile_event(event: str, duration: float, **_: Any) -> None:
    if event.endswith(_COMPILE_EVENT_SUFFIX):
        with _compile_lock:
            _compiles[0] += 1
            _compiles[1] += duration


def compile_totals() -> tuple:
    """(count, seconds) of JAX backend compiles in this process since the
    first call — every executable, whichever jit it belongs to, so a compile
    per new prompt length shows where a per-function cache-size delta is
    blind. The first call registers the process's one ``jax.monitoring``
    listener; callers difference two readings around their own work."""
    global _compile_listener_on
    claimed = False
    with _compile_lock:
        if not _compile_listener_on:
            _compile_listener_on = claimed = True
        totals = (_compiles[0], _compiles[1])
    if claimed:  # registered outside the lock: one thread wins the claim
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
    return totals


try:
    import resource as _resource
    _RUSAGE_THREAD = _resource.RUSAGE_THREAD
except (ImportError, AttributeError):  # no per-thread accounting here
    _resource = _RUSAGE_THREAD = None


#: what :func:`thread_usage` returns, field by field
THREAD_USAGE = ("cpu_user_s", "cpu_sys_s", "nvcsw", "nivcsw", "minflt",
                "majflt")


def thread_usage() -> Optional[tuple]:
    """The calling thread's running totals since it started, or None where
    the platform keeps none a thread (``RUSAGE_THREAD``: Linux): (user CPU
    seconds, system CPU seconds, voluntary context switches: it blocked;
    involuntary ones: it was preempted; minor page faults; major ones),
    named by :data:`THREAD_USAGE`. One system call; a caller differences
    two readings around its own work."""
    if _RUSAGE_THREAD is None:
        return None
    ru = _resource.getrusage(_RUSAGE_THREAD)
    return (ru.ru_utime, ru.ru_stime, ru.ru_nvcsw, ru.ru_nivcsw,
            ru.ru_minflt, ru.ru_majflt)


#: where :func:`host_counters` reads: the container's ``cpu.stat`` (cgroup
#: v2, then v1: the first that opens) and the directory of pressure files
HOST_COUNTER_PATHS = {
    "cpu_stat": ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"),
    "pressure": "/proc/pressure",
}
_PRESSURES = ("cpu", "memory", "io")


def _read_text(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def host_counters() -> Dict[str, Optional[float]]:
    """The machine's own monotone counters, each None where its file is
    absent, unreadable or lacks the line: ``nr_throttled`` and
    ``cpu_throttled_s``, the periods in which the container ran into its CPU
    quota and the seconds its threads were held off for it (``cpu.stat``:
    cgroup v2 ``throttled_usec``, v1 ``throttled_time`` in ns), and
    ``pressure_cpu_s`` / ``pressure_memory_s`` / ``pressure_io_s``, the
    seconds in which some task of the machine waited for that resource (the
    ``some`` line's ``total`` of ``/proc/pressure/<resource>``, in us).
    Running totals of the kernel's, so a caller differences two readings;
    four small files, read where a caller asks and never on a hot path."""
    out: Dict[str, Optional[float]] = dict.fromkeys(
        ("nr_throttled", "cpu_throttled_s",
         *(f"pressure_{r}_s" for r in _PRESSURES)))
    for path in HOST_COUNTER_PATHS["cpu_stat"]:
        text = _read_text(path)
        if text is None:
            continue
        fields = dict(pair for pair in map(str.split, text.splitlines())
                      if len(pair) == 2)
        try:
            if "nr_throttled" in fields:
                out["nr_throttled"] = int(fields["nr_throttled"])
            if "throttled_usec" in fields:
                out["cpu_throttled_s"] = int(fields["throttled_usec"]) * 1e-6
            elif "throttled_time" in fields:
                out["cpu_throttled_s"] = int(fields["throttled_time"]) * 1e-9
        except ValueError:
            pass
        break
    for res in _PRESSURES:
        text = _read_text(os.path.join(HOST_COUNTER_PATHS["pressure"], res))
        for line in (text or "").splitlines():
            if line.startswith("some "):
                total = line.rpartition("total=")[2]
                if total.isdigit():
                    out[f"pressure_{res}_s"] = int(total) * 1e-6
    return out


@contextlib.contextmanager
def trace_capture(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler.trace`` XLA profile of the enclosed block to
    ``log_dir`` (None → no-op). A capture that was asked for and cannot start
    (double capture, missing backend support) raises: the per-layer metrics
    are read from these traces, so a run that silently carried on without
    one would report a layer as measured when nothing was recorded."""
    if not log_dir:
        yield
        return
    import jax.profiler

    with jax.profiler.trace(log_dir):
        yield
