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
  :class:`Span`, nested under its parent by the same per-thread stack.

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
- **trace_capture** wraps ``jax.profiler.trace`` (the XLA-level profiler
  dump) and subsumes the old ``utils.profiling.trace`` stub, which now
  delegates here.
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
    "Span", "Tracer", "compile_totals", "configure", "get_tracer", "phase",
    "span", "trace_capture", "tracing_enabled",
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

    ``attrs`` given here reach the profiler capture (as the event's stats)
    and the recorded :class:`Span`; :meth:`set` adds what is known only at
    the end (how many were admitted) to the recorded Span alone — a
    ``TraceAnnotation``'s attributes are fixed when it is entered.
    """

    __slots__ = ("name", "attrs", "start", "end", "_acc", "_key", "_after",
                 "_ann", "_span")

    def __init__(self, name: str, acc: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None, after: Optional["phase"] = None,
                 **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs
        self.end: Optional[float] = None
        self._acc = acc
        self._key = key
        self._after = after
        self._span: Optional[Span] = None

    def __enter__(self) -> "phase":
        after = self._after
        if after is None:
            self.start = time.monotonic()
        else:
            self.start = after.start if after.end is None else after.end
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
        self.end = time.monotonic()
        if self._acc is not None:
            self._acc[self._key] = (self._acc.get(self._key, 0.0)
                                    + self.end - self.start)
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
