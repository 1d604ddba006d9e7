"""Host-side span tracing: nested spans on a monotonic clock, exported as
Chrome trace-event JSON (load the file at https://ui.perfetto.dev), bridged
into ``jax.profiler`` so host spans line up with the device timeline.

Design points:

- **Thread-safe, nesting-aware.** Each thread keeps its own open-span stack
  (``threading.local``); finished spans append to one locked list. Chrome's
  viewer infers nesting from ``ts``/``dur`` on the same ``tid``, which the
  per-thread stack discipline guarantees.
- **Disabled is near-free.** :func:`span` hands back a shared
  ``nullcontext`` when tracing is off — no allocation, no clock read, no
  lock. The serve loops call it unconditionally.
- **Device bridge.** When tracing is on and jax is importable, each span
  also enters ``jax.profiler.TraceAnnotation``, so a
  ``jax.profiler.trace`` capture (see :func:`trace_capture`) shows host
  spans on the TensorBoard/Perfetto device timeline. The bridge degrades
  silently when jax or its profiler is unavailable — tracing must work in
  a bare-stdlib process.
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
    "Span", "Tracer", "configure", "get_tracer", "span", "trace_capture",
    "tracing_enabled",
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


def _jax_annotation(name: str) -> contextlib.AbstractContextManager:
    try:  # bridge is best-effort: bare-stdlib processes still trace
        import jax.profiler as _prof
        return _prof.TraceAnnotation(name)
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

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Optional[Span]]:
        if not self.enabled:
            yield None
            return
        stack = getattr(self._stack, "open", None)
        if stack is None:
            stack = self._stack.open = []
        labels = _context.current_labels()
        if labels:  # ambient request labels; explicit span kwargs win
            labels.update(attrs)
            attrs = labels
        s = Span(name, self._now_us(), threading.get_ident(), attrs)
        stack.append(s)
        try:
            with _jax_annotation(name):
                yield s
        finally:
            s.dur_us = self._now_us() - s.ts_us
            stack.pop()
            with self._lock:
                self._spans.append(s)
            sink = self._sink
            if sink is not None:
                try:
                    sink(s)
                except Exception:  # pragma: no cover - defensive
                    pass

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
