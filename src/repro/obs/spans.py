"""Span tracing: thread-aware begin/end intervals over the training
pipeline, with an optional ``jax.profiler.TraceAnnotation`` bridge.

A :class:`Span` is a context manager handed out by ``Telemetry.span``.
On exit it reports one completed record — name, wall-clock interval
(relative to the stream's t0, monotonic clock), thread id/name, optional
step and attributes — to the recorder (the Telemetry object), which fans
it out to the JSONL and Chrome-trace sinks.  Emitting only *completed*
spans keeps every line a balanced begin/end pair by construction; the
tracer still keeps a per-thread stack of open spans so shutdown can
assert nothing was left dangling.

Step inheritance: a span opened without ``step=`` takes the step of the
innermost span open on its thread (None if there is none), so a leaf
inside ``spec_build(step=i)`` carries ``i`` without the code it wraps
knowing the step.  A span may also set ``step`` itself before it exits
(``prefetch_get`` learns its batch's step only when the batch arrives).

The jax bridge wraps the same interval in a ``TraceAnnotation`` so the
span shows up inside an XLA profiler trace (``jax.profiler.trace``)
aligned with device activity; it degrades to a no-op when jax (or the
profiler API) is unavailable.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

_TRACE_ANNOTATION = None
_TRACE_ANNOTATION_TRIED = False


def _trace_annotation_cls():
    """``jax.profiler.TraceAnnotation`` if importable, else None — resolved
    once, lazily, so importing repro.obs never pulls in jax."""
    global _TRACE_ANNOTATION, _TRACE_ANNOTATION_TRIED
    if not _TRACE_ANNOTATION_TRIED:
        _TRACE_ANNOTATION_TRIED = True
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:
            _TRACE_ANNOTATION = None
    return _TRACE_ANNOTATION


class Span:
    """One begin/end interval.  Re-entrant use of a single instance is not
    supported — ``Telemetry.span`` constructs a fresh one per ``with``."""

    __slots__ = ("name", "step", "attrs", "_recorder", "_jax", "_t0_ns",
                 "_annotation", "_tracker")

    def __init__(self, recorder: Callable, name: str,
                 step: Optional[int] = None, jax_annotation: bool = False,
                 tracker: Optional["OpenSpanTracker"] = None, **attrs):
        self.name = name
        self.step = step
        self.attrs = attrs
        self._recorder = recorder
        self._jax = jax_annotation
        self._t0_ns = 0
        self._annotation = None
        self._tracker = tracker

    def __enter__(self) -> "Span":
        if self._tracker is not None:
            if self.step is None:
                self.step = self._tracker.current_step()
            self._tracker.push(self.step)
        if self._jax:
            cls = _trace_annotation_cls()
            if cls is not None:
                self._annotation = cls(self.name)
                self._annotation.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self._tracker is not None:
            self._tracker.pop()
        t = threading.current_thread()
        self._recorder(self.name, self._t0_ns, end_ns - self._t0_ns,
                       t.ident or 0, t.name, self.step, self.attrs)


class OpenSpanTracker:
    """Per-thread stack of the open spans' steps — the balance check
    behind the 'no dangling spans at shutdown' assertion, and the source
    of the step a span without one inherits."""

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._open_total = 0

    def _stack(self) -> list:
        st = getattr(self._local, "steps", None)
        if st is None:
            st = self._local.steps = []
        return st

    def current_step(self) -> Optional[int]:
        """The step of the innermost span open on this thread."""
        st = self._stack()
        return st[-1] if st else None

    def push(self, step: Optional[int] = None) -> None:
        self._stack().append(step)
        with self._lock:
            self._open_total += 1

    def pop(self) -> None:
        self._stack().pop()
        with self._lock:
            self._open_total -= 1

    @property
    def open_total(self) -> int:
        with self._lock:
            return self._open_total
