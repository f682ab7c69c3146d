"""The benchmark's window on the program's own spans and compiles."""
from __future__ import annotations

import threading
import time
from typing import List, NamedTuple, Optional

from repro.obs import Telemetry, TelemetryConfig


class SpanRec(NamedTuple):
    name: str
    t0_ns: int  # time.perf_counter_ns() clock
    dur_ns: int
    thread: str
    step: Optional[int]
    attrs: dict

    @property
    def t1_ns(self) -> int:
        return self.t0_ns + self.dur_ns


class RecordingTelemetry(Telemetry):
    """A ``repro.obs.Telemetry`` with no sinks that keeps every completed
    span in memory.  Metric snapshots happen only when the run closes (the
    window is larger than any run), so the stepping loop pays for span
    bookkeeping alone.  ``jax_annotations`` bridges the spans into the
    profiler trace, for traced runs."""

    def __init__(self, jax_annotations: bool):
        super().__init__(TelemetryConfig(jsonl_path=None, trace_path=None,
                                         window=1 << 40,
                                         jax_annotations=jax_annotations))
        self.records: List[SpanRec] = []

    def _record_span(self, name, t0_ns, dur_ns, tid, thread, step, attrs):
        super()._record_span(name, t0_ns, dur_ns, tid, thread, step, attrs)
        self.records.append(SpanRec(name, t0_ns, dur_ns, thread, step,
                                    dict(attrs)))

    def named(self, name: str) -> List[SpanRec]:
        return sorted((r for r in self.records if r.name == name),
                      key=lambda r: r.t0_ns)


class CompileLog:
    """Every JAX tracing, lowering and backend compile, with its end time
    on the ``perf_counter_ns`` clock (``jax.monitoring`` duration events)."""

    WATCH = ("/jax/core/compile/backend_compile_duration",
             "/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax

        self.events = []
        self._lock = threading.Lock()
        self._on = True
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, secs: float, **_):
        if self._on and event in self.WATCH:
            with self._lock:
                self.events.append((event, time.perf_counter_ns(), secs))

    def stop(self) -> None:
        self._on = False

    def within(self, t0_ns: int, t1_ns: int) -> dict:
        """Counts of each watched event that ended inside [t0, t1]."""
        out = {e.rsplit("/", 1)[-1]: 0 for e in self.WATCH}
        with self._lock:
            for e, t, _ in self.events:
                if t0_ns <= t <= t1_ns:
                    out[e.rsplit("/", 1)[-1]] += 1
        return out
