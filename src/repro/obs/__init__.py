"""Runtime telemetry for the MPSL stack.

Three pieces (ROADMAP "Observability"):

  * ``recorder`` — structured, buffered JSONL event/metrics emitter
    (counters, gauges, spans, run metadata) with a no-op ambient
    default: until ``obs.configure(path)`` runs, every call site hits
    shared null singletons and the hot loop pays nothing. An enabled
    recorder's spans also appear in a ``jax.profiler`` trace, as host
    annotations on the clock of the device's events.
  * ``spans``    — host-boundary span tracing of the step pipeline plus
    an opt-in ``jax.profiler`` trace window (``ProfileWindow``).
  * ``comm``     — trace-time per-client/per-link byte accounting of
    the smashed-activation uplink, cut-layer-gradient downlink, and
    head-FedAvg links, cross-checked against ``core.costs``.

``python -m repro.obs.report runlog.jsonl`` renders a run log into
per-stage latency and per-link byte tables.
"""
from repro.obs.recorder import (NullRecorder, Recorder, StructuredLogger,
                                configure, counter, enabled, event, gauge,
                                get, get_logger, shutdown, span)
from repro.obs.spans import ProfileWindow
from repro.obs import comm

__all__ = [
    "NullRecorder", "Recorder", "StructuredLogger", "ProfileWindow",
    "comm", "configure", "counter", "enabled", "event", "gauge", "get",
    "get_logger", "shutdown", "span",
]
