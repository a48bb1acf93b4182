"""Telemetry subsystem: span tracing, metrics, profiler capture, compiles.

Four parts, all off by default and near-free when off:

  * ``spans``   -- round-phase span tracer emitting Chrome trace-event JSON
                   (Perfetto-loadable) and, while on, the same spans as
                   ``jax.profiler`` annotations; the global tracer
                   instruments the round driver, the popstore prefetch
                   ring, the hot-swap server, and the watchdog.
  * ``metrics`` -- Counter/Gauge/Histogram registry absorbing the device
                   round-metrics dicts and host-side counters, flushed to a
                   crash-safe JSONL sink and an optional Prometheus
                   textfile exporter.
  * ``jaxprof`` -- opt-in ``jax.profiler`` device-trace capture for an
                   exact round window (``--profile-rounds A:B``), with the
                   span tracer on inside it.
  * ``compiles`` -- a count of the process's XLA compilations, from JAX's
                   own monitoring event.

See docs/telemetry.md for the span taxonomy and metric names.
"""
from repro.telemetry import compiles
from repro.telemetry.jaxprof import RoundProfiler
from repro.telemetry.metrics import (
    COUNTER_KEYS,
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    Registry,
    read_jsonl,
    write_prometheus,
)
from repro.telemetry.spans import (
    Tracer,
    close,
    configure,
    counter,
    enabled,
    flush,
    get_tracer,
    instant,
    load_trace,
    span,
)

__all__ = [
    "COUNTER_KEYS", "Counter", "Gauge", "Histogram", "JsonlSink", "Registry",
    "RoundProfiler", "Tracer", "close", "compiles", "configure", "counter",
    "enabled", "flush", "get_tracer", "instant", "load_trace", "read_jsonl",
    "span", "write_prometheus",
]
