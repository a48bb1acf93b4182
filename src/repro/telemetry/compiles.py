"""Count of the XLA compilations this process makes.

JAX reports every backend compilation, a load from the persistent
compilation cache included, as the monitoring event
``/jax/core/compile/backend_compile_duration``.  ``install`` registers one
listener for it, however often it is called; the listener keeps a running
count and the seconds spent, and puts an instant ``jit/compile`` on the
global tracer when that is on.  ``launch/train.run`` reports what its run
added as ``jit/compiles`` and ``jit/compile_s``, so a recompile in a long
run (a watchdog rebuild, a new shape) shows in ``--metrics-out``.
"""
from __future__ import annotations

import threading

import jax

from repro.telemetry import spans

EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_installed = False
_count = 0
_seconds = 0.0


def _listener(event: str, duration_secs: float, **kwargs) -> None:
    global _count, _seconds
    if event != EVENT:
        return
    with _lock:
        _count += 1
        _seconds += duration_secs
    spans.instant("jit/compile", {"s": duration_secs,
                                  "fun": str(kwargs.get("fun_name", ""))})


def install() -> None:
    """Register the listener once per process."""
    global _installed
    with _lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(_listener)
            _installed = True


def totals() -> tuple[int, float]:
    """(compilations, seconds) since ``install``."""
    with _lock:
        return _count, _seconds
