"""On-demand ``jax.profiler`` device-trace capture for a round window.

``--profile-rounds A:B`` captures a profiler trace for exactly rounds A..B
(inclusive, 0-indexed round numbers as the launchers log them): the trace
starts before round A's dispatch and stops after round B completes, so the
capture holds whole rounds -- XLA device timelines, host/device transfer
lanes, and (on TPU) the per-kernel breakdown -- viewable in Perfetto or
TensorBoard's profile plugin.

Inside the window the global span tracer is on, even without
``--trace-out``: its spans enter ``jax.profiler`` annotations, so the
capture holds ``round/dispatch``, ``round/block_until_ready`` and the
``popstore/*`` phases on ``/host:CPU``, on the device operations' clock.
Besides the ``.xplane.pb`` the capture writes a ``.trace.json.gz`` that
Perfetto and ``json`` read, with each device operation's name stack.

Why a WINDOW and not the whole run: the profiler's overhead and trace size
are per-event, so profiling a 10^4-round job is both slow and unreadable;
two or three steady-state rounds after compilation has settled is what the
popstore/async tuning work actually needs.

Zero cost when unset: ``RoundProfiler.parse(None, ...)`` returns None and
the launchers guard every call site on that.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

from repro.telemetry import spans


class RoundProfiler:
    """Start/stop ``jax.profiler`` around a [start, stop] round window.

    The launcher calls ``before_round(r)`` ahead of each dispatch and
    ``after_round(r)`` once the round's results are materialised; ``close``
    is the crash/early-exit backstop (a trace left open at process exit is
    truncated and unreadable)."""

    def __init__(self, start: int, stop: int, out_dir: str | os.PathLike):
        if start < 0 or stop < start:
            raise ValueError(
                f"--profile-rounds window must be 0 <= A <= B, got {start}:{stop}")
        self.start = start
        self.stop = stop
        self.out_dir = str(out_dir)
        self.active = False
        self.captured = False
        self._tracer_was_on = False

    @classmethod
    def parse(cls, spec: Optional[str],
              out_dir: str | os.PathLike) -> Optional["RoundProfiler"]:
        """``"A:B"`` -> profiler for rounds A..B; ``"A"`` -> just round A;
        None/"" -> None (profiling off)."""
        if not spec:
            return None
        parts = str(spec).split(":")
        try:
            if len(parts) == 1:
                a = b = int(parts[0])
            elif len(parts) == 2:
                a, b = int(parts[0]), int(parts[1])
            else:
                raise ValueError(spec)
        except ValueError:
            raise ValueError(
                f"--profile-rounds expects 'A:B' or 'A' (round numbers), "
                f"got {spec!r}") from None
        return cls(a, b, out_dir)

    def before_round(self, round_idx: int) -> None:
        if self.active or self.captured or round_idx < self.start:
            return
        if round_idx > self.stop:
            return  # window already passed (e.g. resumed beyond it)
        import jax

        pathlib.Path(self.out_dir).mkdir(parents=True, exist_ok=True)
        # a failing profiler raises: a run that asked for a trace and got
        # none must not exit 0
        jax.profiler.start_trace(self.out_dir, create_perfetto_trace=True)
        self.active = True
        self._tracer_was_on = spans.enabled()
        spans.get_tracer().configure(enabled=True)
        print(f"[telemetry] jax.profiler capture started at round "
              f"{round_idx} -> {self.out_dir}", flush=True)

    def after_round(self, round_idx: int) -> None:
        if self.active and round_idx >= self.stop:
            self._stop()

    def _stop(self) -> None:
        import jax

        self.active = False
        self.captured = True
        tracer = spans.get_tracer().configure(enabled=self._tracer_was_on)
        if not self._tracer_was_on:
            tracer.drain()  # the window's events served the annotations only
        jax.profiler.stop_trace()
        print(f"[telemetry] jax.profiler capture written to "
              f"{self.out_dir}", flush=True)

    def close(self) -> None:
        if self.active:
            self._stop()
