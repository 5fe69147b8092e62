"""Span helpers beyond the recorder's host spans.

The pipeline spans (``step/get_batch``, ``step/dispatch``,
``host/assemble``, ``host/place``, ``h2d/place_batch``,
``metrics/readback``, ``ckpt/save``) are instrumented strictly at host
boundaries and close on the host clock — a ``step/dispatch`` span
measures dispatch latency, NOT device compute (the sync-free loop never
blocks on the step's outputs; device time keeps coming from the
MetricsRing readback cadence and the run-level synchronized steps/sec).

For deep dives where device-side timing IS wanted, ``ProfileWindow``
arms an opt-in ``jax.profiler`` trace over a bounded step window; it is
entirely inert unless a log directory is given, and fails the run when
the trace it was given cannot be taken. With a recorder enabled, every
span also lands in that trace as a host annotation of the same name, on
the clock of the device's events, beside the trainer's per-step
``StepTraceAnnotation("train", step_num=i)`` markers.
"""
from __future__ import annotations

from typing import Optional

from repro.obs import recorder as _rec


class ProfileWindow:
    """Opt-in ``jax.profiler`` trace over steps [start, start+num).

    The trainer calls ``on_step(step)`` at the top of every iteration
    and ``stop()`` on exit; with ``logdir=None`` both are no-ops. Once a
    directory is given the trace is what the run was asked for: a
    profiler that cannot start or stop raises (after recording a
    ``profile/*_failed`` error event) instead of letting the run exit 0
    without it.
    """

    def __init__(self, logdir: Optional[str], start_step: int = 5,
                 num_steps: int = 2):
        self.logdir = logdir
        self.start = int(start_step)
        self.num = max(1, int(num_steps))
        self._active = False
        self._done = logdir is None

    def on_step(self, step: int):
        if self._done:
            return
        if not self._active and step >= self.start:
            import jax
            try:
                jax.profiler.start_trace(self.logdir)
            except Exception as e:
                self._done = True
                _rec.event("profile/start_failed", level="error",
                           error=repr(e))
                raise RuntimeError(
                    f"profiler trace into {self.logdir} did not start") from e
            self._active = True
            _rec.event("profile/started", logdir=self.logdir, step=step)
        elif self._active and step >= self.start + self.num:
            self.stop()

    def stop(self):
        if not self._active:
            self._done = True
            return
        import jax
        self._active = False
        self._done = True
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            _rec.event("profile/stop_failed", level="error", error=repr(e))
            raise RuntimeError(
                f"profiler trace into {self.logdir} did not stop") from e
        _rec.event("profile/stopped", logdir=self.logdir)
