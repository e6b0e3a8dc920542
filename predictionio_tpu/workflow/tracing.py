"""Structured per-phase timing + jax.profiler trace capture.

The reference has no tracing beyond logging — Spark's UI is its implicit
profiler (SURVEY.md §5). The TPU build surfaces the equivalents natively:

- ``phase_timer``: wall-clock per pipeline phase (read/prepare/train-algo,
  serialize/put), logged structured and accumulated on the Context so
  `pio train -v` prints a phase breakdown at the end — the role of
  Spark's stage view. Each phase is an ``obs.trace.span``, so a capture
  shows it as ``pio.train.<phase>`` beside the device's lines.
- ``maybe_profile``: captures a region with ``jax.profiler`` when a
  trace directory is set (``pio train --profile-dir``, ``POST
  /debug/profile``); the output loads in TensorBoard/XProf (device
  timelines, HLO cost analysis) with the program's spans on the host's
  lines.
"""

from __future__ import annotations

import contextlib
import json
import logging

from ..obs.trace import DEVICE_SCOPES, span

log = logging.getLogger("predictionio_tpu.workflow")

__all__ = [
    "maybe_profile", "phase_timer", "phase_report", "reset_phases",
    "phase_times_json",
]


def reset_phases(ctx) -> None:
    """Start a run (or a supervised RETRY attempt) with a clean slate.

    ``phase_times`` accumulates on the Context object; a retried attempt
    re-runs every phase, so without this reset the breakdown would
    double-count and the persisted record would blame phases for time
    they never spent in the successful attempt."""
    ctx.phase_times = []


def phase_times_json(ctx) -> str:
    """The phase breakdown as a compact JSON list of [phase, seconds]
    pairs — the shape persisted into the EngineInstance record."""
    times = getattr(ctx, "phase_times", None) or []
    return json.dumps([[p, round(dt, 6)] for p, dt in times])


def phase_timer(ctx, phase: str) -> span:
    """Time one pipeline phase; record on ctx.phase_times + log."""
    def record(_name: str, t0: float, t1: float) -> None:
        times = getattr(ctx, "phase_times", None)
        if times is None:
            times = ctx.phase_times = []
        times.append((phase, t1 - t0))
        log.info("phase %-24s %8.3fs", phase, t1 - t0)

    return span("train." + phase, sink=record)


def phase_report(ctx) -> str:
    """One-line breakdown of every timed phase, longest first."""
    times = getattr(ctx, "phase_times", None) or []
    total = sum(dt for _, dt in times)
    parts = ", ".join(
        f"{p}={dt:.2f}s" for p, dt in sorted(times, key=lambda x: -x[1]))
    return f"total {total:.2f}s ({parts})" if parts else "no phases timed"


@contextlib.contextmanager
def maybe_profile(trace_dir: str | None):
    """A jax.profiler capture when a directory is given; no-op otherwise.

    The capture runs with the profiler's Python tracer off
    (``python_tracer_level`` 0; jax's default, 1, hooks every Python call
    of every thread, which slows an aiohttp server several times over and
    names idle gaps by file and line): what the host was doing is told
    by the program's own spans (``pio.*``, docs/operations.md). The
    region sits inside one ``pio.profile.window`` annotation, entered
    once the profiler has started and left before it is stopped, so a
    reader can cut the profiler's own start and stop stalls away. The
    operation-to-scope map of the programs compiled so far goes beside
    the capture (``pio_scopes.json``; obs/trace.py says why)."""
    if not trace_dir:
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    log.info("capturing jax profiler trace -> %s", trace_dir)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with span("profile.window"):
            yield
    finally:
        jax.profiler.stop_trace()
        DEVICE_SCOPES.dump(trace_dir)
    log.info("profiler trace written to %s (open with TensorBoard/XProf)",
             trace_dir)
