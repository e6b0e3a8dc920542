"""Per-request stage waterfalls: where did each millisecond go?

The PR-5 telemetry core answers "how slow is serving overall" with
aggregate histograms; this module answers "where inside ONE request did
the time go". Every serve request carries a :class:`Waterfall` — a
per-request stage timeline keyed by the canonical serving stages:

    admission -> queue_wait -> batch_form -> host_assembly ->
    device_dispatch -> device_compute -> result_scatter -> response_write

The invariant this module is built around: **stage durations sum to the
request's wall latency** (within scheduler noise). ``response_write`` is
computed as the *residual* at :meth:`Waterfall.finish` — wall minus the
sum of the marked stages — so the invariant holds structurally rather
than by hoping every code path remembered to mark.

Attribution mechanics
---------------------

``mark(stage)`` attributes the time elapsed *since the previous mark* to
``stage``, and marks are **additive** — a request served by two models
accumulates two ``device_compute`` slices into one stage total. Deep
code (``ops/retrieval._dispatch_topk``, ``serve_query_batch``) never
threads a waterfall object through its signatures; it calls the
module-level :func:`mark_stage`, which resolves the ambient sink from a
contextvar (copied into ``asyncio.to_thread`` workers, so the fallback
serve path attributes correctly without plumbing).

The batch-shared stages are marked by :func:`stage_span`: the span
``pio.serve.<stage>`` (``obs/trace.py``) wraps the block where the
stage's work happens, so a profiler capture shows it by name on the
dispatch thread, and the clock reading the span takes at its end is the
stage's ``mark``.

The batched path is two-phase: the request's own waterfall marks
``admission`` at submit and receives ``queue_wait`` when its batch is
cut; the batch-shared stages (formation, host assembly, device dispatch/
compute, scatter) are accumulated on a per-dispatch :class:`BatchClock`
(installed as the sink inside the dispatch worker thread) and merged
into every member's waterfall when the batch completes. Batch-shared
time is attributed *in full* to each member — a request that waited
through a 3 ms device step experienced all 3 ms of it.

Two attribution caveats, documented rather than hidden:

- Retrievers whose ``invoke`` blocks internally (ShardedDeviceRetriever
  fences inside the shard loop) land their compute in ``device_dispatch``
  rather than ``device_compute``; the ``hostShare``/``deviceShare``
  split counts both as device time, so the split is robust either way.
- Models with no device retriever (host scoring) have no device stages;
  their predict time lands in ``result_scatter`` (everything between
  assembly and response handoff).

Per-stage histograms are separate unlabeled families
(``pio_serve_stage_<stage>_seconds``) per the registry's one-family-per-
site rule, plus ``pio_serve_waterfall_wall_seconds`` for the wall side
of the invariant.
"""

from __future__ import annotations

import logging
import threading
import time
from contextvars import ContextVar

from .metrics import METRICS
from .trace import span

__all__ = [
    "STAGES",
    "DEVICE_STAGES",
    "STAGE_HISTOGRAMS",
    "Waterfall",
    "BatchClock",
    "mark_stage",
    "stage_span",
    "set_stage_sink",
    "reset_stage_sink",
    "current_sink",
    "stage_sink_active",
    "stage_summary",
]

#: Canonical stage order of one serve request, ingress to egress.
STAGES: tuple[str, ...] = (
    "admission",        # ingress -> body parsed + admission decided
    "queue_wait",       # submitted to the batcher -> batch cut
    "batch_form",       # batch cut -> dispatch worker running
    "host_assembly",    # id->row decode, padding, batch matrix build
    "device_dispatch",  # the invoke() call itself (enqueue to XLA)
    "device_compute",   # block_until_ready delta around the invoke
    "result_scatter",   # unpad, host pull, blend, fan-out to futures
    "response_write",   # residual: future resolution -> bytes on wire
)

#: Stages counted as device time in the hostShare/deviceShare split.
DEVICE_STAGES: tuple[str, ...] = ("device_dispatch", "device_compute")

STAGE_HISTOGRAMS = {
    s: METRICS.histogram(
        f"pio_serve_stage_{s}_seconds",
        f"per-request time attributed to the {s} serving stage")
    for s in STAGES
}

_H_WALL = METRICS.histogram(
    "pio_serve_waterfall_wall_seconds",
    "wall latency of requests carrying a stage waterfall (the sum-to-wall"
    " invariant's right-hand side)")


class _Clock:
    """Shared cursor mechanics: ``mark(stage)`` attributes time since the
    previous mark, additively per stage."""

    __slots__ = ("t0", "_last", "stages", "_order")

    def __init__(self, now: float | None = None):
        now = time.perf_counter() if now is None else now
        self.t0 = now
        self._last = now
        self.stages: dict[str, float] = {}
        self._order: list[str] = []

    def mark(self, stage: str, now: float | None = None) -> None:
        now = time.perf_counter() if now is None else now
        dt = now - self._last
        if dt < 0.0:
            dt = 0.0
        if stage not in self.stages:
            self._order.append(stage)
        self.stages[stage] = self.stages.get(stage, 0.0) + dt
        self._last = now

    def add(self, stage: str, dt: float) -> None:
        """Attribute an externally measured duration without moving the
        cursor (used to merge batch-shared stage time into members)."""
        if dt <= 0.0:
            return
        if stage not in self.stages:
            self._order.append(stage)
        self.stages[stage] = self.stages.get(stage, 0.0) + dt

    def cursor(self, now: float | None = None) -> None:
        """Re-seat the cursor so the next ``mark`` doesn't inherit
        unrelated elapsed time (bench loops re-seat per iteration)."""
        self._last = time.perf_counter() if now is None else now


class Waterfall(_Clock):
    """One request's stage timeline, finished exactly once."""

    __slots__ = ("rid", "path", "wall", "status", "stalled_stage",
                 "meta", "finished")

    def __init__(self, rid: str | None = None, path: str = "serve"):
        super().__init__()
        self.rid = rid
        self.path = path
        self.wall: float | None = None
        self.status: str | None = None
        self.stalled_stage: str | None = None
        self.meta: dict = {}
        self.finished = False

    def merge_batch(self, clock: "BatchClock") -> None:
        # list() snapshot: a watchdog-abandoned zombie thread may still
        # be marking stages on this clock while the loop merges it
        for stage, dt in list(clock.stages.items()):
            self.add(stage, dt)

    def finish(self, status: str | None = None,
               record: bool = True) -> "Waterfall":
        """Close the waterfall: wall = now - ingress; the unattributed
        residual becomes ``response_write`` so stages sum to wall by
        construction. Records the per-stage histograms unless told not
        to. Idempotent — the first finish wins."""
        if self.finished:
            return self
        self.finished = True
        self.wall = time.perf_counter() - self.t0
        self.status = status
        residual = self.wall - sum(self.stages.values())
        if residual > 0.0:
            self.add("response_write", residual)
        if record:
            for stage, dt in self.stages.items():
                h = STAGE_HISTOGRAMS.get(stage)
                if h is not None:
                    h.record(dt)
            _H_WALL.record(self.wall)
        return self

    def to_dict(self) -> dict:
        wall = self.wall if self.wall is not None else (
            time.perf_counter() - self.t0)
        d: dict = {
            "requestId": self.rid,
            "path": self.path,
            "status": self.status,
            "finished": self.finished,
            "wallMs": round(wall * 1e3, 3),
            "stagesMs": {s: round(self.stages[s] * 1e3, 3)
                         for s in STAGES if s in self.stages},
        }
        if self.stalled_stage is not None:
            d["stalledStage"] = self.stalled_stage
        if self.meta:
            d["context"] = dict(self.meta)
        return d


class BatchClock(_Clock):
    """Stage accumulator for ONE micro-batch dispatch, installed as the
    stage sink inside the dispatch worker thread and merged into every
    member waterfall on completion."""

    __slots__ = ()

    def in_progress(self) -> str:
        """The stage underway right now — the canonical successor of the
        last completed mark. This is what the watchdog stamps onto hung
        requests as ``stalledStage``: a dispatch that never marked
        anything stalled before batch formation completed."""
        if not self._order:
            return "batch_form"
        last = self._order[-1]
        try:
            i = STAGES.index(last)
        except ValueError:
            return last
        return STAGES[i + 1] if i + 1 < len(STAGES) else last


# ---------------------------------------------------------------------------
# Ambient sink: deep code marks stages without signature plumbing.

_SINK: ContextVar[_Clock | None] = ContextVar("pio_stage_sink", default=None)


def set_stage_sink(sink: _Clock | None):
    """Install ``sink`` as the ambient stage sink for this context;
    returns the reset token."""
    return _SINK.set(sink)


def reset_stage_sink(token) -> None:
    _SINK.reset(token)


def current_sink() -> _Clock | None:
    return _SINK.get()


def stage_sink_active() -> bool:
    return _SINK.get() is not None


def mark_stage(stage: str) -> None:
    """Attribute time-since-last-mark to ``stage`` on the ambient sink;
    a no-op (one contextvar read) when no request is being attributed —
    training and bench paths pay nothing."""
    sink = _SINK.get()
    if sink is not None:
        sink.mark(stage)


def _mark_span_end(name: str, _t0: float, t1: float) -> None:
    """Span sink: the reading a stage's span took at its end is the
    stage's mark on the ambient clock."""
    sink = _SINK.get()
    if sink is not None:
        sink.mark(name.rpartition(".")[2], t1)


def stage_span(stage: str, **facts) -> span:
    """The span ``pio.serve.<stage>`` around the block where a stage's
    work happens, whether or not a request is being attributed; its end
    is ``mark_stage(stage)``, on the same clock reading. The stage keeps
    its meaning: all the time since the previous mark, of which the
    block is the last part. One span a batch, so its log line is
    debug-level: ``serve.dispatch`` already tells the batch at info."""
    return span("serve." + stage, sink=_mark_span_end, level=logging.DEBUG,
                **facts)


# ---------------------------------------------------------------------------
# Aggregate views.

_split_lock = threading.Lock()


def stage_summary() -> dict:
    """JSON-ready aggregate of the stage histograms plus the
    ``hostShare``/``deviceShare`` split (shares of total attributed
    time; device = dispatch + compute, see module docstring)."""
    stages = {}
    total = 0.0
    device = 0.0
    for s in STAGES:
        snap = STAGE_HISTOGRAMS[s].snapshot()
        stages[s] = snap
        total += snap["sum"]
        if s in DEVICE_STAGES:
            device += snap["sum"]
    wall = _H_WALL.snapshot()
    host = total - device
    return {
        "stages": stages,
        "wall": wall,
        "hostShare": round(host / total, 4) if total > 0 else None,
        "deviceShare": round(device / total, 4) if total > 0 else None,
    }
