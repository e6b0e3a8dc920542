"""Micro-batching query dispatcher — fixed-shape TPU serving under load.

The reference serves each query independently on the Spark driver
(reference: workflow/CreateServer.scala:462-591 — spray routes straight
into ``algorithms.map(_.predictBase(...))``); per-query dispatch is fine
on a JVM, but on a TPU each device call has a fixed launch overhead and
the fused retrieval kernel (ops/retrieval.py) amortizes it over a query
batch. This dispatcher coalesces concurrent ``/queries.json`` requests
into batched serve calls:

- first arrival opens a window (default 1 ms); everything arriving within
  it (up to ``max_batch``) is served as ONE batch;
- per-query failures are isolated — one malformed query 400s alone, the
  rest of its batch still answers;
- an idle server adds at most the window to p50; a loaded server turns N
  device calls into ceil(N/max_batch).

With ``adaptive=True`` the window is not fixed: ``window_s`` becomes a
CEILING and the actual window per batch scales with the observed arrival
rate (EWMA of inter-arrival gaps) and pipeline occupancy. An idle server
converges to a ~0 window (a lone query pays wire latency, not the
ceiling); under load the window stretches toward the time it takes
``max_batch`` arrivals to accumulate, capped at the ceiling. Arrival
order is still preserved — only the sleep length changes.

Batches are PIPELINED, two deep: a batch is cut only while fewer than
``STAGING_DEPTH`` (the serving pipeline's double buffer,
``ops/pipeline.py``) cut batches are short of the end of their device
step, one running on the device and one behind it, so that the device
never waits for the host and a query never waits behind more scans than
that. A place is held from the cut, not from the dispatch: the batch's
host work before its step stands ahead of the device too, and what is
cut early rides an earlier, emptier scan. Until the gate opens the
forming batch keeps filling from the queue. The end of a device step
opens it: the pipeline reports it on the dispatch worker thread
(``ops.pipeline.set_step_end_hook``), and the batcher is woken on its
loop. Host work after the step (result scatter, the answers' JSON)
holds no place ahead of the device and overlaps the next batch's device
step. A ``batch_fn`` that reports nothing (a model served through its
retriever alone, host-scored engines, a plain callable) is gated on its
whole call: the same rule with the only signal there is.

``max_inflight`` is not the depth of that queue. It is the gate's other
count: the calls that may be live at once, those still in their host
work after the step included, so that a slow scatter cannot pile worker
threads up without bound; degraded mode halves it. Batch
FORMATION stays on one loop (arrival order and the window are preserved,
so single-query p50 is unchanged); only the serve calls overlap.
Completions may land out of order; each query's future resolves
individually, so callers never observe reordering.

The batch function contract: ``batch_fn(list[query]) -> list[("ok",
result) | ("err", exception)]``, run in a worker thread; it must be
thread-safe up to ``max_inflight`` concurrent calls (the engine-server
batch path is: stats under a lock, deployed bundle read via snapshot).

Rows of unequal cost: ``costing()`` is asked at every cut and answers
``(cost_of, budget)``, what a query costs (a sequence model's history
length in tokens) and what one device step takes, or None. A cut then
takes queries in arrival order up to that budget (and ``max_batch``), at
least one; the cost is read over the queries the cut looks at, never per
arrival. No ``costing``, or an answer of None: the cut is by
``max_batch`` alone, as before. The sojourn estimate, the drain rate and the adaptive window
still count ``max_batch`` rows a batch: under a cut by cost they read
low (they promise more than is served, so they shed late, not early).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, Callable, Sequence

from ..faults import FAULTS
from ..obs.flight import FLIGHT
from ..obs.metrics import METRICS
from ..obs.trace import current_request_id, span, trace_event
from ..obs.waterfall import (BatchClock, current_sink, reset_stage_sink,
                             set_stage_sink, stage_span)
from ..ops.pipeline import (STAGING_DEPTH, reset_step_end_hook,
                            set_step_end_hook)

log = logging.getLogger("predictionio_tpu.server")

__all__ = ["MicroBatcher", "ServerBusy", "DeadlineExceeded", "DispatchTimeout"]

# ISSUE 5: the micro-batch hot sites, dark since PR 1/2, now land in the
# process registry. Instance counters below stay the per-batcher view
# (stats()/tests); these are the cross-process-scrape view.
_M_QUEUE_WAIT = METRICS.histogram(
    "pio_microbatch_queue_wait_seconds",
    "time a query waits in the micro-batch queue before batch formation")
_M_WINDOW = METRICS.histogram(
    "pio_microbatch_window_seconds",
    "coalescing window chosen per formed batch (adaptive: EWMA-scaled)")
_M_CUT_HELD = METRICS.histogram(
    "pio_microbatch_cut_held_seconds",
    "time the formation loop waited, with queries queued, for the gate "
    "to open (one observation per wait)")
_M_DISPATCH = METRICS.histogram(
    "pio_microbatch_dispatch_seconds",
    "wall time of one batched dispatch (thread hop + device call)")
_M_DEVICE = METRICS.histogram(
    "pio_microbatch_device_seconds",
    "batch_fn execution inside the dispatch worker thread (device time)")
_M_DEADLINE = METRICS.counter(
    "pio_deadline_expired_total",
    "queries failed 504 because their end-to-end deadline passed")
_M_CODEL = METRICS.counter(
    "pio_codel_dropped_total",
    "queries dropped at enqueue because their estimated queue sojourn "
    "already exceeded their deadline (CoDel-style early shed)")
_M_WATCHDOG = METRICS.counter(
    "pio_watchdog_reclaims_total",
    "stuck-dispatch watchdog trips (pipeline slot reclaimed, thread "
    "zombied)")


class ServerBusy(RuntimeError):
    """Raised by submit() when the pending queue is at capacity — the
    HTTP layer maps it to 503 so overload sheds load instead of queueing
    without bound (the reference's per-query dispatch is implicitly
    bounded by its thread pool)."""


class DeadlineExceeded(RuntimeError):
    """The query's end-to-end deadline passed while it was queued (or
    before submission) — the HTTP layer maps it to 504. An expired query
    is failed at batch-formation time and never consumes a batch slot."""


class DispatchTimeout(RuntimeError):
    """A dispatched batch exceeded the stuck-dispatch watchdog timeout.
    Its place ahead of the device and its count against ``max_inflight``
    are given back (the hung worker thread is tracked as a zombie), its
    queries 504, and the on_watchdog hook fires so the server can flip
    into degraded mode."""


class MicroBatcher:
    """Coalesces concurrent submissions into pipelined batched calls."""

    def __init__(
        self,
        batch_fn: Callable[[Sequence[Any]], list],
        *,
        max_batch: int = 128,
        window_s: float = 0.001,
        max_pending: int = 1024,
        max_inflight: int = 8,
        adaptive: bool = False,
        dispatch_timeout_s: float | None = None,
        on_watchdog: Callable[[], None] | None = None,
        costing: Callable[
            [], tuple[Callable[[Any], int], int] | None] | None = None,
    ):
        self.batch_fn = batch_fn
        self.max_batch = max(1, max_batch)
        #: asked at every cut (a /reload may swap the model behind it):
        #: (what one query costs a device step, the most a step takes),
        #: or None: the cut is by max_batch alone
        self.costing = costing
        self.window_s = max(0.0, window_s)
        self.max_pending = max(1, max_pending)
        self.max_inflight = max(1, max_inflight)
        self.adaptive = adaptive
        #: stuck-dispatch watchdog: a batch_fn call exceeding this wall
        #: time has its futures failed (DispatchTimeout) and its places
        #: in the gate given back; the thread keeps running as a
        #: tracked zombie (to_thread work cannot be interrupted). None
        #: disables (pre-watchdog behavior: a hang wedges a place forever).
        self.dispatch_timeout_s = dispatch_timeout_s
        #: called (no args, on the event loop) after each watchdog trip —
        #: the engine server hooks degraded mode here
        self.on_watchdog = on_watchdog
        # adaptive-window state: EWMA of inter-arrival gaps + last arrival
        self._ewma_iv: float | None = None
        self._last_arrival: float | None = None
        self.last_window_s = 0.0 if adaptive else self.window_s
        #: (query, future, absolute-monotonic deadline | None,
        #:  enqueue instant, trace id | None,
        #:  stage waterfall sink | None — the submitting request's
        #:  obs/waterfall.Waterfall, captured from its context)
        self._pending: list[tuple] = []
        self._wake: asyncio.Event | None = None
        self._task: asyncio.Task | None = None
        self._inflight: set[asyncio.Task] = set()
        # the gate: a batch is cut only while _cut_allowed()
        self._gate: asyncio.Event | None = None  # set when it opens
        self._ahead = 0  # cut batches short of their device step's end
        self._live = 0  # cut batches whose call has not returned
        self._open = True
        self._opened_at = float("-inf")  # monotonic; last closed -> open
        self._zombies = 0  # hung batch_fn threads the watchdog abandoned
        self._closing = False
        # observability: how well batching + pipelining are working
        self.batches = 0
        self.cuts_held = 0  # of them, those the gate held (see _launch)
        self.batched_queries = 0
        self.max_seen_batch = 0
        self.peak_inflight = 0
        self.watchdog_trips = 0
        self.deadline_expired = 0
        self.codel_dropped = 0
        # EWMA of successful dispatch wall time — the CoDel sojourn
        # estimate and the admission controller's drain-rate both key
        # off it; None until the first batch completes
        self._ewma_dispatch_s: float | None = None

    def _ensure_started(self) -> None:
        if self._task is None or self._task.done():
            self._wake = asyncio.Event()
            self._gate = asyncio.Event()
            self._task = asyncio.create_task(self._run())

    async def submit(self, query: Any, *, deadline: float | None = None) -> Any:
        """Enqueue one query; resolves to its result (or raises its own
        error) when its batch completes. Raises ServerBusy at capacity.

        ``deadline``: absolute ``time.monotonic()`` instant after which
        the query is worthless to its caller — an already-expired submit
        raises DeadlineExceeded immediately, and a query whose deadline
        passes while queued is failed at batch-formation time WITHOUT
        consuming a batch slot (the load balancer's 504, not a wasted
        device call)."""
        if self._closing:
            # close() is mid-drain: starting a fresh worker generation now
            # would either leak it or have close() cancel this future —
            # shed instead (the HTTP layer answers 503)
            raise ServerBusy("micro-batcher is shutting down")
        if deadline is not None and time.monotonic() >= deadline:
            self.deadline_expired += 1
            _M_DEADLINE.inc()
            FLIGHT.note_deadline_expired()
            trace_event("serve.deadline_expired", where="submit")
            raise DeadlineExceeded("request deadline expired before submit")
        if len(self._pending) >= self.max_pending:
            raise ServerBusy(
                f"micro-batch queue full ({self.max_pending} pending)")
        if deadline is not None:
            # CoDel-style sojourn check: if the queue ahead of this query
            # cannot drain before its deadline, fail it NOW instead of
            # letting it rot in the queue to be swept at batch formation.
            # Engages only once the queue is at least one full batch deep
            # AND dispatch history exists — a cold or shallow queue never
            # pre-drops (the sweep remains the authority there).
            est = self._estimate_sojourn_s()
            if est > 0 and time.monotonic() + est >= deadline:
                self.codel_dropped += 1
                _M_CODEL.inc()
                trace_event("serve.codel_dropped", where="submit",
                            est_sojourn_ms=round(est * 1e3, 3),
                            queued=len(self._pending))
                raise DeadlineExceeded(
                    f"queue sojourn estimate {est * 1e3:.1f}ms exceeds "
                    f"remaining deadline; dropped at enqueue")
        self._ensure_started()
        if self.adaptive:
            self._note_arrival(time.monotonic())
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending.append(
            (query, fut, deadline, time.monotonic(), current_request_id(),
             current_sink()))
        assert self._wake is not None
        self._wake.set()
        return await fut

    def _depth(self) -> int:
        """Batches that are served at once: a dispatch's wall time spans
        this many device steps, so the queue drains by one batch every
        ``_ewma_dispatch_s`` over it."""
        return min(STAGING_DEPTH, self.max_inflight)

    def _cut_allowed(self) -> bool:
        """The gate is open: a batch formed now would be cut now."""
        return (self._ahead < STAGING_DEPTH
                and self._live < self.max_inflight)

    def _gate_moved(self) -> None:
        """After every change to what the gate counts, on the loop: an
        opening wakes the formation loop and is stamped (a batch whose
        oldest query was queued before that instant was held by the
        gate)."""
        is_open = self._cut_allowed()
        if is_open and not self._open:
            self._opened_at = time.monotonic()
            if self._gate is not None:
                self._gate.set()
        self._open = is_open

    def _take(self) -> list[tuple]:
        """Cut: the queued queries, in arrival order, that one device
        step takes. Up to ``max_batch`` rows; where rows have a cost, up
        to the budget too, and never fewer than one."""
        n = min(len(self._pending), self.max_batch)
        stated = self.costing() if self.costing is not None else None
        if stated is not None:
            cost_of, budget = stated
            spent = 0
            for j in range(n):
                spent += cost_of(self._pending[j][0])
                if spent > budget and j > 0:
                    n = j
                    break
        batch = self._pending[:n]
        del self._pending[:n]
        return batch

    def _estimate_sojourn_s(self) -> float:
        """Expected queue wait for a query enqueued now: the number of
        pipeline waves the queued-ahead batches need, times the EWMA
        dispatch time. Deliberately conservative — returns 0.0 (never
        drop) until the queue is >= one full batch deep and at least one
        dispatch has completed."""
        if self._ewma_dispatch_s is None or len(self._pending) < self.max_batch:
            return 0.0
        batches_ahead = len(self._pending) // self.max_batch
        depth = self._depth()
        waves = (batches_ahead + depth - 1) // depth
        # + partial wave when the next batch has to wait for its cut
        if not self._cut_allowed():
            waves += 1
        return waves * self._ewma_dispatch_s

    def drain_rate_per_s(self) -> float | None:
        """Throughput estimate (queries/sec) at the current pipeline
        shape, or None before the first dispatch completes. The
        admission controller sizes Retry-After from this."""
        if self._ewma_dispatch_s is None or self._ewma_dispatch_s <= 0:
            return None
        return self.max_batch * self._depth() / self._ewma_dispatch_s

    def _note_arrival(self, now: float) -> None:
        if self._last_arrival is not None:
            # clamp: an idle hour is a gap, not a rate estimate
            gap = min(now - self._last_arrival, 1.0)
            self._ewma_iv = (gap if self._ewma_iv is None
                             else 0.7 * self._ewma_iv + 0.3 * gap)
        self._last_arrival = now

    def _choose_window(self, now: float) -> float:
        """Window for the batch about to form: 0 when waiting can't help
        (batch already full, no rate history, or arrivals slower than the
        ceiling with the gate open), else the time ``need`` more
        arrivals are expected to take, capped at the ``window_s`` ceiling."""
        if not self.adaptive:
            return self.window_s
        need = self.max_batch - len(self._pending)
        if need <= 0 or self._ewma_iv is None:
            return 0.0
        iv = self._ewma_iv
        if self._last_arrival is not None:
            # a fresh idle gap overrides a stale burst-rate estimate
            iv = max(iv, now - self._last_arrival)
        if iv >= self.window_s and self._cut_allowed():
            # a window can't fill a batch at this rate; with the gate
            # closed waiting is free, otherwise dispatch now
            return 0.0
        w = min(self.window_s, need * iv)
        # Deadline headroom clamp (ISSUE 16 satellite): when EVERY
        # queued entry carries a deadline, never hold the batch past the
        # tightest one minus the expected dispatch wall — admission
        # already accepted these queries, so a slow-arrival EWMA must
        # not expire them in the queue. Entries without deadlines leave
        # the window alone (no deadline means no headroom to protect).
        if self._pending and all(len(t) > 2 and t[2] is not None
                                 for t in self._pending):
            margin = self._ewma_dispatch_s or 0.0
            headroom = min(t[2] for t in self._pending) - now - margin
            w = max(0.0, min(w, headroom))
        return w

    def set_max_inflight(self, n: int) -> None:
        """Resize the bound on calls live at once (degraded mode shrinks
        it, recovery restores it; at 1 the calls run one at a time).
        Takes effect on the next cut; calls already live run on."""
        self.max_inflight = max(1, n)
        self._gate_moved()

    async def close(self) -> None:
        """Hard stop: cancel the worker, let in-flight batches finish,
        FAIL anything still queued (CancelledError). For the graceful
        variant that flushes the queue instead, see drain()."""
        await self._shutdown(flush=False)

    async def drain(self) -> None:
        """Graceful drain (SIGTERM / /stop): stop accepting, FLUSH the
        queued queries as immediate batches, wait for every in-flight
        dispatch, then stop the worker. Queued callers get answers, not
        cancellations; expired deadlines still 504."""
        await self._shutdown(flush=True)

    async def _shutdown(self, *, flush: bool) -> None:
        self._closing = True  # submit() sheds until the drain finishes
        try:
            if self._task is not None:
                self._task.cancel()
                try:
                    await self._task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
                self._task = None
            if flush:
                # dispatch everything still queued, no window — the point
                # of the drain is answering admitted requests, fast
                self._sweep_expired(time.monotonic())
                while self._pending:
                    await self._admit()
                    self._launch(self._take())
            # let dispatched batches finish — their queries already left
            # the queue and their callers are awaiting results; to_thread
            # work cannot be interrupted anyway
            inflight, self._inflight = set(self._inflight), set()
            if inflight:
                await asyncio.gather(*inflight, return_exceptions=True)
            # fail anything still queued — a caller awaiting submit() must
            # not hang forever because shutdown won the race with its batch
            pending, self._pending = self._pending, []
            for _, fut, *_rest in pending:
                if not fut.done():
                    fut.set_exception(asyncio.CancelledError("batcher closed"))
        finally:
            self._closing = False  # a later submit() may restart cleanly

    def _sweep_expired(self, now: float) -> None:
        """Fail queued queries whose deadline passed (504) so they never
        consume a batch slot; runs at every batch-formation point."""
        if not any(t[2] is not None and t[2] <= now for t in self._pending):
            return
        keep: list[tuple] = []
        for item in self._pending:
            query, fut, dl, t_enq, rid, *rest = item
            if dl is not None and dl <= now:
                self.deadline_expired += 1
                _M_DEADLINE.inc()
                FLIGHT.note_deadline_expired()
                trace_event("serve.deadline_expired", trace=rid,
                            where="queued",
                            waited_ms=round((now - t_enq) * 1e3, 3))
                wf = rest[0] if rest else None
                if wf is not None:
                    # the time it rotted in the queue IS its queue_wait
                    wf.add("queue_wait", now - t_enq)
                if not fut.done():
                    fut.set_exception(DeadlineExceeded(
                        "request deadline expired while queued"))
            else:
                keep.append(item)
        self._pending[:] = keep

    async def _run(self) -> None:
        """Batch-formation loop: serializes windowing + arrival order,
        hands each formed batch to a concurrent dispatch task."""
        assert self._wake is not None
        while True:
            await self._wake.wait()
            w = self._choose_window(time.monotonic())
            self.last_window_s = w
            _M_WINDOW.record(w)
            if w > 0 and len(self._pending) < self.max_batch:
                # window open: let concurrent requests pile in
                await asyncio.sleep(w)
            # expired queries 504 here, before a slot is spent on them
            self._sweep_expired(time.monotonic())
            # wait for the gate BEFORE taking queries off the queue: the
            # forming batch keeps filling meanwhile, and a saturated
            # pipeline backpressures into max_pending/503 land instead of
            # stripping the queue into waiting tasks
            await self._admit()
            self._sweep_expired(time.monotonic())  # the wait takes time
            batch = self._take()
            if not self._pending:
                self._wake.clear()
            if batch:
                self._launch(batch)

    async def _admit(self) -> None:
        """Wait until a batch may be cut. The end of a device step (or
        of a call) opens the gate and wakes this loop; nothing polls."""
        if self._cut_allowed():
            return
        assert self._gate is not None
        # the one span that crosses an await: every other span on the
        # loop's thread is a synchronous block, so it lies inside this
        # interval or outside it
        with span("serve.cut_held", level=logging.DEBUG,
                  pending=len(self._pending)) as wait:
            while not self._cut_allowed():
                self._gate.clear()
                await self._gate.wait()
        _M_CUT_HELD.record(wait.t1 - wait.t0)

    def _launch(self, batch: list[tuple]) -> None:
        """Cut: the batch takes its place ahead of the device, counts as
        live, and its dispatch task starts. The place is given back once,
        by whichever comes first: the device step's end, the call's end,
        the watchdog. The cut was held if the gate was closed at any time
        since the batch's oldest query was queued."""
        held = len(batch[0]) > 3 and batch[0][3] < self._opened_at
        self._ahead += 1
        self._live += 1
        self.peak_inflight = max(self.peak_inflight, self._live)
        self._gate_moved()
        released = False

        def release() -> None:  # on the loop
            nonlocal released
            if not released:
                released = True
                self._ahead -= 1
                self._gate_moved()

        task = asyncio.create_task(self._dispatch(batch, release, held))
        self._inflight.add(task)
        task.add_done_callback(self._inflight.discard)

    def _call_batch_fn(self, queries: list, clock: BatchClock | None = None,
                       step_end: Callable[[], None] | None = None) -> list:
        """Runs in the dispatch worker thread; the chaos harness's hang/
        error/slow site for 'a device call wedged' lives here so an
        injected hang occupies the thread exactly like a real one.

        ``clock`` is this dispatch's batch stage accumulator, installed
        as the ambient stage sink for the thread (to_thread gave it a
        private context copy) so serve_query_batch/_dispatch_topk marks
        land on the batch clock, not on any one member's waterfall. The
        fault site fires BEFORE the first mark: a hang here shows up as
        stalled before any stage completed (stalledStage=batch_form).
        ``step_end`` is what the serving pipeline calls on this thread
        where the batch's device step ends."""
        hook = set_step_end_hook(step_end)
        token = set_stage_sink(clock) if clock is not None else None
        try:
            # batch cut -> worker thread running; the span is the
            # worker's first block, the stage also the thread hop
            with stage_span("batch_form", rows=len(queries)):
                FAULTS.fire("microbatch.dispatch")
            t0 = time.perf_counter()
            try:
                return self.batch_fn(queries)
            finally:
                _M_DEVICE.record(time.perf_counter() - t0)
        finally:
            if token is not None:
                reset_stage_sink(token)
            reset_step_end_hook(hook)

    def _zombie_done(self, task: asyncio.Task) -> None:
        self._zombies -= 1
        if not task.cancelled() and task.exception() is not None:
            # retrieve it (else asyncio logs "exception never retrieved");
            # the batch's futures were already failed by the watchdog
            log.warning("abandoned dispatch finally failed: %s",
                        task.exception())
        else:
            log.info("abandoned dispatch thread finally returned "
                     "(%d zombie(s) left)", self._zombies)

    async def _dispatch(self, batch: list[tuple[Any, asyncio.Future, Any]],
                        release: Callable[[], None], held: bool) -> None:
        """Serve ONE formed batch; gives back what ``_launch`` took: the
        place ahead of the device (``release``) from the end of the
        device step at the earliest to the end of this call at the
        latest, the count against ``max_inflight`` at the end of this
        call. With dispatch_timeout_s set, a batch_fn call that outlives
        the watchdog has its futures failed (504) and both given back;
        the un-interruptible worker thread is tracked as a zombie until
        it returns."""
        loop = asyncio.get_running_loop()

        def step_end() -> None:  # on the worker thread
            try:
                loop.call_soon_threadsafe(release)
            except RuntimeError:
                pass  # the loop is closed: no batch is left to cut

        t_start = time.monotonic()
        traces = [t[4] for t in batch if len(t) > 4 and t[4]]
        wfs = [t[5] for t in batch if len(t) > 5 and t[5] is not None]
        for t in batch:
            if len(t) > 3:
                _M_QUEUE_WAIT.record(t_start - t[3])
            if len(t) > 5 and t[5] is not None:
                # per-member queue wait: its enqueue -> this batch cut
                t[5].add("queue_wait", t_start - t[3])
        clock = BatchClock() if wfs else None
        try:
            queries = [t[0] for t in batch]
            inner = asyncio.ensure_future(
                asyncio.to_thread(self._call_batch_fn, queries, clock,
                                  step_end))
            try:
                if self.dispatch_timeout_s is not None:
                    # shield: on timeout the outer wait is cancelled but
                    # the thread task keeps running (tracked below)
                    outcomes = await asyncio.wait_for(
                        asyncio.shield(inner), self.dispatch_timeout_s)
                else:
                    outcomes = await inner
                if len(outcomes) != len(batch):
                    raise RuntimeError(
                        f"batch_fn returned {len(outcomes)} outcomes for "
                        f"{len(batch)} queries")
            except asyncio.TimeoutError:
                self.watchdog_trips += 1
                _M_WATCHDOG.inc()
                trace_event("serve.watchdog_reclaim", trace=None,
                            traces=traces, batch=len(batch),
                            timeout_s=self.dispatch_timeout_s)
                self._zombies += 1
                inner.add_done_callback(self._zombie_done)
                log.error(
                    "watchdog: batch of %d stuck > %.1fs; reclaiming its "
                    "pipeline slot (trip #%d, %d zombie thread(s))",
                    len(batch), self.dispatch_timeout_s,
                    self.watchdog_trips, self._zombies)
                err = DispatchTimeout(
                    f"batch dispatch exceeded {self.dispatch_timeout_s}s "
                    f"watchdog; slot reclaimed")
                # stamp the hung members' waterfalls with the stage the
                # batch stalled in and push them into the flight ring
                # BEFORE on_watchdog dumps it — the incident file must
                # contain its victims
                stalled = clock.in_progress() if clock is not None else None
                for wf in wfs:
                    if clock is not None:
                        wf.merge_batch(clock)
                    wf.stalled_stage = stalled
                    FLIGHT.note_hung(wf.to_dict())
                for _, fut, *_rest in batch:
                    if not fut.done():
                        fut.set_exception(err)
                if self.on_watchdog is not None:
                    try:
                        self.on_watchdog()
                    except Exception:  # noqa: BLE001 — hook must not kill
                        log.exception("on_watchdog hook failed")
                return
            except Exception as e:  # noqa: BLE001 — batch-level failure
                for _, fut, *_rest in batch:
                    if not fut.done():
                        fut.set_exception(e)
                return
            if clock is not None:
                # hand the batch-shared stage time to every member: each
                # request lived through the whole formation/assembly/
                # device step, so each is attributed the full duration
                for wf in wfs:
                    wf.merge_batch(clock)
            self.batches += 1
            self.cuts_held += held
            self.batched_queries += len(batch)
            self.max_seen_batch = max(self.max_seen_batch, len(batch))
            dispatch_s = time.monotonic() - t_start
            _M_DISPATCH.record(dispatch_s)
            self._ewma_dispatch_s = (
                dispatch_s if self._ewma_dispatch_s is None
                else 0.7 * self._ewma_dispatch_s + 0.3 * dispatch_s)
            trace_event("serve.dispatch", trace=None, traces=traces,
                        batch=len(batch), ms=round(dispatch_s * 1e3, 3))
            for (_, fut, *_rest), (tag, payload) in zip(batch, outcomes):
                if fut.done():
                    continue
                if tag == "ok":
                    fut.set_result(payload)
                else:
                    fut.set_exception(payload)
        finally:
            self._live -= 1
            release()
            self._gate_moved()

    def stats(self) -> dict:
        return {
            "batches": self.batches,
            "cutsHeld": self.cuts_held,
            "batchedQueries": self.batched_queries,
            "avgBatchSize": (self.batched_queries / self.batches) if self.batches else 0.0,
            "maxBatchSize": self.max_seen_batch,
            "maxInflight": self.max_inflight,
            "peakInflight": self.peak_inflight,
            "adaptive": self.adaptive,
            "windowCeilingMs": self.window_s * 1e3,
            "lastWindowMs": self.last_window_s * 1e3,
            "inflight": self._live,
            "aheadOfDevice": self._ahead,
            "occupancy": self._live / self.max_inflight,
            "arrivalIntervalMs": (self._ewma_iv * 1e3
                                  if self._ewma_iv is not None else None),
            "dispatchTimeoutS": self.dispatch_timeout_s,
            "watchdogTrips": self.watchdog_trips,
            "zombieDispatches": self._zombies,
            "deadlineExpired": self.deadline_expired,
            "codelDropped": self.codel_dropped,
            "ewmaDispatchMs": (self._ewma_dispatch_s * 1e3
                               if self._ewma_dispatch_s is not None else None),
        }
