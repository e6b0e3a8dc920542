"""Feedback-loop publisher: one session, bounded retries, circuit breaker.

The engine server's feedback loop POSTs every (query, prediction) pair
back to the event server (reference CreateServer.scala:488-541). The
original port opened a NEW ``aiohttp.ClientSession`` per POST and
launched fire-and-forget tasks whose exceptions (and references) were
lost. This module replaces that with a lifecycle-owned publisher:

- ONE shared ``ClientSession`` for the server's lifetime, closed on
  drain;
- every POST task is TRACKED (cancelled and awaited during drain, so
  shutdown never leaks a task or loses its exception);
- failures land in a BOUNDED retry queue replayed with jittered
  exponential backoff (oldest entries drop when the queue is full — the
  feedback loop is best-effort telemetry, it must never become an
  unbounded memory leak because the event server is down);
- a 429/503 answer with ``Retry-After`` (the event server's admission
  layer under overload) schedules the replay at the SERVER-provided
  horizon instead of the local exponential guess, and does not advance
  the circuit breaker — a shedding server is alive, not dead;
- a circuit breaker (closed → open → half-open) stops hammering a dead
  event server: past ``breaker_threshold`` consecutive failures new
  publishes drop fast; after ``breaker_reset_s`` ONE probe is let
  through and its outcome closes or re-opens the breaker.

Counters (sent/failed/retried/dropped/breaker state) surface through
``stats()`` into the engine server's ``/stats.json`` and
``/health.json``.
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from collections import deque

from ..faults import FAULTS
from ..obs.breaker import breaker_set as _breaker_set
from ..obs.metrics import METRICS
from ..obs.trace import current_request_id, trace_event

log = logging.getLogger("predictionio_tpu.server")

__all__ = ["FeedbackPublisher"]


class _Backpressure(RuntimeError):
    """The event server said 429/503 — back off for as long as IT asked.

    Carries the parsed ``Retry-After`` (seconds, possibly fractional —
    the admission layer emits decimal seconds) so the retry queue can
    schedule the replay at the server-provided horizon instead of the
    local exponential guess. A publisher that ignores Retry-After and
    retries on its own clock is exactly the feedback storm the event
    server's admission controller exists to shed."""

    def __init__(self, status: int, retry_after_s: float | None):
        super().__init__(f"event server answered {status} (backpressure)")
        self.status = status
        self.retry_after_s = retry_after_s

# ISSUE 5: breaker state/transition gauges live in obs/breaker.py
# (shared with the ingest drainer); these two are feedback-specific
_M_RETRY_DEPTH = METRICS.gauge(
    "pio_feedback_retry_depth",
    "feedback events waiting in the bounded retry queue")
_M_FEEDBACK = METRICS.counter(
    "pio_feedback_events_total",
    "feedback publishes by outcome (sent/failed/retried/dropped)",
    labelnames=("outcome",))


class FeedbackPublisher:
    """Owns the feedback loop's session, tasks, retry queue and breaker."""

    def __init__(
        self,
        feedback_url: str,
        access_key: str,
        *,
        timeout_s: float = 5.0,
        queue_max: int = 256,
        retry_max: int = 3,
        breaker_threshold: int = 5,
        breaker_reset_s: float = 30.0,
        backoff_base_s: float = 0.25,
        backoff_cap_s: float = 5.0,
    ):
        self.feedback_url = feedback_url
        self.access_key = access_key
        self.timeout_s = timeout_s
        self.queue_max = max(1, queue_max)
        self.retry_max = retry_max
        self.breaker_threshold = max(1, breaker_threshold)
        self.breaker_reset_s = breaker_reset_s
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self._session = None
        self._tasks: set[asyncio.Task] = set()
        #: (event dict, attempt, not-before monotonic time)
        self._retry: deque[tuple[dict, int, float]] = deque()
        self._retry_wake: asyncio.Event | None = None
        self._worker: asyncio.Task | None = None
        self._closing = False
        # breaker state
        self._state = "closed"  # closed | open | half_open
        self._consecutive_failures = 0
        self._opened_at = 0.0
        # counters
        self.sent = 0
        self.failed = 0
        self.retried = 0
        self.dropped = 0
        self.breaker_opens = 0

    # -- breaker -----------------------------------------------------------
    def _breaker_allows(self, now: float) -> bool:
        """closed: pass. open: drop until ``breaker_reset_s`` elapsed,
        then flip half-open and admit ONE probe. half-open: a probe is
        already in the air — drop until it reports back."""
        if self._state == "closed":
            return True
        if self._state == "open":
            if now - self._opened_at >= self.breaker_reset_s:
                self._state = "half_open"
                _breaker_set("feedback", "half_open", prev="open")
                return True
            return False
        return False  # half_open: probe outstanding

    def _on_success(self) -> None:
        if self._state != "closed":
            log.info("feedback breaker closed (probe succeeded)")
            _breaker_set("feedback", "closed", prev=self._state)
        self._state = "closed"
        self._consecutive_failures = 0
        self.sent += 1
        _M_FEEDBACK.inc(outcome="sent")

    def _on_failure(self, err: Exception) -> None:
        self.failed += 1
        _M_FEEDBACK.inc(outcome="failed")
        self._consecutive_failures += 1
        if self._state == "half_open" or (
                self._state == "closed"
                and self._consecutive_failures >= self.breaker_threshold):
            if self._state != "open":
                self.breaker_opens += 1
                _breaker_set("feedback", "open", prev=self._state)
                log.warning(
                    "feedback breaker OPEN after %d consecutive failures "
                    "(last: %s); dropping feedback for %.1fs",
                    self._consecutive_failures, err, self.breaker_reset_s)
            self._state = "open"
            self._opened_at = time.monotonic()

    # -- publish path ------------------------------------------------------
    def publish(self, query_json: dict, prediction, pr_id: str,
                request_id: str | None = None) -> None:
        """Fire-and-forget from the query hot path; the task is tracked
        so drain can cancel/await it. Breaker-open publishes drop
        immediately (counted) instead of queuing against a dead server.

        ``request_id`` (default: the context's trace id) is stamped into
        the event as a ``pio_request_id`` property, so the event-store
        row joins back to the serving log line that produced it."""
        if self._closing:
            self.dropped += 1
            _M_FEEDBACK.inc(outcome="dropped")
            return
        rid = request_id or current_request_id()
        props = {"query": query_json, "prediction": prediction}
        if rid:
            props["pio_request_id"] = rid
        event = {
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": props,
            "prId": pr_id,
        }
        if not self._breaker_allows(time.monotonic()):
            self.dropped += 1
            _M_FEEDBACK.inc(outcome="dropped")
            return
        trace_event("serve.feedback_publish", trace=rid, pr_id=pr_id)
        self._track(asyncio.create_task(self._post(event, attempt=0)))

    def _track(self, task: asyncio.Task) -> None:
        self._tasks.add(task)
        task.add_done_callback(self._task_done)

    def _task_done(self, task: asyncio.Task) -> None:
        self._tasks.discard(task)
        if task.cancelled():
            return
        exc = task.exception()  # retrieve: a lost exception logs nothing
        if exc is not None:
            log.warning("feedback task died: %s", exc)

    async def _ensure_session(self):
        import aiohttp

        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.timeout_s))
        return self._session

    async def _post(self, event: dict, attempt: int) -> None:
        try:
            await FAULTS.afire("server.feedback")
            session = await self._ensure_session()
            async with session.post(
                f"{self.feedback_url}/events.json",
                params={"accessKey": self.access_key},
                json=event,
            ) as resp:
                if resp.status in (429, 503):
                    ra = resp.headers.get("Retry-After")
                    try:
                        ra_s = float(ra) if ra is not None else None
                    except ValueError:
                        ra_s = None
                    raise _Backpressure(resp.status, ra_s)
                if resp.status >= 500:
                    raise RuntimeError(f"event server answered {resp.status}")
        except asyncio.CancelledError:
            raise
        except _Backpressure as e:
            # A shedding server is ALIVE — don't advance the breaker's
            # consecutive-failure count; replay at the horizon the server
            # itself asked for (its Retry-After is lag-proportional).
            self.failed += 1
            _M_FEEDBACK.inc(outcome="failed")
            self._enqueue_retry(event, attempt + 1,
                                retry_after_s=e.retry_after_s)
            return
        except Exception as e:  # noqa: BLE001 — feedback is best-effort
            self._on_failure(e)
            self._enqueue_retry(event, attempt + 1)
            return
        self._on_success()

    # -- retry queue -------------------------------------------------------
    def _enqueue_retry(self, event: dict, attempt: int,
                       retry_after_s: float | None = None) -> None:
        if attempt > self.retry_max:
            self.dropped += 1
            _M_FEEDBACK.inc(outcome="dropped")
            return
        if len(self._retry) >= self.queue_max:
            self._retry.popleft()  # oldest out: the queue is a buffer,
            self.dropped += 1      # not an archive
            _M_FEEDBACK.inc(outcome="dropped")
        if retry_after_s is not None:
            # server-provided horizon (429/503 Retry-After) wins over the
            # local exponential guess; small positive jitter so a herd of
            # publishers doesn't replay on the same tick
            delay = max(0.0, retry_after_s) * (1.0 + 0.1 * random.random())
        else:
            backoff = min(self.backoff_cap_s,
                          self.backoff_base_s * (2 ** (attempt - 1)))
            # full jitter: desynchronizes a thundering herd of retries
            # when the event server comes back
            delay = backoff * (0.5 + random.random() / 2)
        self._retry.append((event, attempt, time.monotonic() + delay))
        _M_RETRY_DEPTH.set(len(self._retry))
        self._ensure_worker()
        if self._retry_wake is not None:
            self._retry_wake.set()

    def _ensure_worker(self) -> None:
        if self._worker is None or self._worker.done():
            self._retry_wake = asyncio.Event()
            self._worker = asyncio.create_task(self._retry_loop())

    async def _retry_loop(self) -> None:
        """Replays due retries; breaker-open entries wait (they are
        already queued — dropping them is the queue-bound's job)."""
        assert self._retry_wake is not None
        while True:
            if not self._retry:
                self._retry_wake.clear()
                await self._retry_wake.wait()
            now = time.monotonic()
            due_in = min((t for _, _, t in self._retry), default=now) - now
            if due_in > 0:
                await asyncio.sleep(min(due_in, 0.5))
                continue
            if not self._breaker_allows(now):
                await asyncio.sleep(min(0.5, self.breaker_reset_s / 4))
                continue
            for i, (event, attempt, not_before) in enumerate(self._retry):
                if not_before <= now:
                    del self._retry[i]
                    self.retried += 1
                    _M_FEEDBACK.inc(outcome="retried")
                    _M_RETRY_DEPTH.set(len(self._retry))
                    await self._post(event, attempt)
                    break

    # -- lifecycle ---------------------------------------------------------
    async def aclose(self) -> None:
        """Drain-time teardown: stop the retry worker, cancel + await
        every tracked task, close the shared session. Idempotent."""
        self._closing = True
        if self._worker is not None:
            self._worker.cancel()
            try:
                await self._worker
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._worker = None
        tasks, self._tasks = set(self._tasks), set()
        for t in tasks:
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        if self._session is not None and not self._session.closed:
            await self._session.close()
        self._session = None

    def reopen(self) -> None:
        """Undo a close for a server that keeps living (failed-bind
        retry); the session and retry worker are recreated lazily."""
        self._closing = False

    def stats(self) -> dict:
        return {
            "sent": self.sent,
            "failed": self.failed,
            "retried": self.retried,
            "dropped": self.dropped,
            "retryQueueDepth": len(self._retry),
            "inflightTasks": len(self._tasks),
            "breakerState": self._state,
            "breakerOpens": self.breaker_opens,
        }
