"""Telemetry-driven overload control: adaptive admission at ingress.

PR-2..PR-5 taught the serving path to *survive* overload — deadlines
504 at batch formation, the watchdog reclaims hung slots, the journal
503s when full — but every request was still admitted unconditionally
and paid the full queue before dying. This module closes the loop the
other way: it samples the live telemetry the PR-5 registry already
collects and sheds work **at ingress** with ``429 + Retry-After``
before it can blow its deadline (Google's ads-serving stack treats
overload control as a first-class subsystem for exactly this reason —
retrieval/scoring services fall over at the queue, not the kernel;
arXiv:2501.10546).

Three cooperating pieces:

- :class:`AdmissionController` — samples signals (microbatch queue
  depth, windowed queue-wait p99, inflight occupancy, deadline-expiry
  rate, journal fill) and computes a per-request-class decision.
  Classes are shed in priority order: ``feedback`` (cheapest to lose)
  sheds first, then ``ingest``, then ``serve``. The controller also
  exposes a *brownout* pressure the engine server uses to degrade
  gracefully (smaller top-k, skip feedback, fallback path) before any
  hard shedding starts.
- :class:`TokenBucket` / :class:`RateLimiter` — per-client rate
  limiting keyed on access key, with burst headroom, so one hot client
  cannot starve the rest even when aggregate pressure is low.
- :func:`backpressure_retry_after_s` — the shared, jittered,
  lag-proportional Retry-After computation. The admission 429, the
  journal-full 503 (``api/ingest.py``) and the feedback publisher's
  client side all speak the same pacing language.

``decide()`` carries the ``admission.decide`` fault site and **fails
open**: overload control must never be the thing that takes serving
down, so an injected (or real) error inside the controller admits the
request and counts ``decision="error_open"``.
"""

from __future__ import annotations

import collections
import random
import threading
import time
from dataclasses import dataclass

from ..faults import FAULTS
from ..obs.metrics import METRICS, Histogram

__all__ = [
    "AdmissionController",
    "AdmissionDecision",
    "TokenBucket",
    "RateLimiter",
    "backpressure_retry_after_s",
    "CLASSES",
    "DECISIONS",
]

#: request classes, in shed-priority order (feedback goes first)
CLASSES: tuple[str, ...] = ("serve", "feedback", "ingest")
#: decision outcomes recorded per class
DECISIONS: tuple[str, ...] = ("admit", "shed", "throttle", "error_open")

#: default per-class shed thresholds against the composite pressure
#: (max over signal fractions, 1.0 = a signal at its configured limit).
#: feedback sheds well before serve so the cheap class absorbs the
#: first wave; ingest sheds just under its own journal-full hard stop.
DEFAULT_SHED_THRESHOLDS: dict[str, float] = {
    "serve": 1.0,
    "feedback": 0.7,
    "ingest": 0.95,
}

_M_ADMIT = METRICS.counter(
    "pio_admission_total",
    "admission decisions by request class "
    "(admit / shed = overload 429 / throttle = per-client rate limit 429 "
    "/ error_open = controller failed, request admitted)",
    labelnames=("klass", "decision"))
for _c in CLASSES:
    for _d in DECISIONS:
        _M_ADMIT.labels(klass=_c, decision=_d).inc(0)

_M_PRESSURE = METRICS.gauge(
    "pio_admission_pressure",
    "composite overload pressure per admission plane "
    "(max signal fraction; >= 1.0 means the hottest signal is at its "
    "configured limit and the serve class sheds)",
    labelnames=("plane",))


def backpressure_retry_after_s(backlog: float, drain_per_s: float | None,
                               *, base_s: float = 1.0, cap_s: float = 30.0,
                               jitter: float = 0.25,
                               rng=random.random) -> float:
    """Seconds a shed client should wait before retrying.

    Proportional to the time the current backlog needs to drain
    (``backlog / drain_per_s``), clamped to ``[base_s, cap_s]``, with
    ±``jitter`` uniform spread so a shed thundering herd does not
    return in one synchronized wave. Falls back to ``base_s`` when the
    drain rate is unknown (no dispatch/drain history yet).
    """
    if drain_per_s is not None and drain_per_s > 0 and backlog > 0:
        est = backlog / drain_per_s
    else:
        est = base_s
    est = min(max(est, base_s), cap_s)
    return est * (1.0 + jitter * (2.0 * rng() - 1.0))


def _interval_p99(bounds: tuple[float, ...], prev: tuple[int, ...],
                  cur: tuple[int, ...]) -> float:
    """p99 of the observations recorded *between* two bucket snapshots
    (same interpolation as Histogram.quantile, over the delta)."""
    delta = [c - p for p, c in zip(prev, cur)]
    total = sum(delta)
    if total <= 0:
        return 0.0
    rank = 0.99 * total
    cum = 0
    for i, c in enumerate(delta):
        if c == 0:
            continue
        if i >= len(bounds):
            return bounds[-1]  # overflow bucket: report top boundary
        lo = 0.0 if i == 0 else bounds[i - 1]
        hi = bounds[i]
        if cum + c >= rank:
            frac = (rank - cum) / c
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        cum += c
    return bounds[-1]


class TokenBucket:
    """Classic token bucket with burst headroom and a monotonicity
    guard: a clock that stands still or steps backwards never refills
    (and never penalizes) — ``allow`` stays correct under test-supplied
    clocks and suspend/resume jumps."""

    __slots__ = ("rate", "burst", "tokens", "_t_last", "_lock")

    def __init__(self, rate_per_s: float, burst: float | None = None):
        if rate_per_s <= 0:
            raise ValueError("rate_per_s must be > 0")
        self.rate = float(rate_per_s)
        # default burst: 2x the sustained rate, at least one request
        self.burst = float(burst) if burst else max(1.0, 2.0 * self.rate)
        self.tokens = self.burst  # start full: clients get their burst
        self._t_last: float | None = None
        self._lock = threading.Lock()

    def allow(self, now: float | None = None, n: float = 1.0) -> bool:
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._t_last is None:
                self._t_last = now
            elapsed = now - self._t_last
            if elapsed > 0:
                self.tokens = min(self.burst, self.tokens + elapsed * self.rate)
                self._t_last = now
            if self.tokens >= n:
                self.tokens -= n
                return True
            return False

    def retry_after_s(self, n: float = 1.0) -> float:
        """Seconds until ``n`` tokens will have refilled."""
        with self._lock:
            deficit = n - self.tokens
        return max(0.0, deficit) / self.rate


class RateLimiter:
    """Per-key token buckets (LRU-bounded so unbounded key cardinality
    cannot grow memory; an evicted key simply restarts with a full
    burst)."""

    def __init__(self, rate_per_s: float, burst: float | None = None,
                 max_keys: int = 1024):
        self.rate = float(rate_per_s)
        self.burst = burst
        self.max_keys = int(max_keys)
        self._buckets: collections.OrderedDict[str, TokenBucket] = \
            collections.OrderedDict()
        self._lock = threading.Lock()

    def allow(self, key: str, now: float | None = None) -> tuple[bool, float]:
        """Returns ``(allowed, retry_after_s)`` for one request from
        ``key`` (retry_after_s is 0.0 when allowed)."""
        with self._lock:
            bucket = self._buckets.get(key)
            if bucket is None:
                bucket = TokenBucket(self.rate, self.burst)
                self._buckets[key] = bucket
                while len(self._buckets) > self.max_keys:
                    self._buckets.popitem(last=False)
            else:
                self._buckets.move_to_end(key)
        if bucket.allow(now):
            return True, 0.0
        return False, bucket.retry_after_s()

    def __len__(self) -> int:
        with self._lock:
            return len(self._buckets)


@dataclass(frozen=True)
class AdmissionDecision:
    admitted: bool
    retry_after_s: float
    reason: str
    pressure: float


class AdmissionController:
    """Computes admit/shed per request class from live telemetry.

    Signal callables are injected by the hosting server (so the
    controller has no import ties to the batcher or journal); the
    queue-wait p99 and deadline-expiry *rate* are sampled straight off
    the :data:`~predictionio_tpu.obs.metrics.METRICS` registry over a
    sliding window (bucket-count diffs / counter deltas), so they
    recover to zero when the overload passes instead of being stuck at
    the lifetime worst case.

    Every signal is normalized to a fraction of its configured limit;
    the composite *shed pressure* is the max fraction. A class sheds
    when shed pressure >= its threshold (:data:`DEFAULT_SHED_THRESHOLDS`).
    *Brownout pressure* additionally folds in inflight occupancy — a
    saturated pipeline is reason to degrade gracefully, but never, by
    itself, to refuse work.
    """

    def __init__(self, name: str = "serve", *,
                 queue_depth=None, queue_high: int = 64,
                 wait_hist_name: str | None = None,
                 wait_budget_s: float = 0.0,
                 inflight=None,
                 expiry_counter_name: str | None = None,
                 expiry_rate_high: float = 10.0,
                 journal_fill=None, journal_fill_high: float = 0.9,
                 backlog=None, drain_per_s=None,
                 rate_limit_qps: float = 0.0, rate_limit_burst: float = 0.0,
                 shed_thresholds: dict[str, float] | None = None,
                 brownout_enter: float = 0.75, brownout_exit: float = 0.5,
                 retry_after_base_s: float = 1.0,
                 retry_after_cap_s: float = 30.0,
                 sample_interval_s: float = 0.05,
                 window_s: float = 0.25):
        self.name = name
        self._queue_depth = queue_depth
        self.queue_high = max(1, int(queue_high))
        self._wait_hist_name = wait_hist_name
        self.wait_budget_s = float(wait_budget_s)
        self._inflight = inflight
        self._expiry_counter_name = expiry_counter_name
        self.expiry_rate_high = float(expiry_rate_high)
        self._journal_fill = journal_fill
        self.journal_fill_high = float(journal_fill_high)
        self._backlog = backlog
        self._drain_per_s = drain_per_s
        self.limiter = (RateLimiter(rate_limit_qps,
                                    rate_limit_burst or None)
                        if rate_limit_qps > 0 else None)
        self.shed_thresholds = dict(DEFAULT_SHED_THRESHOLDS)
        if shed_thresholds:
            self.shed_thresholds.update(shed_thresholds)
        self.brownout_enter = float(brownout_enter)
        self.brownout_exit = float(brownout_exit)
        self.retry_after_base_s = float(retry_after_base_s)
        self.retry_after_cap_s = float(retry_after_cap_s)
        self.sample_interval_s = float(sample_interval_s)
        self.window_s = float(window_s)

        self._lock = threading.Lock()
        self._sampled_at: float | None = None
        self._signals: dict[str, float] = {}
        self.shed_pressure = 0.0
        self.brownout_pressure = 0.0
        # windowed-sample state: last bucket snapshot / counter reading
        self._wait_prev: tuple[int, ...] | None = None
        self._wait_prev_t: float | None = None
        self._wait_p99 = 0.0
        self._expiry_prev: float | None = None
        self._expiry_prev_t: float | None = None
        self._expiry_rate = 0.0
        # per-class decision tallies (mirrors pio_admission_total, but
        # per controller instance so two planes in one process do not
        # mix in /health.json)
        self._counts = {c: collections.Counter() for c in CLASSES}
        _M_PRESSURE.set(0.0, plane=self.name)

    # -- signal sampling ---------------------------------------------------
    def _sample_wait_p99(self, now: float) -> float:
        hist = METRICS.get(self._wait_hist_name) if self._wait_hist_name \
            else None
        if not isinstance(hist, Histogram):
            return 0.0
        counts, _, _ = hist.bucket_counts()
        if self._wait_prev is None or len(self._wait_prev) != len(counts):
            self._wait_prev, self._wait_prev_t = counts, now
            return self._wait_p99
        if now - self._wait_prev_t >= self.window_s:
            self._wait_p99 = _interval_p99(hist.bounds, self._wait_prev,
                                           counts)
            self._wait_prev, self._wait_prev_t = counts, now
        return self._wait_p99

    def _sample_expiry_rate(self, now: float) -> float:
        ctr = METRICS.get(self._expiry_counter_name) \
            if self._expiry_counter_name else None
        if ctr is None:
            return 0.0
        val = ctr.value()
        if self._expiry_prev is None or val < self._expiry_prev:
            # first sample, or the registry was reset under us
            self._expiry_prev, self._expiry_prev_t = val, now
            return self._expiry_rate
        if now - self._expiry_prev_t >= self.window_s:
            self._expiry_rate = ((val - self._expiry_prev)
                                 / (now - self._expiry_prev_t))
            self._expiry_prev, self._expiry_prev_t = val, now
        return self._expiry_rate

    def _resample(self, now: float) -> None:
        """Recompute signal fractions (holding the lock); cached for
        ``sample_interval_s`` so a request burst costs dict reads, not
        histogram walks."""
        signals: dict[str, float] = {}
        if self._queue_depth is not None:
            signals["queue"] = float(self._queue_depth()) / self.queue_high
        if self.wait_budget_s > 0:
            p99 = self._sample_wait_p99(now)
            signals["queue_wait"] = p99 / self.wait_budget_s
        if self._expiry_counter_name and self.expiry_rate_high > 0:
            rate = self._sample_expiry_rate(now)
            signals["deadline_rate"] = rate / self.expiry_rate_high
        if self._journal_fill is not None:
            signals["journal"] = (float(self._journal_fill())
                                  / self.journal_fill_high)
        self._signals = signals
        self.shed_pressure = max(signals.values(), default=0.0)
        occupancy = float(self._inflight()) if self._inflight is not None \
            else 0.0
        self._signals["inflight"] = occupancy
        self.brownout_pressure = max(self.shed_pressure, occupancy)
        self._sampled_at = now
        _M_PRESSURE.set(self.shed_pressure, plane=self.name)

    def pressure(self, now: float | None = None) -> float:
        """Current composite shed pressure (resampling if the cache is
        stale)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if (self._sampled_at is None
                    or now - self._sampled_at >= self.sample_interval_s):
                self._resample(now)
            return self.shed_pressure

    def retry_after_s(self) -> float:
        """Jittered, lag-proportional Retry-After for a shed response."""
        backlog = float(self._backlog()) if self._backlog is not None else 0.0
        drain = self._drain_per_s() if self._drain_per_s is not None else None
        return backpressure_retry_after_s(
            backlog, drain,
            base_s=self.retry_after_base_s, cap_s=self.retry_after_cap_s)

    # -- the decision ------------------------------------------------------
    def _count(self, klass: str, decision: str) -> None:
        _M_ADMIT.inc(klass=klass, decision=decision)
        self._counts[klass][decision] += 1

    def decide(self, klass: str, key: str | None = None,
               now: float | None = None) -> AdmissionDecision:
        """Admission decision for one request of class ``klass``
        (optionally attributed to client ``key`` for rate limiting).
        Fails OPEN on any internal error — including the armed
        ``admission.decide`` fault site — because the overload
        controller must never be the outage."""
        if klass not in self._counts:
            self._counts[klass] = collections.Counter()
        try:
            FAULTS.fire("admission.decide")
            now = time.monotonic() if now is None else now
            if self.limiter is not None and key:
                ok, bucket_wait = self.limiter.allow(key, now)
                if not ok:
                    self._count(klass, "throttle")
                    # pace the client to its own bucket, de-synchronized
                    ra = max(bucket_wait, 0.05) * (1.0 + 0.25 * random.random())
                    return AdmissionDecision(
                        False, ra, "rate limit exceeded for client key",
                        self.shed_pressure)
            p = self.pressure(now)
            threshold = self.shed_thresholds.get(klass, 1.0)
            if p >= threshold:
                self._count(klass, "shed")
                with self._lock:
                    hot = max(self._signals, key=self._signals.get,
                              default="queue")
                return AdmissionDecision(
                    False, self.retry_after_s(),
                    f"overloaded ({hot} pressure {p:.2f} >= {threshold:.2f})",
                    p)
            self._count(klass, "admit")
            return AdmissionDecision(True, 0.0, "ok", p)
        except Exception as e:  # fail open: admission is never the outage
            self._count(klass, "error_open")
            return AdmissionDecision(
                True, 0.0, f"admission error ({e!r}); failing open", 0.0)

    # -- reporting ---------------------------------------------------------
    @property
    def overloaded(self) -> bool:
        """True when brownout pressure warrants graceful degradation."""
        return self.brownout_pressure >= self.brownout_enter

    @property
    def recovered(self) -> bool:
        """True when brownout pressure has fallen back under the exit
        threshold (hysteresis: exit < enter)."""
        return self.brownout_pressure <= self.brownout_exit

    def pressure_snapshot(self) -> dict:
        """Tiny view for the flight recorder's ambient context: the two
        composite pressures plus the hottest contributing signal. Reads
        the cached sample only — never resamples, so it is safe to call
        from an incident-dump path that must not add load."""
        with self._lock:
            signals = dict(self._signals)
            shed_p, brown_p = self.shed_pressure, self.brownout_pressure
        hot = max(signals, key=signals.get, default=None)
        return {
            "pressure": round(shed_p, 4),
            "brownoutPressure": round(brown_p, 4),
            "hotSignal": hot,
        }

    def stats(self) -> dict:
        """JSON-friendly view for /health.json and /stats.json."""
        with self._lock:
            signals = dict(self._signals)
            shed_p, brown_p = self.shed_pressure, self.brownout_pressure
        classes = {}
        for c, tally in self._counts.items():
            total = sum(tally.values())
            admitted = tally["admit"] + tally["error_open"]
            classes[c] = {
                "admitted": tally["admit"],
                "shed": tally["shed"],
                "throttled": tally["throttle"],
                "errorOpen": tally["error_open"],
                "admitRate": (admitted / total) if total else 1.0,
            }
        return {
            "pressure": round(shed_p, 4),
            "brownoutPressure": round(brown_p, 4),
            "signals": {k: round(v, 4) for k, v in signals.items()},
            "rateLimit": ({"qps": self.limiter.rate,
                           "burst": self.limiter.burst
                           if self.limiter.burst is not None else None,
                           "trackedKeys": len(self.limiter)}
                          if self.limiter is not None else None),
            "classes": classes,
        }
