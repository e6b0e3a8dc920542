"""Streaming online learning: the journal-tailing fold-in updater.

ISSUE 10 closes the feedback -> retrain -> redeploy loop (reference
ServerActor/MasterActor) in streaming form: a user unseen at train time
gets personalized serving within seconds of their first events, without
a retrain. Google's ads infrastructure makes continuous training off the
event stream the default posture (arXiv:2501.10546); this is that loop
scaled to the single-box stack.

The pipeline, end to end::

    event server --append--> partitioned journal (PR 9 WAL)
                                 |                     drain cursor ->
                                 |  (drainer, untouched)   event store
                                 v
                     JournalFollower (follow-<name>.json per partition,
                       independent READ-ONLY cursor; storage/journal.py)
                                 |
                                 v
        StreamingUpdater.run_cycle: group events per user
                                 |
                                 v
        ALSModel.fold_in_users — ONE batched normal-equations solve
          for the whole batch (models/als.py; host float64 by default
          so the published factor bitwise-matches ``fold_in_user``)
                                 |
                     eval gate: leave-one-out hit@k on the batch's
                     holdout slice (controller.metric.AverageMetric);
                     regression past --eval-gate skips the publish
                                 |
                                 v
        POST /reload/delta on the deployed engine server — copy-on-write
          user-factor patch under the reload lock (create_server.py);
          item factors untouched, ANN index and compiled retrieval
          programs stay valid

Delivery semantics mirror the drainer's exactly-once discipline: the
follow cursor commits only after the publish succeeded or the gate
DELIBERATELY skipped the batch. A transient publish failure (engine
server down, breaker open, injected ``stream.publish`` fault) holds the
cursor, so a crash/restart replays the same events — and replay is
idempotent because fold-in is a deterministic per-user recomputation
from the model's item factors, not an accumulation.

Supervision is the training stack's (workflow/supervisor.py): errors are
classified transient/fatal via ``classify_error``; transient cycle
failures back off with jitter and retry forever, fatal ones raise to the
operator. The publish path carries its own circuit breaker
(``pio_breaker_state{subsystem="stream"}``), the same closed -> open ->
half-open contract as the ingest drainer's.

Fault sites: ``stream.tail`` / ``stream.fold_in`` / ``stream.publish``
(faults.py). Trace ids ride from the WAL record (the ``"t"``
field stamped at ingress) through the ``stream.tail`` / ``stream.fold_in``
trace events into the patch request's ``X-PIO-Request-ID`` header, so one
grep joins ingress -> journal -> fold-in -> serve.
"""

from __future__ import annotations

import json
import logging
import random
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from ..controller.metric import AverageMetric
from ..faults import FAULTS, FaultInjected
from ..obs.breaker import breaker_set
from ..obs.metrics import METRICS
from ..obs.trace import TRACE_HEADER, trace_event
from ..obs.training import TRAINING
from ..storage.journal import JournalFollower
from .supervisor import classify_error

log = logging.getLogger("predictionio_tpu.workflow.streaming")

__all__ = ["StreamingUpdater", "HoldoutHitRate"]

# ISSUE 10 metric surface (PR-5 registry). Tail lag is per partition —
# one hot partition lagging behind is the signal the triage table keys
# on; the rest are process-wide.
_M_LAG = METRICS.gauge(
    "pio_stream_tail_lag",
    "journal records at/after the follow cursor, per partition",
    labelnames=("partition",))
_M_FOLD = METRICS.histogram(
    "pio_stream_fold_in_seconds",
    "batched fold-in solve latency per updater batch")
_M_USERS = METRICS.counter(
    "pio_stream_users_patched_total",
    "user factors published to the engine server via /reload/delta")
_M_GATE = METRICS.counter(
    "pio_stream_gate_decisions_total",
    "eval-gate decisions by outcome (publish/skip/unevaluated/ungated)",
    labelnames=("decision",))
_M_EPOCH = METRICS.gauge(
    "pio_stream_patch_epoch",
    "latest patch epoch acked by the engine server's /reload/delta")


class HoldoutHitRate(AverageMetric):
    """hit@k over the gate's holdout slice: q = user id, p = the top-k
    item ids scored by a candidate factor, a = the held-out item. The
    existing evaluation scaffolding (controller/metric.py) does the
    aggregation — the gate is just another Metric over (q, p, a)."""

    def calculate_qpa(self, q, p, a) -> float:
        return 1.0 if a in p else 0.0


class _PublishBreaker:
    """closed -> open -> half-open breaker on the delta-publish path —
    the ingest drainer's contract (api/ingest.py), reported through the
    shared ``pio_breaker_state{subsystem="stream"}`` family."""

    def __init__(self, threshold: int, reset_s: float):
        self.threshold = max(1, int(threshold))
        self.reset_s = reset_s
        self.state = "closed"
        self.consecutive_failures = 0
        self.opened_at = 0.0
        self.opens = 0
        breaker_set("stream", "closed")

    def allows(self, now: float) -> bool:
        if self.state == "closed":
            return True
        if self.state == "open" and now - self.opened_at >= self.reset_s:
            prev, self.state = self.state, "half_open"
            breaker_set("stream", "half_open", prev=prev)
        return self.state == "half_open"

    def success(self) -> None:
        prev = self.state
        self.state, self.consecutive_failures = "closed", 0
        if prev != "closed":
            log.info("stream publish breaker closed (engine server is "
                     "answering again)")
            breaker_set("stream", "closed", prev=prev)

    def failure(self, now: float) -> None:
        self.consecutive_failures += 1
        if self.state == "half_open" or (
                self.state == "closed"
                and self.consecutive_failures >= self.threshold):
            prev, self.state = self.state, "open"
            self.opened_at = now
            self.opens += 1
            log.warning(
                "stream publish breaker OPEN after %d consecutive "
                "failure(s); probing every %.1fs",
                self.consecutive_failures, self.reset_s)
            breaker_set("stream", "open", prev=prev)


class StreamingUpdater:
    """Tail the journal, fold events into user factors, hot-patch the
    deployed engine server. One instance = one follow-cursor family
    (``follow-<name>.json``); run several with distinct names for
    independent consumers.

    ``model`` is the trained model fold-in solves against — anything
    with ``fold_in_users`` / ``fold_in_user`` / ``item_ids`` (ALSModel).
    ``solver="host"`` (default) publishes factors that bitwise-match the
    single-user ``fold_in_user`` reference; ``"device"`` dispatches the
    jitted batched Cholesky kernel instead (f32 — fast, not bitwise).
    """

    def __init__(
        self,
        model,
        journal_dir,
        engine_url: str,
        *,
        name: str = "stream",
        partitions: int | None = None,
        batch_window_ms: float = 500.0,
        max_records: int = 1024,
        eval_gate: float | None = None,
        eval_k: int = 10,
        solver: str = "host",
        breaker_threshold: int = 5,
        breaker_reset_s: float = 5.0,
        backoff_base_s: float = 0.5,
        backoff_cap_s: float = 30.0,
        publish_timeout_s: float = 10.0,
        variant: str | None = None,
        rng: random.Random | None = None,
    ):
        self.model = model
        self.follower = JournalFollower(journal_dir, name=name,
                                        partitions=partitions)
        self.engine_url = engine_url.rstrip("/")
        self.batch_window_s = max(0.0, batch_window_ms) / 1e3
        self.max_records = max(1, int(max_records))
        self.eval_gate = eval_gate
        self.eval_k = max(1, int(eval_k))
        self.solver = solver
        self.breaker = _PublishBreaker(breaker_threshold, breaker_reset_s)
        self.backoff_base_s = max(0.0, backoff_base_s)
        self.backoff_cap_s = backoff_cap_s
        self.publish_timeout_s = publish_timeout_s
        # ISSUE 14: which serving variant this updater feeds. Stamped
        # into every /reload/delta payload so a multi-variant server
        # routes the patch to the right bounded table; None preserves
        # the single-variant behavior (patch lands on the live variant).
        self.variant = variant
        self._rng = rng or random.Random()
        self._stop = threading.Event()
        # counters mirrored into stats() for tests and `pio stream` logs
        self.cycles = 0
        self.events_seen = 0
        self.events_skipped = 0  # records with nothing foldable in them
        self.users_patched = 0
        self.gate_skips = 0
        self.publish_failures = 0
        self.last_epoch = 0
        self.last_gate: dict | None = None
        # ISSUE 17: when engine_url is a fleet ROUTER, the publish
        # response carries per-replica fan-out outcomes; the latest one
        # is surfaced in stats() so `pio stream` logs show which
        # replicas took the patch and which will reconcile by epoch
        self.last_fanout: dict | None = None

    # -- event parsing -----------------------------------------------------
    @staticmethod
    def _parse_record(payload: bytes):
        """One WAL record -> ``(user, item, rating, trace_id)`` or None.
        The journal payload is the drainer's (api/ingest.py ``encode``):
        ``{"e": <api event dict>, "a": app, "c": channel, "t": trace}``.
        Foldable events are user->item interactions; ``$set``-style
        reserved events and malformed records are skipped (counted)."""
        try:
            d = json.loads(payload)
        except (ValueError, UnicodeDecodeError):
            return None
        e = d.get("e") or {}
        uid, iid = e.get("entityId"), e.get("targetEntityId")
        name = str(e.get("event") or "")
        if not uid or not iid or name.startswith("$"):
            return None
        props = e.get("properties") or {}
        try:
            rating = float(props.get("rating", 1.0))
        except (TypeError, ValueError):
            rating = 1.0
        return str(uid), str(iid), rating, d.get("t")

    def _group(self, records: list[bytes], partition: int):
        """Per-user delta accumulation for one polled batch: ordered
        ``{user: {item: rating}}`` (a later event for the same item
        supersedes the earlier one, matching training's one-cell-per-pair
        view) plus each user's most recent trace id."""
        users: dict[str, dict[str, float]] = {}
        traces: dict[str, str] = {}
        for payload in records:
            self.events_seen += 1
            parsed = self._parse_record(payload)
            if parsed is None:
                self.events_skipped += 1
                continue
            uid, iid, rating, trace = parsed
            users.setdefault(uid, {})[iid] = rating
            if trace:
                traces[uid] = trace
                trace_event("stream.tail", trace=trace, user=uid,
                            item=iid, partition=partition)
        return users, traces

    # -- eval gate ---------------------------------------------------------
    def _gate_decision(self, users: dict[str, dict[str, float]],
                       kept_uids: list[str]) -> str:
        """Leave-one-out promotion gate: for each batch user with >= 2
        known-item events, hold out the last item, fold in from the
        rest, and score hit@k of the held item against the CURRENT
        serving factor's hit@k (unknown user = guaranteed miss — the
        fold-in only has to beat nothing). Skips the publish when the
        batch metric regresses past ``eval_gate``."""
        if self.eval_gate is None:
            return "ungated"
        m = self.model
        folded_qpa: list[tuple[str, list, str]] = []
        base_qpa: list[tuple[str, list, str]] = []
        for uid in kept_uids:
            known = [(i, r) for i, r in users[uid].items()
                     if i in m.item_ids]
            if len(known) < 2:
                continue
            held = known[-1][0]
            f = m.fold_in_user([i for i, _ in known[:-1]],
                               [r for _, r in known[:-1]])
            if f is None:
                continue
            folded_qpa.append(
                (uid, [i for i, _ in m.top_n_from_catalog(f, self.eval_k)],
                 held))
            row = m.user_ids.get(uid)
            base_top = ([i for i, _ in m.top_n_from_catalog(
                m.user_factors[row], self.eval_k)] if row is not None else [])
            base_qpa.append((uid, base_top, held))
        if not folded_qpa:
            return "unevaluated"
        metric = HoldoutHitRate()
        folded = metric.calculate(None, [(None, folded_qpa)])
        baseline = metric.calculate(None, [(None, base_qpa)])
        self.last_gate = {"holdoutUsers": len(folded_qpa),
                          "folded": folded, "baseline": baseline,
                          "threshold": self.eval_gate}
        return "publish" if folded >= baseline - self.eval_gate else "skip"

    def _observe_convergence(self, kept_uids: list[str], factors,
                             fold_s: float) -> None:
        """Stream-side convergence telemetry (ISSUE 12): the mean
        relative factor-delta norm over the batch's already-known users
        (how hard fold-in is moving the serving factors) plus the gate's
        holdout metric as a loss signal (1 - hit@k = holdout miss rate).
        Pure bookkeeping — never fails the cycle."""
        try:
            m = self.model
            deltas = []
            for j, u in enumerate(kept_uids):
                row = m.user_ids.get(u)
                if row is None:
                    continue
                old = np.asarray(m.user_factors[row], np.float32)
                denom = float(np.linalg.norm(old))
                if denom > 0.0:
                    deltas.append(
                        float(np.linalg.norm(factors[j] - old)) / denom)
            loss = None
            gate = self.last_gate
            if gate and gate.get("folded") is not None:
                loss = 1.0 - float(gate["folded"])
            TRAINING.observe(
                "stream", self.cycles, loss=loss,
                delta_norm=(sum(deltas) / len(deltas)) if deltas else None,
                step_seconds=fold_s)
        except Exception:
            pass

    # -- publish path ------------------------------------------------------
    def _post(self, patches: dict[str, list[float]],
              trace: str | None) -> dict:
        payload: dict = {"users": patches}
        if self.variant is not None:
            # ISSUE 14: target variant. The server 400s (fatal here — no
            # point replaying) when the variant is unknown or retired.
            payload["variant"] = self.variant
        if self.last_gate is not None:
            # ride the latest eval-gate hit@k along: the server keeps it
            # per variant, so the dashboard's A/B view can show each
            # variant's online quality next to its traffic share
            payload["gate"] = {**self.last_gate, "k": self.eval_k}
        body = json.dumps(payload).encode()
        headers = {"Content-Type": "application/json"}
        if trace:
            headers[TRACE_HEADER] = trace
        req = urllib.request.Request(
            f"{self.engine_url}/reload/delta", data=body,
            headers=headers, method="POST")
        with urllib.request.urlopen(req,
                                    timeout=self.publish_timeout_s) as resp:
            return json.loads(resp.read().decode())

    @staticmethod
    def _classify_publish(exc: BaseException) -> str:
        """Publish-path refinement of ``classify_error``: 5xx/408/429 and
        every connection-level failure are transient (the engine server
        restarts, the breaker paces the retries); other HTTP codes are
        fatal — a 400 means the patch itself is malformed and replaying
        it forever would wedge the partition."""
        if isinstance(exc, FaultInjected):
            return "transient"
        if isinstance(exc, urllib.error.HTTPError):
            return ("transient" if exc.code in (408, 429) or exc.code >= 500
                    else "fatal")
        if isinstance(exc, (urllib.error.URLError, ConnectionError,
                            TimeoutError, OSError)):
            return "transient"
        return classify_error(exc)

    def _backoff(self) -> float:
        i = min(self.breaker.consecutive_failures, 10)
        raw = min(self.backoff_cap_s, self.backoff_base_s * (2 ** i))
        return raw * (0.5 + self._rng.random() / 2)

    def _publish_batch(self, patches: dict[str, list[float]],
                       trace: str | None, *, partition: int) -> bool:
        """POST one fold-in batch. True = applied (commit the cursor);
        False = transient failure or breaker-open (hold the cursor, the
        batch replays). Fatal errors raise."""
        if not self.breaker.allows(time.monotonic()):
            log.debug("stream publish breaker open; holding partition %d "
                      "cursor", partition)
            self._sleep(self._backoff())
            return False
        try:
            FAULTS.fire("stream.publish")
            out = self._post(patches, trace)
        except Exception as exc:  # noqa: BLE001 — classified below
            self.publish_failures += 1
            self.breaker.failure(time.monotonic())
            if self._classify_publish(exc) != "transient":
                log.error("fatal stream publish failure: %r", exc)
                raise
            delay = self._backoff()
            log.warning(
                "transient stream publish failure on partition %d "
                "(%r); cursor held, batch replays in >= %.2fs",
                partition, exc, delay)
            self._sleep(delay)
            return False
        self.breaker.success()
        self.last_epoch = int(out.get("epoch", 0))
        _M_EPOCH.set(self.last_epoch)
        # ISSUE 17: a fleet router answers with per-replica fan-out
        # outcomes. Partial delivery still commits the cursor — the
        # router journaled this epoch and reconciles every laggard
        # before it rejoins hashed traffic — but the laggards are worth
        # a log line and a stats() surface.
        fanout = out.get("replicas")
        if isinstance(fanout, dict):
            self.last_fanout = fanout
            lagging = sorted(n for n, v in fanout.items()
                             if not (isinstance(v, dict) and v.get("ok")))
            if lagging:
                log.warning(
                    "fleet fan-out epoch %d partial: replica(s) %s "
                    "lagging (router reconciles them from its journal)",
                    self.last_epoch, ", ".join(lagging))
        trace_event("stream.publish", trace=trace, partition=partition,
                    users=len(patches), epoch=self.last_epoch)
        return True

    # -- the cycle ---------------------------------------------------------
    def run_cycle(self) -> dict:
        """One tail -> fold -> gate -> publish pass over every partition.
        Returns a summary dict (polled/published/skipped counts)."""
        self.cycles += 1
        summary = {"polled": 0, "published": 0, "gateSkipped": 0}
        for k in range(self.follower.num_partitions):
            FAULTS.fire("stream.tail")
            records, pos = self.follower.poll(k, self.max_records)
            _M_LAG.set(float(self.follower.lag(k)), partition=str(k))
            if not records:
                continue
            summary["polled"] += len(records)
            users, traces = self._group(records, k)
            if not users:
                # nothing foldable in the whole poll ($set traffic,
                # malformed records): consumed, advance past it
                self.follower.commit(k, pos)
                continue
            uids = list(users)
            batch = [(list(users[u].keys()), list(users[u].values()))
                     for u in uids]
            FAULTS.fire("stream.fold_in")
            t0 = time.perf_counter()
            factors, kept = self.model.fold_in_users(batch,
                                                     solver=self.solver)
            fold_s = time.perf_counter() - t0
            _M_FOLD.record(fold_s)
            kept_uids = [u for u, keep in zip(uids, kept) if keep]
            for u in kept_uids:
                trace_event("stream.fold_in", trace=traces.get(u), user=u,
                            partition=k, items=len(users[u]))
            if not kept_uids:
                # every event referenced unknown items — nothing to
                # publish, but the records ARE consumed
                self.follower.commit(k, pos)
                continue
            decision = self._gate_decision(users, kept_uids)
            _M_GATE.inc(decision=decision)
            self._observe_convergence(kept_uids, factors, fold_s)
            if decision == "skip":
                self.gate_skips += 1
                summary["gateSkipped"] += len(kept_uids)
                log.warning(
                    "eval gate SKIPPED publishing %d user(s) on partition "
                    "%d: %s", len(kept_uids), k, self.last_gate)
                # a deliberate skip still advances: replaying the same
                # regressing batch forever would wedge the partition
                self.follower.commit(k, pos)
                continue
            patches = {u: factors[j].tolist()
                       for j, u in enumerate(kept_uids)}
            trace = next((traces[u] for u in kept_uids if u in traces),
                         None)
            if not self._publish_batch(patches, trace, partition=k):
                continue  # cursor held — the batch replays
            self.users_patched += len(patches)
            _M_USERS.inc(len(patches))
            self.follower.commit(k, pos)
            summary["published"] += len(patches)
        return summary

    def run_forever(self) -> None:
        """The supervised daemon loop (`pio stream`): cycle every batch
        window; transient failures (injected faults, journal races, a
        down engine server) back off with jitter and retry, fatal ones
        raise to the operator."""
        log.info(
            "streaming updater started: %d partition(s), window %.0f ms, "
            "gate %s, solver %s -> %s",
            self.follower.num_partitions, self.batch_window_s * 1e3,
            self.eval_gate if self.eval_gate is not None else "off",
            self.solver, self.engine_url)
        while not self._stop.is_set():
            try:
                self.run_cycle()
            except Exception as exc:  # noqa: BLE001 — classified below
                if classify_error(exc) != "transient":
                    raise
                delay = self._backoff()
                log.warning(
                    "transient streaming-cycle failure; retrying in "
                    "%.2fs: %r", delay, exc)
                self._sleep(delay)
                continue
            self._stop.wait(self.batch_window_s)

    def stop(self) -> None:
        self._stop.set()

    def _sleep(self, delay: float) -> None:
        self._stop.wait(delay)  # interruptible by stop()

    def stats(self) -> dict:
        return {
            "variant": self.variant,
            "cycles": self.cycles,
            "eventsSeen": self.events_seen,
            "eventsSkipped": self.events_skipped,
            "usersPatched": self.users_patched,
            "gateSkips": self.gate_skips,
            "publishFailures": self.publish_failures,
            "patchEpoch": self.last_epoch,
            "lastGate": self.last_gate,
            "lastFanout": self.last_fanout,
            "breaker": {
                "state": self.breaker.state,
                "opens": self.breaker.opens,
                "consecutiveFailures": self.breaker.consecutive_failures,
            },
            "lag": {str(k): self.follower.lag(k)
                    for k in range(self.follower.num_partitions)},
            "convergence": TRAINING.snapshot().get("stream"),
        }
