"""Durable ingestion: journal-backed acks + outage-tolerant drainers.

The event server's write path with a journal configured becomes:

    POST /events.json -> validate -> assign event id -> route by
    hash(entity_type, entity_id) -> partition journal append (+ fsync per
    policy) -> 201 {"eventId": ...}

and one background drainer PER PARTITION owns its journal-to-backend
pipe: it reads that partition's undrained records in append order,
pushes ordered batches into the ``EventBackend``, and only then advances
that partition's persisted cursor. A storage outage therefore costs
availability of READS, never of ingestion — the 201 contract is "durably
journaled", the same promise the reference's HBase WAL gave it (and the
posture streaming-log training pipelines take: capture first, apply
later).

Partitioning (ISSUE 9) is the reference's region-server split
(``HBEventsUtil.RowKey`` hash prefix) applied to the whole pipe: appends
to different partitions take different locks and fsync different files
concurrently, and each drainer carries its own circuit breaker — a
poison partition browns out ALONE while the other N-1 keep draining.
Ordering weakens from global to per-entity (one entity always lands in
one partition), which is what training and ``aggregate_properties``
actually rely on.

Failure handling reuses the ``workflow/feedback.py`` pattern, per
partition:

- a closed → open → half-open **circuit breaker** around backend pushes
  (past ``breaker_threshold`` consecutive failures that drainer stops
  hammering and probes once per ``breaker_reset_s``);
- **jittered exponential backoff** between failed pushes so a recovering
  backend is not thundering-herded;
- unlike feedback, drainers NEVER drop: records wait in the journal
  until the backend takes them (backpressure past a partition's journal
  cap is the server's 503, storage/journal.py).

Exactly-once effect: event ids are assigned before the append, and both
built-in backends upsert by id (``INSERT OR REPLACE`` / dict replace) —
a batch that half-landed before a crash or error is simply re-pushed.

Chaos sites: ``eventserver.drain`` fires before every backend push
(async, all partitions) and ``eventserver.drain_partition`` right after
it; additionally a partition-targeted ``eventserver.drain_partition.p<k>``
site fires per drainer so a single partition can be wedged in tests
while its siblings stay healthy (faults.py).

``start()`` replays undrained records of every partition from a previous
process before the server starts accepting traffic (reachable backend),
or leaves them to the background drainers (unreachable backend — the
server still accepts, that is the point).
"""

from __future__ import annotations

import asyncio
import json
import logging
import random
import time
import uuid
from concurrent.futures import ThreadPoolExecutor

from ..obs.metrics import METRICS
from ..obs.trace import current_request_id, trace_event
from ..storage import Storage, event_from_api_dict, event_to_api_dict
from ..storage.journal import JournalFull, PartitionedJournal
from ..storage.partition import entity_key, hash64
from ..obs.breaker import breaker_set as _breaker_set
from ..workflow.admission import backpressure_retry_after_s
from ..faults import FAULTS

log = logging.getLogger("predictionio_tpu.eventserver")

__all__ = ["DurableIngestor", "JournalFull"]

# ISSUE 5: the drain pipe's registry handles. Journal append/fsync
# latency is recorded inside storage/journal.py; this side measures one
# ordered backend push (peek -> insert -> advance) and the queue it
# works off (lag).
_M_DRAIN_BATCH = METRICS.histogram(
    "pio_journal_drain_batch_seconds",
    "one drainer batch: peek + backend push + cursor advance")
_M_JOURNAL_LAG = METRICS.gauge(
    "pio_journal_lag",
    "journaled records not yet pushed to the event backend (all partitions)")
# ISSUE 9: per-partition drain progress/failures — a single wedged
# drainer must be visible as itself, not diluted into the totals
_M_DRAIN_BATCHES_P = METRICS.counter(
    "pio_ingest_drain_batches_total",
    "drain batches pushed, by journal partition",
    labelnames=("partition",))
_M_DRAIN_FAILURES_P = METRICS.counter(
    "pio_ingest_drain_failures_total",
    "drain batch failures, by journal partition",
    labelnames=("partition",))

#: Breaker state severity for the aggregate "ingest" gauge: the worst
#: partition defines the whole pipe's state.
_STATE_RANK = {"closed": 0, "half_open": 1, "open": 2}


class _PartitionState:
    """One drainer's mutable state: breaker, counters, wake, task."""

    __slots__ = ("state", "consecutive_failures", "opened_at", "last_error",
                 "drain_failures", "drained_batches", "breaker_opens",
                 "wake", "task")

    def __init__(self):
        self.state = "closed"  # closed | open | half_open
        self.consecutive_failures = 0
        self.opened_at = 0.0
        self.last_error: str | None = None
        self.drain_failures = 0
        self.drained_batches = 0
        self.breaker_opens = 0
        self.wake: asyncio.Event | None = None
        self.task: asyncio.Task | None = None


class DurableIngestor:
    """Owns the event server's partitioned journal, drainers and
    breakers."""

    def __init__(
        self,
        journal_dir: str,
        *,
        partitions: int = 1,
        fsync: str = "batch",
        max_bytes: int = 256 * 1024 * 1024,
        segment_max_bytes: int | None = None,
        drain_batch: int = 64,
        drain_linger_s: float = 0.005,
        breaker_threshold: int = 5,
        breaker_reset_s: float = 5.0,
        backoff_base_s: float = 0.1,
        backoff_cap_s: float = 2.0,
    ):
        partitions = max(1, int(partitions))
        if segment_max_bytes is None:
            # a handful of segments inside each partition's cap so GC
            # frees space in file-sized steps well before the 503
            # threshold
            per_cap = max(1, max_bytes // partitions)
            segment_max_bytes = min(16 * 1024 * 1024,
                                    max(64 * 1024, per_cap // 4))
        self.journal = PartitionedJournal(
            journal_dir, partitions=partitions, fsync=fsync,
            max_bytes=max_bytes, segment_max_bytes=segment_max_bytes)
        self.partitions = partitions
        self.drain_batch = max(1, drain_batch)
        self.drain_linger_s = max(0.0, drain_linger_s)
        self.breaker_threshold = max(1, breaker_threshold)
        self.breaker_reset_s = breaker_reset_s
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self._closing = False
        self._parts = [_PartitionState() for _ in range(partitions)]
        # appends to distinct partitions fsync distinct files — the pool
        # is what lets those fsyncs overlap instead of queueing on one
        # to_thread worker at a time
        self._pool: ThreadPoolExecutor | None = None
        # aggregate "ingest" breaker gauge = worst partition (kept in
        # sync on every per-partition transition)
        self._agg_state = "closed"
        # EWMA of successful drain-batch wall time across partitions —
        # sizes the dynamic Retry-After on journal-full 503s (lag /
        # drain rate); None until the first batch lands
        self._ewma_drain_s: float | None = None

    # -- ingest-side API ---------------------------------------------------
    def encode(self, event, app_id: int, channel_id: int | None,
               trace: str | None = None) -> bytes:
        """One journal payload. The event id MUST already be assigned —
        it is what makes replay idempotent. The ingress trace id rides
        along (``"t"``) so the drainer's log line — possibly in a later
        process after a crash/replay — still joins the ingress line.
        ``trace`` carries the request id into pool threads, where the
        ingress contextvar is not propagated."""
        assert event.event_id, "journal records require a pre-assigned id"
        d = {"e": event_to_api_dict(event), "a": app_id, "c": channel_id}
        rid = trace if trace is not None else current_request_id()
        if rid:
            d["t"] = rid
        return json.dumps(d, separators=(",", ":")).encode()

    @staticmethod
    def assign_id(event):
        return event if event.event_id else event.with_id(uuid.uuid4().hex)

    def partition_of(self, event) -> int:
        return self.journal.partition_of(event.entity_type, event.entity_id)

    async def submit(self, events, app_id: int,
                     channel_id: int | None) -> tuple[list[str], Exception | None]:
        """Durably append ``events`` (ids already assigned), routed by
        entity hash; per-entity order is preserved. Returns
        ``(statuses, error)`` with one status per event, in order:

        - ``"ok"``    — journaled + synced per policy, safe to ack 201
        - ``"full"``  — that event's partition is at capacity (503 +
          Retry-After; the OTHER partitions' events still ack)
        - ``"error"`` — append or fsync failed (500); ``error`` holds the
          first such exception for the log line

        Appends to distinct partitions run concurrently (distinct locks,
        distinct fsync targets)."""
        events = list(events)
        if not events:
            return [], None
        rid = current_request_id()
        groups: dict[int, list[int]] = {}
        if self.partitions == 1:
            groups[0] = list(range(len(events)))
        else:
            # one native batch hash for the whole request — identical
            # routing to per-event shard_of at a fraction of the cost
            hs = hash64([entity_key(e.entity_type, e.entity_id)
                         for e in events])
            n = self.partitions
            for i, h in enumerate(hs.tolist()):
                groups.setdefault(h % n, []).append(i)
        statuses = ["error"] * len(events)
        err: Exception | None = None
        items = list(groups.items())
        if len(items) == 1:
            p, idxs = items[0]
            outs = [await asyncio.to_thread(
                self._append_partition, p, [events[i] for i in idxs],
                app_id, channel_id, rid)]
        else:
            loop = asyncio.get_running_loop()
            pool = self._ensure_pool()
            outs = await asyncio.gather(*[
                loop.run_in_executor(
                    pool, self._append_partition, p,
                    [events[i] for i in idxs], app_id, channel_id, rid)
                for p, idxs in items])
        woke = False
        for (p, idxs), (sts, perr) in zip(items, outs):
            err = err or perr
            for i, s in zip(idxs, sts):
                statuses[i] = s
            if "ok" in sts:
                woke = True
                wake = self._parts[p].wake
                if wake is not None:
                    wake.set()
        if woke:
            _M_JOURNAL_LAG.set(self.journal.lag)
        return statuses, err

    def _ensure_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.partitions,
                thread_name_prefix="pio-ingest")
        return self._pool

    def _append_partition(self, partition: int, events, app_id: int,
                          channel_id: int | None,
                          trace: str | None) -> tuple[list[str], Exception | None]:
        """Encode + append + batch-fsync one partition's slice of a
        submit. Runs in a worker thread; touches only ``partition``."""
        statuses: list[str] = []
        err: Exception | None = None
        n = 0
        try:
            for e in events:
                payload = self.encode(e, app_id, channel_id, trace=trace)
                self.journal.append(payload, partition)
                statuses.append("ok")
                n += 1
        except JournalFull:
            # appended prefix still acks; the rest is THIS partition's
            # backpressure — sibling partitions are unaffected
            statuses.extend(["full"] * (len(events) - len(statuses)))
        except Exception as e:  # noqa: BLE001 — injected/disk faults -> 500
            err = e
            statuses.extend(["error"] * (len(events) - len(statuses)))
        # whatever happened after them, the appended records must be
        # durable before their 201s leave (policy `always` synced inline)
        if n and self.journal.fsync_policy == "batch":
            try:
                self.journal.sync(partition)
            except Exception as e:  # noqa: BLE001
                # unsynced appends may not survive a power cut — do not ack
                return ["error"] * len(events), err or e
        return statuses, err

    # -- breaker (per partition) -------------------------------------------
    def _subsystem(self, p: int) -> str:
        return "ingest" if self.partitions == 1 else f"ingest.p{p}"

    def _publish_agg_breaker(self) -> None:
        """Keep the aggregate "ingest" breaker gauge = worst partition,
        so dashboards built against the single-journal metric keep
        telling the truth."""
        if self.partitions == 1:
            return  # the lone partition already publishes as "ingest"
        worst = max((st.state for st in self._parts),
                    key=_STATE_RANK.__getitem__)
        if worst != self._agg_state:
            _breaker_set("ingest", worst, prev=self._agg_state)
            self._agg_state = worst

    def _breaker_allows(self, p: int, now: float) -> bool:
        st = self._parts[p]
        if st.state == "closed":
            return True
        if st.state == "open":
            if now - st.opened_at >= self.breaker_reset_s:
                st.state = "half_open"
                _breaker_set(self._subsystem(p), "half_open", prev="open")
                self._publish_agg_breaker()
                return True
            return False
        return True  # half_open: this drainer IS the single probe

    def _on_push_success(self, p: int) -> None:
        st = self._parts[p]
        if st.state != "closed":
            log.info("ingest drain breaker closed (partition %d, backend "
                     "recovered, lag=%d)", p, self.journal.lag_of(p))
            _breaker_set(self._subsystem(p), "closed", prev=st.state)
        st.state = "closed"
        st.consecutive_failures = 0
        st.last_error = None
        self._publish_agg_breaker()

    def _on_push_failure(self, p: int, err: Exception) -> None:
        st = self._parts[p]
        st.drain_failures += 1
        st.consecutive_failures += 1
        st.last_error = str(err)
        _M_DRAIN_FAILURES_P.inc(partition=str(p))
        if st.state == "half_open" or (
                st.state == "closed"
                and st.consecutive_failures >= self.breaker_threshold):
            if st.state != "open":
                st.breaker_opens += 1
                _breaker_set(self._subsystem(p), "open", prev=st.state)
                log.warning(
                    "ingest drain breaker OPEN on partition %d after %d "
                    "consecutive failures (last: %s); events keep acking "
                    "into the journal, partition lag=%d", p,
                    st.consecutive_failures, err, self.journal.lag_of(p))
            st.state = "open"
            st.opened_at = time.monotonic()
            self._publish_agg_breaker()

    # -- drain loops -------------------------------------------------------
    async def _drain_once(self, p: int = 0) -> bool:
        """Push one ordered batch from partition ``p``; True on progress
        (or nothing to do)."""
        st = self._parts[p]
        t0 = time.perf_counter()
        records, pos = await asyncio.to_thread(
            self.journal.peek_batch, p, self.drain_batch)
        if not records:
            return True
        try:
            # chaos sites: arm an error on `eventserver.drain` (or the
            # new alias `eventserver.drain_partition`) for a
            # deterministic all-partition backend outage, or on the
            # partition-targeted twin to wedge ONE drainer while its
            # siblings stay healthy (faults.py)
            await FAULTS.afire("eventserver.drain")
            await FAULTS.afire("eventserver.drain_partition")
            await FAULTS.afire(f"eventserver.drain_partition.p{p}")
            traces = await asyncio.to_thread(self._push_records, records)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — any backend failure retries
            self._on_push_failure(p, e)
            return False
        await asyncio.to_thread(self.journal.advance, p, pos)
        st.drained_batches += 1
        _M_DRAIN_BATCHES_P.inc(partition=str(p))
        self._on_push_success(p)
        dt = time.perf_counter() - t0
        _M_DRAIN_BATCH.record(dt)
        self._ewma_drain_s = (dt if self._ewma_drain_s is None
                              else 0.7 * self._ewma_drain_s + 0.3 * dt)
        _M_JOURNAL_LAG.set(self.journal.lag)
        # the drainer's half of the event-path join: each journaled trace
        # id reappears here, after the backend upsert committed
        trace_event("ingest.drain_batch", trace=None,
                    traces=[t for t in traces if t], partition=p,
                    records=len(records), ms=round(dt * 1e3, 3))
        return True

    def _push_records(self, records: list[bytes]) -> list:
        """Decode + insert in journal order, grouping consecutive records
        of one (app, channel) into one single-transaction backend batch
        call. Returns the journaled trace ids (for the drain-batch trace
        line)."""
        backend = Storage.get_events()
        group: list = []
        group_key: tuple[int, int | None] | None = None
        traces: list = []

        def flush():
            if group:
                backend.insert_batch(group, group_key[0], group_key[1])
                group.clear()

        for raw in records:
            d = json.loads(raw.decode())
            traces.append(d.get("t"))
            key = (d["a"], d["c"])
            if key != group_key:
                flush()
                group_key = key
            group.append(event_from_api_dict(d["e"]))
        flush()
        return traces

    async def _drain_loop(self, p: int) -> None:
        st = self._parts[p]
        assert st.wake is not None
        while not self._closing:
            if self.journal.lag_of(p) == 0:
                st.wake.clear()
                if self.journal.lag_of(p) == 0:  # re-check: append raced
                    await st.wake.wait()
                continue
            now = time.monotonic()
            if not self._breaker_allows(p, now):
                await asyncio.sleep(
                    min(0.2, max(0.01, self.breaker_reset_s / 10)))
                continue
            if (self.drain_linger_s
                    and self.journal.lag_of(p) < self.drain_batch):
                # linger to coalesce in-flight appends into one batch:
                # draining 1-2 records at a time pays a cursor fsync per
                # tiny batch, competing with the append fsyncs for the
                # same disk (and the decode CPU for the same GIL)
                await asyncio.sleep(self.drain_linger_s)
            ok = await self._drain_once(p)
            if not ok:
                backoff = min(self.backoff_cap_s, self.backoff_base_s *
                              (2 ** min(st.consecutive_failures, 8)))
                # full jitter, same rationale as the feedback retries
                await asyncio.sleep(backoff * (0.5 + random.random() / 2))

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> None:
        """Startup replay, then one background drainer per partition.
        Replay pushes every record left by the previous process BEFORE
        the server takes traffic; if the backend is down the server
        starts anyway — new events ack into the journals behind the old
        ones, per-entity order intact."""
        replayed = 0
        for p in range(self.partitions):
            self._parts[p].wake = asyncio.Event()
            while self.journal.lag_of(p) > 0:
                before = self.journal.lag_of(p)
                if not await self._drain_once(p):
                    log.warning(
                        "startup replay deferred on partition %d (%d "
                        "records pending): backend unreachable (%s); "
                        "draining in background", p, self.journal.lag_of(p),
                        self._parts[p].last_error)
                    break
                replayed += before - self.journal.lag_of(p)
        if replayed:
            log.info("startup replay: %d journaled records pushed", replayed)
        for p in range(self.partitions):
            self._parts[p].task = asyncio.create_task(self._drain_loop(p))

    async def aclose(self) -> None:
        """Stop the drainers and close the journal (final fsync).
        Undrained records stay on disk for the next start's replay.
        Idempotent."""
        self._closing = True
        for st in self._parts:
            if st.task is None:
                continue
            if st.wake is not None:
                st.wake.set()
            st.task.cancel()
            try:
                await st.task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            st.task = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        await asyncio.to_thread(self.journal.close)

    # -- surfaces ----------------------------------------------------------
    def fill_fraction(self) -> float:
        """Fullness in [0, 1] of the FULLEST partition — the admission
        controller's ``journal`` signal (sheds ingest shortly BEFORE the
        hard journal-full 503; a single hot partition must trip it)."""
        return self.journal.fill_fraction()

    def drain_rate_per_s(self) -> float | None:
        """Records/sec one drainer clears, or None before the first
        successful batch (a broken-breaker drainer keeps its last
        healthy estimate — the backlog math stays meaningful). Kept
        per-drainer (not x N) so the Retry-After stays conservative when
        only some partitions are healthy."""
        if self._ewma_drain_s is None or self._ewma_drain_s <= 0:
            return None
        return self.drain_batch / self._ewma_drain_s

    def retry_after_s(self) -> float:
        """Dynamic journal-full Retry-After: lag / drain rate (jittered,
        capped) via the shared overload-control helper — the same pacing
        the admission 429s use, instead of the old fixed constant."""
        return backpressure_retry_after_s(
            self.journal.lag, self.drain_rate_per_s())

    @property
    def degraded(self) -> bool:
        """ANY partition's backend push path is failing (breaker not
        closed). Acks still flow — degraded, not down."""
        return any(st.state != "closed" for st in self._parts)

    def _worst_state(self) -> str:
        return max((st.state for st in self._parts),
                   key=_STATE_RANK.__getitem__)

    def stats(self) -> dict:
        j = self.journal.stats()
        per_j = {d["partition"]: d for d in j.get("perPartition", [])}
        last_error = next((st.last_error for st in reversed(self._parts)
                           if st.last_error), None)
        return {
            "journal": j,
            "drain": {
                # aggregate keys keep the single-journal shape: state is
                # the worst partition, counters are sums
                "breakerState": self._worst_state(),
                "breakerOpens": sum(st.breaker_opens for st in self._parts),
                "consecutiveFailures": max(
                    st.consecutive_failures for st in self._parts),
                "failures": sum(st.drain_failures for st in self._parts),
                "drainedBatches": sum(
                    st.drained_batches for st in self._parts),
                "lastError": last_error,
                "partitions": [
                    {
                        "partition": p,
                        "breakerState": st.state,
                        "breakerOpens": st.breaker_opens,
                        "consecutiveFailures": st.consecutive_failures,
                        "failures": st.drain_failures,
                        "drainedBatches": st.drained_batches,
                        "lastError": st.last_error,
                        "lag": per_j.get(p, {}).get("lag", 0),
                        "fill": per_j.get(p, {}).get("fill", 0.0),
                    }
                    for p, st in enumerate(self._parts)
                ],
            },
        }

    def health(self) -> dict:
        """The event server's /health.json body (engine-server parity:
        status/live/ready + the why). Degrades when ANY partition's
        breaker opens — a poison partition is a real brownout even while
        its siblings drain."""
        j = self.journal.stats()
        return {
            "status": "degraded" if self.degraded else "ok",
            "live": True,
            "ready": True,
            "journal": {
                "lag": j["lag"],
                "sizeBytes": j["sizeBytes"],
                "maxBytes": j["maxBytes"],
                "unsyncedBytes": j["unsyncedBytes"],
                "fsyncPolicy": j["fsyncPolicy"],
                "partitions": j["partitions"],
            },
            "drain": {
                "breakerState": self._worst_state(),
                "breakerOpens": sum(st.breaker_opens for st in self._parts),
                "consecutiveFailures": max(
                    st.consecutive_failures for st in self._parts),
                "lastError": next(
                    (st.last_error for st in reversed(self._parts)
                     if st.last_error), None),
            },
            "partitions": [
                {"partition": p, "breakerState": st.state,
                 "lag": self.journal.lag_of(p),
                 "fill": round(self.journal.fill_of(p), 4)}
                for p, st in enumerate(self._parts)
            ],
        }
