"""Golden-traffic capture: a sampled, bounded ring of request/response/
provenance triples persisted to an on-disk capture journal.

ISSUE 13: the serving hot path is about to be rebuilt (device-resident
dispatch, multi-engine variants), and "same answers on real traffic" is
the gate every rewrite must pass. This module is the capture half of
that harness: every served query can be recorded — request (the
EFFECTIVE query, post brownout clamp, so replay is deterministic),
response body, HTTP status, latency, and the provenance envelope naming
the exact model/config that produced it (obs/replay.py re-issues and
diffs).

Design:

- **Hot path is a deque append.** ``record()`` samples, builds one dict
  and appends it to a bounded ring under a lock — no serialization, no
  I/O. What capture costs a served request is not measured on the
  chip.
- **Persistence reuses the WAL.** The ring flushes to an
  ``EventJournal`` (storage/journal.py) — the same CRC-framed segment
  format, torn-tail repair and rotation discipline the ingestion WAL
  already proved. Flushes happen when the ring fills (rotation), on
  flight-recorder incidents (the requests that led in are exactly the
  golden traffic worth keeping), on ``pio capture stop``, and at close.
- **Bounded as a disk ring.** The capture journal never backpressures
  serving: on ``JournalFull`` the OLDEST captured segments are released
  (cursor advance + segment GC) to make room — drop-oldest, matching
  the in-memory ring's semantics, instead of the WAL's 503.
- **Readable offline.** ``iter_capture()`` reads a capture directory
  without touching the writer's cursor (``storage/journal.py
  iter_journal_records``) — `pio capture export` and `pio replay`
  consume it.

Counters/gauges ride the PR-5 registry (``pio_capture_*``, catalogued
in docs/operations.md).
"""

from __future__ import annotations

import json
import logging
import random
import threading
import time
from collections import deque
from pathlib import Path
from typing import Iterator

from ..storage.journal import EventJournal, JournalFull, iter_journal_records
from .metrics import METRICS

log = logging.getLogger("predictionio_tpu.capture")

__all__ = ["CaptureRing", "iter_capture"]

_M_RECORDS = METRICS.counter(
    "pio_capture_records_total",
    "capture decisions per served request "
    "(captured/sampled_out/dropped)",
    labelnames=("outcome",))
_M_FLUSHES = METRICS.counter(
    "pio_capture_flushes_total",
    "capture-ring flushes to the on-disk journal, by trigger "
    "(ring_full/incident/manual/close)",
    labelnames=("trigger",))
_G_RING = METRICS.gauge(
    "pio_capture_ring_records",
    "records currently buffered in the in-memory capture ring")
_G_ENABLED = METRICS.gauge(
    "pio_capture_enabled",
    "1 while golden-traffic capture is recording")
_G_BYTES = METRICS.gauge(
    "pio_capture_journal_bytes",
    "on-disk bytes held by the capture journal (bounded drop-oldest)")


class CaptureRing:
    """Sampled request/response/provenance capture with journal spill."""

    def __init__(
        self,
        directory: str,
        *,
        sample: float = 1.0,
        ring_capacity: int = 256,
        max_bytes: int = 64 * 1024 * 1024,
        segment_max_bytes: int | None = None,
        enabled: bool = True,
    ):
        self.directory = str(directory)
        self.sample = min(1.0, max(0.0, float(sample)))
        self.ring_capacity = max(1, int(ring_capacity))
        # small segments relative to the cap: drop-oldest works at
        # segment granularity (only whole segments behind the cursor are
        # ever unlinked), so the journal must always have closed
        # segments to free when it fills
        seg = (int(segment_max_bytes) if segment_max_bytes
               else max(4096, int(max_bytes) // 16))
        self._journal = EventJournal(
            directory, fsync="batch",
            max_bytes=max(seg + 1, int(max_bytes)), segment_max_bytes=seg)
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque()
        self._rng = random.Random()
        self._closed = False
        self.enabled = bool(enabled)
        # lifetime counters (stats() mirrors the registry families)
        self.captured = 0
        self.sampled_out = 0
        self.dropped = 0
        self.flushes = 0
        _G_ENABLED.set(1 if self.enabled else 0)
        _G_BYTES.set(self._journal.size_bytes())

    # -- control -----------------------------------------------------------
    def start(self) -> None:
        self.enabled = True
        _G_ENABLED.set(1)

    def stop(self) -> None:
        """Disable recording and flush whatever the ring holds — a
        `pio capture stop` must leave everything captured so far on
        disk, not stranded in memory."""
        self.enabled = False
        _G_ENABLED.set(0)
        self.flush("manual")

    # -- hot path ----------------------------------------------------------
    def record(self, *, rid: str, request: dict, response,
               status: int, latency_ms: float,
               provenance: dict | None) -> None:
        """Capture one served request. Cheap by construction: a sample
        draw, one dict build, one deque append; the journal write is
        deferred to the next flush."""
        if self._closed or not self.enabled:
            return
        if self.sample < 1.0 and self._rng.random() >= self.sample:
            self.sampled_out += 1
            _M_RECORDS.inc(outcome="sampled_out")
            return
        rec = {
            "rid": rid,
            "ts": time.time(),
            "request": request,
            "response": response,
            "status": status,
            "latencyMs": round(latency_ms, 3),
            "provenance": provenance,
        }
        with self._lock:
            self._ring.append(rec)
            n = len(self._ring)
        self.captured += 1
        _M_RECORDS.inc(outcome="captured")
        _G_RING.set(n)
        if n >= self.ring_capacity:
            self.flush("ring_full")

    # -- persistence -------------------------------------------------------
    def flush(self, trigger: str = "manual") -> int:
        """Serialize the buffered ring into the capture journal. Returns
        the number of records persisted. Never raises — capture must not
        take serving down; failures count as drops."""
        with self._lock:
            if not self._ring:
                return 0
            batch, self._ring = list(self._ring), deque()
        _G_RING.set(0)
        persisted = 0
        for rec in batch:
            try:
                payload = json.dumps(rec, default=str,
                                     separators=(",", ":")).encode()
            except (TypeError, ValueError):
                self.dropped += 1
                _M_RECORDS.inc(outcome="dropped")
                continue
            if self._persist(payload):
                persisted += 1
            else:
                self.dropped += 1
                _M_RECORDS.inc(outcome="dropped")
        try:
            self._journal.sync()
        except Exception:  # noqa: BLE001 — durability is best-effort here
            log.exception("capture journal sync failed")
        self.flushes += 1
        _M_FLUSHES.inc(trigger=trigger)
        _G_BYTES.set(self._journal.size_bytes())
        return persisted

    def _persist(self, payload: bytes) -> bool:
        """Append with drop-oldest semantics: on ``JournalFull`` release
        the oldest captured records (cursor advance GCs whole segments
        behind it) and retry. Gives up when advancing frees nothing —
        the record is bigger than the journal, or everything left lives
        in the active segment."""
        for _ in range(64):
            try:
                self._journal.append(payload)
                return True
            except JournalFull:
                try:
                    recs, pos = self._journal.peek_batch(1024)
                except Exception:  # noqa: BLE001
                    return False
                if not recs:
                    return False
                before = self._journal.size_bytes()
                self._journal.advance(pos)
                if self._journal.size_bytes() >= before:
                    return False
            except Exception:  # noqa: BLE001 — a broken disk must not
                log.exception("capture journal append failed")  # kill serving
                return False
        return False

    # -- views / lifecycle -------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            ring = len(self._ring)
        j = self._journal.stats()
        return {
            "enabled": self.enabled,
            "directory": self.directory,
            "sample": self.sample,
            "ringRecords": ring,
            "ringCapacity": self.ring_capacity,
            "captured": self.captured,
            "sampledOut": self.sampled_out,
            "dropped": self.dropped,
            "flushes": self.flushes,
            "journalBytes": j["sizeBytes"],
            "journalMaxBytes": j["maxBytes"],
            "journalRecords": j["appended"],
            "journalSegments": j["segments"],
        }

    def close(self) -> None:
        """Final flush + journal close. Idempotent."""
        if self._closed:
            return
        self.flush("close")
        self._closed = True
        self.enabled = False
        _G_ENABLED.set(0)
        try:
            self._journal.close()
        except Exception:  # noqa: BLE001
            log.exception("capture journal close failed")


def iter_capture(directory: str) -> Iterator[dict]:
    """Yield every readable capture record (as a dict) from a capture
    directory, oldest first — a pure read-only scan over the journal
    segments (torn tails are skipped, never fatal), independent of the
    writer's drop-oldest cursor. Unparseable payloads are skipped."""
    for payload in iter_journal_records(Path(directory)):
        try:
            rec = json.loads(payload.decode())
        except (json.JSONDecodeError, UnicodeDecodeError):
            continue
        if isinstance(rec, dict):
            yield rec


def export_capture(directory: str, output: str) -> int:
    """Write a capture directory out as JSONL (one record per line) for
    `pio capture export`. Returns the record count."""
    n = 0
    with open(output, "w") as fh:
        for rec in iter_capture(directory):
            fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
            n += 1
    return n
