"""Segmented append-only event journal — the ingestion write-ahead log.

The reference's HBase event backend gave ingestion a real WAL for free
(every `put` lands in the RegionServer's HLog before it is acked); the
sqlite/memory backends here have nothing between "201 sent" and "row
committed", so a storage outage turns every POST into a 500 and a crash
loses whatever was in flight. This module restores the missing layer:
the event server appends each accepted event to this journal, fsyncs per
its policy, and acks 201 — a background drainer then pushes journal
records into the ``EventBackend`` at its own pace (api/ingest.py).

Design (the classic single-writer log, cf. HLog / Kafka segment logs):

- **Segments**: ``journal-<seq>.log`` files under one directory; the
  active segment rotates at ``segment_max_bytes`` so drained history can
  be garbage-collected file-at-a-time instead of compacted in place.
- **Framing**: each record is ``<u32 length><u32 crc32(payload)>`` +
  payload (little-endian). CRC + length make a torn write detectable.
- **Torn-tail truncation**: a crash mid-append leaves a partial frame at
  the tail. On open, every segment is scanned; the first invalid frame
  truncates its segment there and drops any later segments — recovery
  keeps the longest valid prefix, never a hole.
- **Cursor**: the drainer's progress ``(segment, offset, index)`` is
  persisted atomically (tmp + ``os.replace``) in ``cursor.json``;
  segments wholly behind the cursor are deleted. After a crash the
  drainer resumes from the last persisted cursor — records drained but
  not yet cursored are re-pushed, which is safe because event ids are
  assigned BEFORE journaling and both built-in backends upsert by id
  (``INSERT OR REPLACE``): replay is idempotent.
- **fsync policy**: ``always`` (fsync inside every append), ``batch``
  (the caller fsyncs once per ingest request via ``sync()`` before
  acking), ``never`` (leave durability to the OS page cache — survives a
  process crash, not a power cut).
- **Backpressure**: past ``max_bytes`` of un-collected segments,
  ``append`` raises ``JournalFull`` — the server turns that into 503 +
  ``Retry-After`` instead of silently dropping events.

Chaos sites: ``journal.append`` fires at the head of every append,
``journal.fsync`` before every fsync, and ``journal.partition_append``
at the head of every routed ``PartitionedJournal.append``
(faults.py), so disk-level failures are provable in tests
without a broken disk.

Thread-safety: one lock around all mutation; appends come from the event
server's ``asyncio.to_thread`` workers while the drainer reads/advances
from its own thread.

**Partitioning** (``PartitionedJournal``): the reference scaled ingest by
letting HBase split the event table across region servers by row-key
hash (``HBEventsUtil.RowKey`` = hash(entity) prefix); the analog here is
N independent ``EventJournal`` instances keyed by
``shard_of(entity_type, entity_id, N)`` (storage/partition.py — the same
hash the trainer shards by). Each partition has its own segments,
cursor, fsync batch, GC and fill fraction under ``p<k>/``; ``N == 1``
keeps the original flat single-directory layout, byte-compatible with
journals written before partitioning existed. Global ordering weakens to
per-entity ordering — all that training and ``aggregate_properties``
ever relied on. A ``partitions.json`` marker stamps the layout; opening
with a different N is a **resize** and is refused unless every old
partition is fully drained (see docs/operations.md "Ingestion at
scale").
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import struct
import threading
import time
import zlib
from pathlib import Path

from ..obs.metrics import METRICS
from ..faults import FAULTS

# ISSUE 5: journal durability costs, scrapeable (the stats() dict keeps
# its raw-counter shape; these add the latency distributions)
_M_APPEND = METRICS.histogram(
    "pio_journal_append_seconds",
    "EventJournal.append wall time (frame + write + policy fsync)")
_M_FSYNC = METRICS.histogram(
    "pio_journal_fsync_seconds",
    "journal fsync wall time (the durability floor of a 201 ack)")
# ISSUE 9: per-partition surfaces — a hot or wedged partition must be
# visible as ITSELF, not averaged away in the totals
_M_PART_LAG = METRICS.gauge(
    "pio_journal_partition_lag",
    "undrained records in one journal partition",
    labelnames=("partition",))
_M_PART_FILL = METRICS.gauge(
    "pio_journal_partition_fill",
    "fill fraction (sizeBytes/maxBytes) of one journal partition",
    labelnames=("partition",))

log = logging.getLogger("predictionio_tpu.journal")

__all__ = ["EventJournal", "PartitionedJournal", "JournalFollower",
           "JournalFull", "JournalLayoutError", "FSYNC_POLICIES",
           "iter_journal_records"]

_HEADER = struct.Struct("<II")  # (payload length, crc32(payload))
_SEGMENT_GLOB = "journal-*.log"
_CURSOR_FILE = "cursor.json"
_PARTITIONS_FILE = "partitions.json"

FSYNC_POLICIES = ("always", "batch", "never")


class JournalFull(RuntimeError):
    """The journal hit ``max_bytes`` of undrained data — the caller must
    shed load (503 + Retry-After) instead of dropping the event."""


class JournalLayoutError(RuntimeError):
    """The on-disk partition layout does not match the requested
    partition count and at least one old partition still holds undrained
    records. Resizing N -> M requires drained journals (stop ingest, let
    the drainers reach lag 0, restart with the new count) — re-hashing
    undrained records across a different N would break per-entity
    ordering and exactly-once replay."""


def _layout_of(directory: Path) -> int | None:
    """Partition count of whatever lives in ``directory``: the stamped
    ``partitions.json`` marker if readable, else inferred from the files
    (p<k>/ subdirs, or flat pre-partitioning segments -> 1). Shared by
    the writer (``PartitionedJournal``) and read-only followers."""
    try:
        n = int(json.loads(
            (directory / _PARTITIONS_FILE).read_text())["partitions"])
        if n >= 1:
            return n
    except FileNotFoundError:
        pass
    except (json.JSONDecodeError, ValueError, KeyError, TypeError,
            OSError) as e:
        log.warning("journal: unreadable %s (%s); inferring layout "
                    "from files", _PARTITIONS_FILE, e)
    pdirs = [d for d in directory.glob("p*")
             if d.is_dir() and d.name[1:].isdigit()]
    if pdirs:
        return max(int(d.name[1:]) for d in pdirs) + 1
    if any(directory.glob(_SEGMENT_GLOB)) \
            or (directory / _CURSOR_FILE).exists():
        return 1
    return None


def _segment_name(seq: int) -> str:
    return f"journal-{seq:08d}.log"


def _segment_seq(path: Path) -> int:
    return int(path.name[len("journal-"):-len(".log")])


def iter_journal_records(directory: str | os.PathLike):
    """Yield every valid record payload under ``directory``, oldest
    first — a pure read-only scan (ISSUE 13: the capture/replay layer's
    view of a capture journal). Unlike ``JournalFollower`` this carries
    no cursor at all: every segment's longest valid record prefix is
    read in seq order, torn tails and vanished segments are skipped
    (never fatal), and nothing on disk is touched."""
    for path in sorted(Path(directory).glob(_SEGMENT_GLOB),
                       key=_segment_seq):
        try:
            with open(path, "rb") as fh:
                while True:
                    header = fh.read(_HEADER.size)
                    if len(header) < _HEADER.size:
                        break
                    length, crc = _HEADER.unpack(header)
                    payload = fh.read(length)
                    if len(payload) < length or zlib.crc32(payload) != crc:
                        break  # torn tail: keep the valid prefix only
                    yield payload
        except OSError:
            continue  # segment GC'd mid-scan: the rest still reads


class _Segment:
    """One on-disk segment: its seq, path, logical size and record count.

    ``size`` is the VALID byte length (post torn-tail truncation) — the
    reader never reads past it, the writer only appends at it."""

    __slots__ = ("seq", "path", "size", "records")

    def __init__(self, seq: int, path: Path, size: int = 0, records: int = 0):
        self.seq = seq
        self.path = path
        self.size = size
        self.records = records


class EventJournal:
    """Crash-safe append-only record log with a persisted drain cursor."""

    def __init__(
        self,
        directory: str | os.PathLike,
        *,
        fsync: str = "batch",
        max_bytes: int = 256 * 1024 * 1024,
        segment_max_bytes: int = 16 * 1024 * 1024,
    ):
        if fsync not in FSYNC_POLICIES:
            raise ValueError(
                f"fsync policy must be one of {FSYNC_POLICIES}, got {fsync!r}")
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.fsync_policy = fsync
        self.max_bytes = max(1, int(max_bytes))
        self.segment_max_bytes = max(1, int(segment_max_bytes))
        self._lock = threading.Lock()
        self._closed = False
        self._segments: list[_Segment] = []
        self._write_fh = None  # open append handle on the LAST segment
        # drain cursor: next record to hand the drainer
        self._drain_seq = 0
        self._drain_off = 0
        self._drain_idx = 0  # monotonically increasing global record index
        self._undrained = 0
        # counters (stats()/health surfaces)
        self.appended = 0          # records appended this process
        self.drained = 0           # records acked past the cursor this process
        self.synced = 0            # fsync calls
        self.unsynced_bytes = 0    # bytes appended since the last fsync
        self.truncated_bytes = 0   # torn-tail bytes dropped at open
        self.rotations = 0
        self.segments_removed = 0
        self._recover()

    # -- recovery ----------------------------------------------------------
    def _recover(self) -> None:
        """Scan segments, truncate the torn tail, load the cursor, GC
        fully-drained history."""
        paths = sorted(self.dir.glob(_SEGMENT_GLOB), key=_segment_seq)
        torn = False
        for path in paths:
            if torn:
                # a bad frame invalidates everything after it: keep the
                # longest valid prefix, never a prefix with a hole
                log.warning("journal: dropping segment %s after torn tail",
                            path.name)
                self.truncated_bytes += path.stat().st_size
                path.unlink()
                continue
            seg = _Segment(_segment_seq(path), path)
            valid, records = self._scan_segment(path)
            raw = path.stat().st_size
            if valid < raw:
                log.warning(
                    "journal: truncating torn tail of %s at %d (%d bytes "
                    "dropped)", path.name, valid, raw - valid)
                with open(path, "rb+") as fh:
                    fh.truncate(valid)
                    fh.flush()
                    os.fsync(fh.fileno())
                self.truncated_bytes += raw - valid
                torn = True
            seg.size = valid
            seg.records = records
            self._segments.append(seg)
        cursor = self._load_cursor()
        if not self._segments:
            # nothing on disk (fresh dir, or everything drained + GC'd
            # before the restart): start one segment PAST the cursored
            # one, so a stale in-segment cursor offset can never point
            # beyond the new segment's records
            seq = int(cursor.get("seq", -1)) + 1 if cursor else 0
            self._open_segment(seq)
            self._drain_idx = int(cursor.get("idx", 0)) if cursor else 0
            self._drain_seq, self._drain_off = seq, 0
            self._undrained = 0
            return
        # re-attach the append handle to the surviving tail segment
        # (unbuffered, like _open_segment — the write path never flushes)
        self._write_fh = open(self._segments[-1].path, "ab", buffering=0)
        if cursor:
            self._drain_idx = int(cursor.get("idx", 0))
            seq = int(cursor.get("seq", 0))
            off = int(cursor.get("off", 0))
            known = {s.seq: s for s in self._segments}
            if seq in known:
                # a torn tail can shrink the cursored segment underneath a
                # cursor persisted before the crash — clamp, re-push
                self._drain_seq = seq
                self._drain_off = min(off, known[seq].size)
            else:
                # cursored segment already collected (or never synced):
                # restart at the oldest surviving record; replay is
                # idempotent so over-pushing is safe, holes are not
                self._drain_seq = self._segments[0].seq
                self._drain_off = 0
        else:
            self._drain_seq = self._segments[0].seq
            self._drain_off = 0
        self._undrained = self._count_from(self._drain_seq, self._drain_off)
        self._gc_locked()

    @staticmethod
    def _scan_segment(path: Path) -> tuple[int, int]:
        """Return (valid byte length, record count) of the longest valid
        record prefix of ``path``."""
        valid = 0
        records = 0
        with open(path, "rb") as fh:
            while True:
                header = fh.read(_HEADER.size)
                if len(header) < _HEADER.size:
                    break
                length, crc = _HEADER.unpack(header)
                payload = fh.read(length)
                if len(payload) < length or zlib.crc32(payload) != crc:
                    break
                valid += _HEADER.size + length
                records += 1
        return valid, records

    def _count_from(self, seq: int, off: int) -> int:
        """Records at/after (seq, off) — the restart lag. Counted by
        re-reading the partial segment once at open; later bookkeeping is
        incremental."""
        n = 0
        for seg in self._segments:
            if seg.seq < seq:
                continue
            if seg.seq > seq or off == 0:
                n += seg.records
                continue
            with open(seg.path, "rb") as fh:
                fh.seek(off)
                while True:
                    header = fh.read(_HEADER.size)
                    if len(header) < _HEADER.size:
                        break
                    length, _ = _HEADER.unpack(header)
                    fh.seek(length, os.SEEK_CUR)
                    n += 1
        return n

    # -- cursor ------------------------------------------------------------
    def _cursor_path(self) -> Path:
        return self.dir / _CURSOR_FILE

    def _load_cursor(self) -> dict | None:
        try:
            return json.loads(self._cursor_path().read_text())
        except FileNotFoundError:
            return None
        except (json.JSONDecodeError, ValueError, OSError) as e:
            # a torn cursor write lost the file content: restart from the
            # oldest record (idempotent replay), never fail open
            log.warning("journal: unreadable cursor (%s); replaying from "
                        "the oldest record", e)
            return None

    def _persist_cursor_locked(self) -> None:
        tmp = self._cursor_path().with_suffix(".tmp")
        payload = json.dumps({"seq": self._drain_seq, "off": self._drain_off,
                              "idx": self._drain_idx})
        with open(tmp, "w") as fh:
            fh.write(payload)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, self._cursor_path())

    # -- write path --------------------------------------------------------
    def _open_segment(self, seq: int) -> None:
        if self._write_fh is not None:
            self._write_fh.close()
        seg = _Segment(seq, self.dir / _segment_name(seq))
        # unbuffered: every append is flushed to the OS anyway (the drainer
        # reads through a separate handle), so buffering would only add a
        # memcpy plus an extra flush syscall — and under concurrent
        # partition writers, an extra GIL round-trip — per record
        self._write_fh = open(seg.path, "ab", buffering=0)
        seg.size = self._write_fh.tell()
        self._segments.append(seg)

    def _check_closed(self) -> None:
        if self._closed:
            raise RuntimeError("EventJournal is closed")

    def append(self, payload: bytes) -> int:
        """Durably frame one record; returns its global index. Raises
        ``JournalFull`` past ``max_bytes`` of un-collected data (the
        record is NOT written). With policy ``always`` the record is
        fsynced before return; with ``batch`` the caller must ``sync()``
        before acking."""
        t0 = time.perf_counter()
        try:
            return self._append_timed(payload)
        finally:
            _M_APPEND.record(time.perf_counter() - t0)

    def _append_timed(self, payload: bytes) -> int:
        FAULTS.fire("journal.append")
        frame = _HEADER.pack(len(payload), zlib.crc32(payload)) + payload
        with self._lock:
            self._check_closed()
            if self.size_bytes() + len(frame) > self.max_bytes:
                raise JournalFull(
                    f"journal at capacity ({self.size_bytes()} of "
                    f"{self.max_bytes} bytes undrained)")
            tail = self._segments[-1]
            if tail.size >= self.segment_max_bytes:
                self._sync_locked()  # a rotated-away segment is immutable
                self._open_segment(tail.seq + 1)
                self.rotations += 1
                tail = self._segments[-1]
            # the handle is unbuffered: this lands in the OS (visible to
            # the drainer's read handle) in one syscall; fsync
            # (durability) is the policy's business
            self._write_fh.write(frame)
            tail.size += len(frame)
            tail.records += 1
            self.appended += 1
            self._undrained += 1
            self.unsynced_bytes += len(frame)
            idx = self._drain_idx + self._undrained - 1
            if self.fsync_policy == "always":
                self._sync_locked()
            return idx

    def sync(self) -> None:
        """fsync the active segment (no-op under policy ``never`` — the
        operator chose page-cache durability)."""
        with self._lock:
            self._check_closed()
            if self.fsync_policy != "never":
                self._sync_locked()

    def _sync_locked(self) -> None:
        if self.unsynced_bytes == 0 or self._write_fh is None:
            return
        t0 = time.perf_counter()
        FAULTS.fire("journal.fsync")
        # fdatasync: an append-only segment needs its data and size durable,
        # not atime/mtime — skipping the inode-time flush is the classic WAL
        # sync (PostgreSQL's wal_sync_method default) and measurably cheaper.
        os.fdatasync(self._write_fh.fileno())
        self.synced += 1
        self.unsynced_bytes = 0
        _M_FSYNC.record(time.perf_counter() - t0)

    # -- drain path --------------------------------------------------------
    def peek_batch(self, max_records: int) -> tuple[list[bytes], tuple[int, int, int]]:
        """Up to ``max_records`` undrained payloads in append order, plus
        the cursor position ``(seq, off, idx)`` to ``advance`` to once
        they are safely in the backend. Does not move the cursor."""
        out: list[bytes] = []
        with self._lock:
            self._check_closed()
            seq, off = self._drain_seq, self._drain_off
            by_seq = {s.seq: s for s in self._segments}
            while len(out) < max_records:
                seg = by_seq.get(seq)
                if seg is None or off >= seg.size:
                    nxt = min((s.seq for s in self._segments if s.seq > seq),
                              default=None)
                    if nxt is None:
                        break
                    seq, off = nxt, 0
                    continue
                with open(seg.path, "rb") as fh:
                    fh.seek(off)
                    while len(out) < max_records and off < seg.size:
                        header = fh.read(_HEADER.size)
                        length, _ = _HEADER.unpack(header)
                        out.append(fh.read(length))
                        off += _HEADER.size + length
            return out, (seq, off, self._drain_idx + len(out))

    def advance(self, pos: tuple[int, int, int]) -> None:
        """Persist the drain cursor at ``pos`` and GC segments wholly
        behind it. Called only after the backend accepted the batch."""
        seq, off, idx = pos
        with self._lock:
            self._check_closed()
            self.drained += idx - self._drain_idx
            self._undrained -= idx - self._drain_idx
            self._drain_seq, self._drain_off, self._drain_idx = seq, off, idx
            self._persist_cursor_locked()
            self._gc_locked()

    def _gc_locked(self) -> None:
        keep: list[_Segment] = []
        for seg in self._segments:
            # the active (last) segment is never deleted — the writer
            # holds it open and new appends land there
            if seg.seq < self._drain_seq and seg is not self._segments[-1]:
                try:
                    seg.path.unlink()
                except OSError:
                    keep.append(seg)
                    continue
                self.segments_removed += 1
            else:
                keep.append(seg)
        self._segments = keep

    # -- introspection -----------------------------------------------------
    def size_bytes(self) -> int:
        """On-disk bytes across live segments (the backpressure gauge)."""
        return sum(s.size for s in self._segments)

    @property
    def lag(self) -> int:
        """Undrained record count — 0 means every acked event is in the
        backend."""
        with self._lock:
            return self._undrained

    def stats(self) -> dict:
        with self._lock:
            return {
                "lag": self._undrained,
                "sizeBytes": sum(s.size for s in self._segments),
                "maxBytes": self.max_bytes,
                "segments": len(self._segments),
                "appended": self.appended,
                "drained": self.drained,
                "drainIndex": self._drain_idx,
                "fsyncPolicy": self.fsync_policy,
                "fsyncs": self.synced,
                "unsyncedBytes": self.unsynced_bytes,
                "truncatedBytes": self.truncated_bytes,
                "rotations": self.rotations,
                "segmentsRemoved": self.segments_removed,
            }

    def close(self) -> None:
        """Final fsync (unless policy ``never``) and handle close.
        Idempotent."""
        with self._lock:
            if self._closed:
                return
            if self.fsync_policy != "never":
                try:
                    self._sync_locked()
                except Exception:  # noqa: BLE001 — closing regardless
                    log.exception("journal: final fsync failed")
            if self._write_fh is not None:
                self._write_fh.close()
                self._write_fh = None
            self._closed = True


class PartitionedJournal:
    """N independent ``EventJournal`` shards keyed by
    ``shard_of(entity_type, entity_id, N)``.

    Each partition is a full journal — own segments, cursor, fsync batch,
    GC, backpressure cap (``max_bytes // N``) — so N drainers can append,
    fsync and advance concurrently without sharing a lock or a file.
    ``partitions == 1`` uses the journal directory itself (the original
    flat layout); ``partitions > 1`` uses ``p<k>/`` subdirectories. The
    layout is stamped in ``partitions.json``; opening an existing
    directory with a different count is refused via
    ``JournalLayoutError`` unless every old partition is drained, in
    which case the old layout's files are removed and all partitions
    start empty.
    """

    def __init__(
        self,
        directory: str | os.PathLike,
        *,
        partitions: int = 1,
        fsync: str = "batch",
        max_bytes: int = 256 * 1024 * 1024,
        segment_max_bytes: int = 16 * 1024 * 1024,
    ):
        partitions = int(partitions)
        if partitions < 1:
            raise ValueError(f"partitions must be >= 1, got {partitions}")
        if fsync not in FSYNC_POLICIES:
            raise ValueError(
                f"fsync policy must be one of {FSYNC_POLICIES}, got {fsync!r}")
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.num_partitions = partitions
        self.fsync_policy = fsync
        self.max_bytes = max(1, int(max_bytes))
        prior = self._prior_layout()
        if prior is not None and prior != partitions:
            self._resize_from(prior)
        # the total cap is the operator's disk budget — split it evenly so
        # N partitions together never exceed what one journal was allowed
        per_max = max(1, self.max_bytes // partitions)
        per_seg = max(1, min(int(segment_max_bytes), per_max))
        self._parts = [
            EventJournal(self._partition_dir(k), fsync=fsync,
                         max_bytes=per_max, segment_max_bytes=per_seg)
            for k in range(partitions)
        ]
        self._stamp_layout()
        self._publish_gauges()

    # -- layout ------------------------------------------------------------
    def _partition_dir(self, k: int) -> Path:
        return self.dir if self.num_partitions == 1 else self.dir / f"p{k}"

    def _prior_layout(self) -> int | None:
        """Partition count of whatever already lives in ``dir``: the
        stamped marker if readable, else inferred from the files (p<k>/
        subdirs, or flat pre-partitioning segments -> 1)."""
        return _layout_of(self.dir)

    def _resize_from(self, prior: int) -> None:
        """Refuse unless every old partition is drained, then clear the
        old layout so all new partitions start empty — re-hashing
        undrained records across a different N would reorder entities."""
        undrained: list[int] = []
        for k in range(prior):
            d = self.dir if prior == 1 else self.dir / f"p{k}"
            if not d.is_dir():
                continue
            old = EventJournal(d, fsync="never")
            try:
                if old.lag:
                    undrained.append(k)
            finally:
                old.close()
        if undrained:
            raise JournalLayoutError(
                f"journal at {self.dir} has {prior} partition(s) with "
                f"undrained records in {undrained}; resize to "
                f"{self.num_partitions} requires drained journals — stop "
                f"ingest, wait for lag 0, then restart (docs/operations.md "
                f"'Ingestion at scale')")
        for k in range(prior):
            if prior == 1:
                for p in self.dir.glob(_SEGMENT_GLOB):
                    p.unlink()
                (self.dir / _CURSOR_FILE).unlink(missing_ok=True)
            else:
                shutil.rmtree(self.dir / f"p{k}", ignore_errors=True)

    def _stamp_layout(self) -> None:
        tmp = (self.dir / _PARTITIONS_FILE).with_suffix(".tmp")
        tmp.write_text(json.dumps({"partitions": self.num_partitions}))
        os.replace(tmp, self.dir / _PARTITIONS_FILE)

    # -- routing -----------------------------------------------------------
    def partition_of(self, entity_type: str, entity_id: str) -> int:
        from .partition import shard_of

        return shard_of(entity_type, entity_id, self.num_partitions)

    # -- write path --------------------------------------------------------
    def append(self, payload: bytes, partition: int = 0) -> int:
        """Append one record to ``partition``; returns its index local to
        that partition. Raises ``JournalFull`` when THAT partition is at
        capacity — a hot partition backpressures alone."""
        FAULTS.fire("journal.partition_append")
        # gauges are published from advance()/stats(), not here: the append
        # path is the fsync-parallel hot loop and every microsecond of GIL
        # work in it serializes N otherwise-concurrent partition writers
        return self._parts[partition].append(payload)

    def sync(self, partition: int | None = None) -> None:
        """fsync one partition's active segment, or all of them."""
        if partition is not None:
            self._parts[partition].sync()
            return
        for part in self._parts:
            part.sync()

    # -- drain path --------------------------------------------------------
    def peek_batch(self, partition: int,
                   max_records: int) -> tuple[list[bytes], tuple[int, int, int]]:
        return self._parts[partition].peek_batch(max_records)

    def advance(self, partition: int, pos: tuple[int, int, int]) -> None:
        part = self._parts[partition]
        part.advance(pos)
        _M_PART_LAG.set(part._undrained, partition=str(partition))
        _M_PART_FILL.set(self.fill_of(partition), partition=str(partition))

    # -- introspection -----------------------------------------------------
    @property
    def lag(self) -> int:
        return sum(p.lag for p in self._parts)

    def lag_of(self, partition: int) -> int:
        return self._parts[partition].lag

    def size_bytes(self) -> int:
        return sum(p.size_bytes() for p in self._parts)

    def fill_of(self, partition: int) -> float:
        part = self._parts[partition]
        return min(1.0, part.size_bytes() / part.max_bytes)

    def fill_fraction(self) -> float:
        """Fill of the FULLEST partition — the one about to 503. The max
        (not the mean) is the admission-control signal: a single wedged
        partition must brown out ingest for its keys before it bursts."""
        return max(self.fill_of(k) for k in range(self.num_partitions))

    def _publish_gauges(self) -> None:
        for k, part in enumerate(self._parts):
            _M_PART_LAG.set(part.lag, partition=str(k))
            _M_PART_FILL.set(self.fill_of(k), partition=str(k))

    def stats(self) -> dict:
        """Aggregate stats in the single-journal shape (sums), plus a
        ``perPartition`` breakdown for /stats.json."""
        self._publish_gauges()  # scrapes hit /stats.json first — keep fresh
        per = [p.stats() for p in self._parts]
        agg = {
            "lag": sum(s["lag"] for s in per),
            "sizeBytes": sum(s["sizeBytes"] for s in per),
            "maxBytes": self.max_bytes,
            "segments": sum(s["segments"] for s in per),
            "appended": sum(s["appended"] for s in per),
            "drained": sum(s["drained"] for s in per),
            "drainIndex": sum(s["drainIndex"] for s in per),
            "fsyncPolicy": self.fsync_policy,
            "fsyncs": sum(s["fsyncs"] for s in per),
            "unsyncedBytes": sum(s["unsyncedBytes"] for s in per),
            "truncatedBytes": sum(s["truncatedBytes"] for s in per),
            "rotations": sum(s["rotations"] for s in per),
            "segmentsRemoved": sum(s["segmentsRemoved"] for s in per),
            "partitions": self.num_partitions,
            "perPartition": [
                {"partition": k, "lag": s["lag"],
                 "sizeBytes": s["sizeBytes"], "maxBytes": s["maxBytes"],
                 "fill": round(self.fill_of(k), 4),
                 "appended": s["appended"], "drained": s["drained"],
                 "segments": s["segments"],
                 "truncatedBytes": s["truncatedBytes"]}
                for k, s in enumerate(per)
            ],
        }
        return agg

    def close(self) -> None:
        for part in self._parts:
            part.close()


class JournalFollower:
    """Read-only tail of a (possibly partitioned) journal directory
    behind an INDEPENDENT persisted follow cursor per partition — the
    streaming updater's view of the WAL (ISSUE 10; the Kafka
    consumer-group analog: one log, many cursors).

    Strictly an observer of the drainer's journal: never touches
    ``cursor.json``, never opens a write handle, never truncates or
    GCs. Its own progress persists as ``follow-<name>.json`` beside each
    partition's drain cursor (same ``{"seq", "off", "idx"}`` shape, same
    atomic tmp + ``os.replace`` discipline).

    Races it must absorb:

    - **GC behind the drainer** can collect a segment the follower has
      not finished: when the cursored segment is gone, clamp to the
      oldest surviving one (the writer's own ``_recover`` rule).
      Re-reading is safe — the consumer (fold-in) is a deterministic
      per-user recomputation, so replay is idempotent.
    - **A frame mid-write** (or a torn tail before writer recovery)
      scans as invalid: the follower stops AT it without advancing and
      retries next poll — the writer's next flush or its restart-time
      truncation resolves it.
    """

    def __init__(self, directory: str | os.PathLike, *,
                 name: str = "stream", partitions: int | None = None):
        self.dir = Path(directory)
        self.name = name
        if partitions is not None:
            n = int(partitions)
            if n < 1:
                raise ValueError(f"partitions must be >= 1, got {n}")
        else:
            n = _layout_of(self.dir) or 1
        self.num_partitions = n
        self._pos: dict[int, tuple[int, int, int]] = {
            k: self._load_follow(k) for k in range(n)}

    # -- layout / cursor ---------------------------------------------------
    def _partition_dir(self, k: int) -> Path:
        return self.dir if self.num_partitions == 1 else self.dir / f"p{k}"

    def _cursor_path(self, k: int) -> Path:
        return self._partition_dir(k) / f"follow-{self.name}.json"

    def _load_follow(self, k: int) -> tuple[int, int, int]:
        try:
            c = json.loads(self._cursor_path(k).read_text())
            return int(c["seq"]), int(c["off"]), int(c["idx"])
        except FileNotFoundError:
            return (0, 0, 0)  # oldest surviving record (clamped in poll)
        except (json.JSONDecodeError, ValueError, KeyError, TypeError,
                OSError) as e:
            log.warning("journal: unreadable follow cursor %s (%s); "
                        "replaying from the oldest record",
                        self._cursor_path(k).name, e)
            return (0, 0, 0)

    def position(self, partition: int) -> tuple[int, int, int]:
        return self._pos[partition]

    def commit(self, partition: int, pos: tuple[int, int, int]) -> None:
        """Persist the follow cursor — call only once the batch's effect
        is settled downstream (published, or deliberately skipped); a
        transient failure must NOT commit, so a restart replays."""
        self._pos[partition] = (int(pos[0]), int(pos[1]), int(pos[2]))
        path = self._cursor_path(partition)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        with open(tmp, "w") as fh:
            fh.write(json.dumps({"seq": pos[0], "off": pos[1],
                                 "idx": pos[2]}))
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)

    # -- read path ---------------------------------------------------------
    def _segments_on_disk(self, partition: int) -> dict[int, Path]:
        d = self._partition_dir(partition)
        return {_segment_seq(p): p for p in d.glob(_SEGMENT_GLOB)}

    def poll(self, partition: int, max_records: int = 256,
             ) -> tuple[list[bytes], tuple[int, int, int]]:
        """Up to ``max_records`` payloads at/after the follow cursor, in
        append order, plus the position to ``commit`` once they are
        processed. Does not move the cursor."""
        seq, off, idx = self._pos[partition]
        known = self._segments_on_disk(partition)
        out: list[bytes] = []
        if not known:
            return out, (seq, off, idx)
        if seq not in known:
            # cursored segment collected (or cursor from another life):
            # clamp to the oldest surviving record — the _recover rule
            seq, off = min(known), 0
        while len(out) < max_records:
            path = known.get(seq)
            exhausted = path is None  # GC'd under us mid-poll: skip ahead
            if path is not None:
                hit_invalid = False
                try:
                    size = path.stat().st_size
                    with open(path, "rb") as fh:
                        fh.seek(off)
                        while len(out) < max_records:
                            header = fh.read(_HEADER.size)
                            if len(header) < _HEADER.size:
                                break
                            length, crc = _HEADER.unpack(header)
                            payload = fh.read(length)
                            if len(payload) < length \
                                    or zlib.crc32(payload) != crc:
                                hit_invalid = True
                                break
                            out.append(payload)
                            off += _HEADER.size + length
                except OSError:
                    exhausted = True
                if not exhausted:
                    if hit_invalid or len(out) >= max_records:
                        break  # hold position; retry next poll
                    if off < size:
                        break  # partial frame at the active tail: wait
                    exhausted = True  # consumed to its valid end
            if exhausted:
                nxt = min((s for s in known if s > seq), default=None)
                if nxt is None:
                    break
                seq, off = nxt, 0
        return out, (seq, off, idx + len(out))

    def lag(self, partition: int) -> int:
        """Records on disk at/after the follow cursor — the per-partition
        tail-lag gauge (``pio_stream_tail_lag``)."""
        seq, off, _ = self._pos[partition]
        known = self._segments_on_disk(partition)
        if known and seq not in known:
            seq, off = min(known), 0
        n = 0
        for s in sorted(known):
            if s < seq:
                continue
            path = known[s]
            try:
                size = path.stat().st_size
                with open(path, "rb") as fh:
                    pos = off if s == seq else 0
                    fh.seek(pos)
                    while True:
                        header = fh.read(_HEADER.size)
                        if len(header) < _HEADER.size:
                            break
                        length, _crc = _HEADER.unpack(header)
                        pos += _HEADER.size + length
                        if pos > size:
                            break
                        fh.seek(length, os.SEEK_CUR)
                        n += 1
            except OSError:
                continue
        return n
