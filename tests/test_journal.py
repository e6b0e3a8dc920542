"""EventJournal — the ingestion write-ahead log (storage/journal.py).

Pure file-level contract tests: framing, torn-tail recovery, cursor
persistence, rotation + GC, capacity backpressure and the fsync
policies. The HTTP-level durability story (acks surviving a backend
outage and a process kill) lives in test_ingest_durability.py.

ResourceWarning is promoted to an error here: a journal that leaks an
open segment handle would hold the WAL hostage across restarts.
"""

import pytest

from predictionio_tpu.storage.journal import (
    EventJournal,
    JournalFull,
)

pytestmark = [
    pytest.mark.ingest,
    pytest.mark.filterwarnings("error::ResourceWarning"),
]


def p(i: int) -> bytes:
    return f"payload-{i:04d}".encode()


@pytest.fixture
def jdir(tmp_path):
    return tmp_path / "journal"


def test_append_peek_advance_roundtrip(jdir):
    j = EventJournal(jdir)
    for i in range(5):
        assert j.append(p(i)) == i
    assert j.lag == 5

    records, pos = j.peek_batch(3)
    assert records == [p(0), p(1), p(2)]
    assert j.lag == 5  # peek does not move the cursor
    j.advance(pos)
    assert j.lag == 2

    records, pos = j.peek_batch(10)
    assert records == [p(3), p(4)]
    j.advance(pos)
    assert j.lag == 0
    assert j.peek_batch(10)[0] == []

    s = j.stats()
    assert s["appended"] == 5 and s["drained"] == 5 and s["drainIndex"] == 5
    j.close()


def test_reopen_resumes_from_persisted_cursor(jdir):
    j = EventJournal(jdir)
    for i in range(6):
        j.append(p(i))
    _, pos = j.peek_batch(4)
    j.advance(pos)
    j.close()

    j2 = EventJournal(jdir)
    assert j2.lag == 2
    records, pos = j2.peek_batch(10)
    assert records == [p(4), p(5)]
    # global indices keep counting across the restart
    assert pos[2] == 6
    j2.close()


def test_crash_without_advance_replays_everything(jdir):
    j = EventJournal(jdir, fsync="always")
    for i in range(5):
        j.append(p(i))
    j.close()  # no advance() ever ran — simulates a crash pre-drain

    j2 = EventJournal(jdir)
    assert j2.lag == 5
    assert j2.peek_batch(10)[0] == [p(i) for i in range(5)]
    j2.close()


def test_torn_tail_truncated_on_open(jdir):
    j = EventJournal(jdir)
    for i in range(3):
        j.append(p(i))
    j.sync()
    seg = next(j.dir.glob("journal-*.log"))
    j.close()
    # a crash mid-append: a frame header promising bytes that never landed
    with open(seg, "ab") as fh:
        fh.write(b"\xff\xff\x00\x00GARB")

    j2 = EventJournal(jdir)
    assert j2.stats()["truncatedBytes"] > 0
    assert j2.lag == 3
    assert j2.peek_batch(10)[0] == [p(i) for i in range(3)]
    # the truncated tail is writable again — new appends frame cleanly
    j2.append(p(99))
    assert j2.peek_batch(10)[0][-1] == p(99)
    j2.close()


def test_corruption_drops_all_later_segments(jdir):
    # tiny segments: every append rotates, so corruption lands mid-history
    j = EventJournal(jdir, segment_max_bytes=1)
    for i in range(4):
        j.append(p(i))
    j.sync()
    segs = sorted(j.dir.glob("journal-*.log"))
    assert len(segs) == 4
    j.close()
    # flip one payload byte in segment 1 -> CRC mismatch there
    raw = bytearray(segs[1].read_bytes())
    raw[-1] ^= 0xFF
    segs[1].write_bytes(raw)

    j2 = EventJournal(jdir)
    # the longest valid prefix is record 0 alone: segment 1 truncates at
    # its bad frame and segments 2..3 are dropped entirely — never a hole
    assert j2.peek_batch(10)[0] == [p(0)]
    assert j2.lag == 1
    assert not segs[2].exists() and not segs[3].exists()
    j2.close()


def test_rotation_and_gc_behind_cursor(jdir):
    j = EventJournal(jdir, segment_max_bytes=1)
    for i in range(5):
        j.append(p(i))
    assert j.stats()["rotations"] == 4
    _, pos = j.peek_batch(10)
    j.advance(pos)
    # drained segments are unlinked file-at-a-time; the active one stays
    assert j.stats()["segmentsRemoved"] == 4
    assert len(list(j.dir.glob("journal-*.log"))) == 1
    # appends keep working after GC, indices still monotonic
    assert j.append(p(5)) == 5
    assert j.peek_batch(10)[0] == [p(5)]
    j.close()


def test_journal_full_backpressure_and_recovery(jdir):
    j = EventJournal(jdir, max_bytes=256, segment_max_bytes=1)
    appended = 0
    with pytest.raises(JournalFull):
        for i in range(100):
            j.append(p(i))
            appended += 1
    assert 0 < appended < 100
    assert j.lag == appended  # the failed append wrote nothing

    # draining + GC frees capacity in whole segments -> appends resume
    _, pos = j.peek_batch(1000)
    j.advance(pos)
    j.append(p(500))
    assert j.peek_batch(10)[0] == [p(500)]
    j.close()


def test_fsync_policies(jdir):
    with pytest.raises(ValueError):
        EventJournal(jdir / "x", fsync="sometimes")

    j = EventJournal(jdir / "always", fsync="always")
    j.append(p(0))
    assert j.stats()["fsyncs"] >= 1 and j.stats()["unsyncedBytes"] == 0
    j.close()

    j = EventJournal(jdir / "batch", fsync="batch")
    j.append(p(0))
    assert j.stats()["unsyncedBytes"] > 0
    j.sync()
    assert j.stats()["fsyncs"] == 1 and j.stats()["unsyncedBytes"] == 0
    j.close()

    j = EventJournal(jdir / "never", fsync="never")
    j.append(p(0))
    j.sync()  # no-op by operator choice
    assert j.stats()["fsyncs"] == 0 and j.stats()["unsyncedBytes"] > 0
    j.close()


def test_close_is_idempotent_and_guards_use(jdir):
    j = EventJournal(jdir)
    j.append(p(0))
    j.close()
    j.close()
    for op in (lambda: j.append(p(1)), lambda: j.sync(),
               lambda: j.peek_batch(1), lambda: j.advance((0, 0, 1))):
        with pytest.raises(RuntimeError, match="closed"):
            op()


def test_reopen_after_segments_vanish_respects_cursor(jdir):
    j = EventJournal(jdir)
    for i in range(3):
        j.append(p(i))
    _, pos = j.peek_batch(10)
    j.advance(pos)
    j.close()
    for seg in jdir.glob("journal-*.log"):
        seg.unlink()  # ops wiped drained history; cursor.json survives

    j2 = EventJournal(jdir)
    assert j2.lag == 0
    # the fresh segment starts PAST the cursored one so the stale
    # in-segment offset can never skip new records
    assert j2.append(p(3)) == 3
    assert j2.peek_batch(10)[0] == [p(3)]
    j2.close()


def test_unreadable_cursor_replays_from_oldest(jdir):
    j = EventJournal(jdir)
    for i in range(3):
        j.append(p(i))
    _, pos = j.peek_batch(2)
    j.advance(pos)
    j.close()
    (jdir / "cursor.json").write_text("{torn")

    # fail open, never fail closed: replay everything (idempotent by id)
    j2 = EventJournal(jdir)
    assert j2.lag == 3
    assert j2.peek_batch(10)[0] == [p(i) for i in range(3)]
    j2.close()


@pytest.mark.chaos
def test_append_fault_site(jdir):
    from predictionio_tpu.faults import FAULTS, FaultInjected

    j = EventJournal(jdir)
    FAULTS.inject("journal.append", "error", times=1)
    with pytest.raises(FaultInjected):
        j.append(p(0))
    assert j.lag == 0  # the failed append left no partial frame
    assert j.append(p(1)) == 0
    j.close()


@pytest.mark.chaos
def test_fsync_fault_site(jdir):
    from predictionio_tpu.faults import FAULTS, FaultInjected

    j = EventJournal(jdir, fsync="batch")
    j.append(p(0))
    FAULTS.inject("journal.fsync", "error", times=1)
    with pytest.raises(FaultInjected):
        j.sync()
    FAULTS.clear()
    j.sync()  # the retry fsyncs the still-pending bytes
    assert j.stats()["unsyncedBytes"] == 0
    j.close()


# ---------------------------------------------------------------------------
# PartitionedJournal (ISSUE 9): N independent journals keyed by entity hash


def _pj(jdir, n, **kw):
    from predictionio_tpu.storage.journal import PartitionedJournal

    kw.setdefault("fsync", "never")
    return PartitionedJournal(jdir, partitions=n, **kw)


def test_partitioned_layout_and_routing(jdir):
    """Seed pin for the on-disk layout: N>1 puts each partition under
    p<k>/ with its own segments + cursor, and stamps partitions.json;
    routing is shard_of(entity_type, entity_id, N)."""
    from predictionio_tpu.storage.partition import shard_of

    j = _pj(jdir, 4)
    assert (jdir / "partitions.json").exists()
    for i in range(20):
        part = j.partition_of("user", f"u{i}")
        assert part == shard_of("user", f"u{i}", 4)
        j.append(p(i), part)
    assert j.lag == 20
    assert sum(j.lag_of(k) for k in range(4)) == 20
    touched = [k for k in range(4) if j.lag_of(k)]
    assert len(touched) > 1  # the hash actually spreads entities
    for k in touched:
        assert list((jdir / f"p{k}").glob("journal-*.log"))
    assert not list(jdir.glob("journal-*.log"))  # nothing at the root
    # per-partition drain: each cursor is independent
    k0 = touched[0]
    records, pos = j.peek_batch(k0, 100)
    assert len(records) == j.lag_of(k0)
    j.advance(k0, pos)
    assert j.lag_of(k0) == 0
    assert j.lag == 20 - len(records)
    j.close()


def test_partitioned_n1_keeps_flat_legacy_layout(jdir):
    """Seed pin: partitions=1 is byte-compatible with the pre-partition
    journal — segments + cursor live at the directory root, no p0/."""
    j = _pj(jdir, 1)
    j.append(p(0), 0)
    j.close()
    assert list(jdir.glob("journal-*.log"))
    assert not (jdir / "p0").exists()
    # a journal written BEFORE partitioning existed (no marker) opens
    # as one partition with its records intact
    (jdir / "partitions.json").unlink()
    j2 = _pj(jdir, 1)
    assert j2.lag == 1
    assert j2.peek_batch(0, 10)[0] == [p(0)]
    j2.close()


def test_partitioned_gc_isolation(jdir):
    """Draining one partition GCs ITS segments only — a lagging sibling
    keeps every file it still needs."""
    j = _pj(jdir, 2, segment_max_bytes=64)
    for i in range(12):
        j.append(p(i), i % 2)
    segs_before = {k: len(list((jdir / f"p{k}").glob("journal-*.log")))
                   for k in (0, 1)}
    assert min(segs_before.values()) > 1  # both rotated
    records, pos = j.peek_batch(0, 100)
    j.advance(0, pos)
    assert j.lag_of(0) == 0 and j.lag_of(1) == 6
    segs_after0 = len(list((jdir / "p0").glob("journal-*.log")))
    segs_after1 = len(list((jdir / "p1").glob("journal-*.log")))
    assert segs_after0 < segs_before[0]   # p0 collected
    assert segs_after1 == segs_before[1]  # p1 untouched
    assert j.peek_batch(1, 100)[0] == [p(i) for i in range(12) if i % 2]
    j.close()


def test_partitioned_torn_tail_isolated(jdir):
    """A torn tail in one partition truncates THAT partition on reopen;
    siblings replay every record untouched."""
    j = _pj(jdir, 2)
    for i in range(6):
        j.append(p(i), i % 2)
    j.close()
    seg = sorted((jdir / "p1").glob("journal-*.log"))[-1]
    with open(seg, "ab") as fh:
        fh.write(b"\x40\x00\x00\x00\x99\x99torn")
    j2 = _pj(jdir, 2)
    assert j2.peek_batch(0, 100)[0] == [p(0), p(2), p(4)]
    assert j2.peek_batch(1, 100)[0] == [p(1), p(3), p(5)]
    st = j2.stats()
    assert st["truncatedBytes"] > 0
    per = {d["partition"]: d for d in st["perPartition"]}
    assert per[1]["truncatedBytes"] > 0 and per[0]["truncatedBytes"] == 0
    j2.close()


def test_partitioned_full_is_per_partition(jdir):
    """Capacity is split across partitions; a hot partition 503s alone
    while its siblings keep accepting."""
    j = _pj(jdir, 2, max_bytes=600, segment_max_bytes=300)
    hot = 0
    with pytest.raises(JournalFull):
        for i in range(1000):
            j.append(p(i), hot)
    j.append(p(0), 1)  # the sibling still has its own headroom
    assert j.fill_of(hot) > j.fill_of(1)
    assert j.fill_fraction() == pytest.approx(j.fill_of(hot))
    j.close()


def test_partition_resize_requires_drained(jdir):
    """N -> M with undrained records is refused; drained journals resize
    cleanly and every partition starts empty (docs/operations.md
    'Ingestion at scale')."""
    from predictionio_tpu.storage.journal import JournalLayoutError

    j = _pj(jdir, 2)
    j.append(p(0), 0)
    j.close()
    with pytest.raises(JournalLayoutError, match="drained"):
        _pj(jdir, 4)
    # drain, then resize both ways
    j = _pj(jdir, 2)
    records, pos = j.peek_batch(0, 10)
    j.advance(0, pos)
    j.close()
    j4 = _pj(jdir, 4)
    assert j4.num_partitions == 4 and j4.lag == 0
    j4.append(p(1), 3)
    j4.close()
    with pytest.raises(JournalLayoutError):
        _pj(jdir, 1)  # shrink is guarded the same way
    j4 = _pj(jdir, 4)
    records, pos = j4.peek_batch(3, 10)
    j4.advance(3, pos)
    j4.close()
    j1 = _pj(jdir, 1)
    assert j1.num_partitions == 1 and j1.lag == 0
    j1.close()


@pytest.mark.chaos
def test_partition_append_fault_site(jdir):
    from predictionio_tpu.faults import FAULTS, FaultInjected

    j = _pj(jdir, 2)
    FAULTS.inject("journal.partition_append", "error", times=1)
    with pytest.raises(FaultInjected):
        j.append(p(0), 0)
    assert j.lag == 0  # refused before any partition was touched
    j.append(p(0), 0)
    assert j.lag == 1
    j.close()


def test_partitioned_stats_and_metrics_labels(jdir):
    """The per-partition gauges carry a partition label and the stats
    aggregate keeps the single-journal key shape."""
    from predictionio_tpu.obs.metrics import METRICS

    j = _pj(jdir, 2)
    j.append(p(0), 0)
    j.append(p(1), 0)
    j.append(p(2), 1)
    st = j.stats()
    assert st["lag"] == 3 and st["partitions"] == 2
    assert {d["partition"] for d in st["perPartition"]} == {0, 1}
    assert {d["lag"] for d in st["perPartition"]} == {1, 2}
    text = METRICS.render_prometheus()
    assert 'pio_journal_partition_lag{partition="0"} 2' in text
    assert 'pio_journal_partition_lag{partition="1"} 1' in text
    assert 'pio_journal_partition_fill{partition="0"}' in text
    j.close()
