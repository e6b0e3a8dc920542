"""Disaster recovery (ISSUE 19): cross-store backup, point-in-time
restore, and the fsck integrity audit.

Layers under test, bottom-up:

- backup unit semantics — a backup EXISTS only when its CRC-framed
  manifest parses and all listed files are present (PR-8's checkpoint
  discipline applied store-wide): torn-write/bitflip walk, incremental
  hardlink dedup, complete-only retention, the dr.lock;
- restore semantics — verify-before-apply, non-empty-target refusal
  (exit 2), WAL-tail replay through the id-keyed exactly-once insert
  path, point-in-time `--until <ts|seq>` cuts that also drop the
  post-cut tail;
- fsck invariant matrix — flipped blob byte, deleted checkpoint shard,
  truncated WAL segment, regressed router epoch marker; `--repair`
  quarantines/clamps and never deletes;
- the acceptance drills — SIGKILL mid-second-backup leaves the prior
  backup manifest-complete and restorable, and a full train -> serve ->
  capture golden traffic -> backup under live ingest -> wipe $PIO_HOME
  -> restore -> redeploy cycle replays the captured traffic with 100%
  bitwise parity (the PR-13 harness) and exactly-once event counts.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from datetime import datetime, timezone
from pathlib import Path

import pytest

from predictionio_tpu.storage import Storage, SQLiteEvents, EventQuery
from predictionio_tpu.storage import backup as B
from predictionio_tpu.storage.event import Event, event_to_api_dict
from predictionio_tpu.storage.journal import EventJournal
from predictionio_tpu.storage.metadata import (EngineInstance, MetadataStore,
                                               Model)
from predictionio_tpu.tools.cli import main as pio
from predictionio_tpu.faults import FAULTS

pytestmark = pytest.mark.dr

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# home builders


def _event(i: int) -> Event:
    return Event(event="rate", entity_type="user", entity_id=f"u{i}",
                 target_entity_type="item", target_entity_id=f"i{i}",
                 properties={"rating": float(i)},
                 event_time=datetime(2026, 1, 1, 0, 0, i,
                                     tzinfo=timezone.utc),
                 event_id=f"ev{i:04d}")


def _wal_payload(e: Event, app_id: int = 1) -> bytes:
    # the DurableIngestor.encode() wire shape the drain loop decodes
    return json.dumps({"e": event_to_api_dict(e), "a": app_id, "c": None},
                      separators=(",", ":")).encode()


def _seed_home(home: Path, *, n_db: int = 4, n_tail: int = 6) -> list[Event]:
    """A $PIO_HOME with every durable store populated: metadata (one
    COMPLETED instance), a model blob + sidecar, an event DB holding the
    first ``n_db`` events, and a WAL journal holding ALL events — the
    first ``n_db`` overlap the DB (drained but not yet GC'd), the rest
    are the undrained tail."""
    home.mkdir(parents=True, exist_ok=True)
    meta = MetadataStore(str(home / "metadata.db"))
    meta.engine_instance_insert(EngineInstance(
        id="inst-ok", status="COMPLETED", engine_id="e1",
        engine_version="1", engine_variant="default"))
    meta.close()
    blob = b"model-bytes-0123456789"
    (home / "models").mkdir(exist_ok=True)
    (home / "models" / "inst-ok").write_bytes(blob)
    (home / "models" / "inst-ok.sha256").write_text(
        Model.compute_checksum(blob))
    events = [_event(i) for i in range(n_db + n_tail)]
    ev = SQLiteEvents({"path": str(home / "events.db")})
    ev.insert_batch(events[:n_db], 1, None)
    ev.close()
    j = EventJournal(home / "journal")
    for e in events:
        j.append(_wal_payload(e))
    j.close()
    return events


def _seed_router(home: Path, *, journal_epochs=(1, 2, 3),
                 marker_epoch: int = 3) -> None:
    rdir = home / "run" / "fleet-router"
    dj = EventJournal(rdir / "delta-journal", fsync="always")
    for ep in journal_epochs:
        dj.append(ep.to_bytes(8, "little") + b'{"delta":"x"}')
    dj.close()
    (rdir / "epoch.json").write_text(json.dumps({"epoch": marker_epoch}))


def _seed_checkpoint(home: Path) -> Path:
    import hashlib

    step = home / "checkpoints" / "step_10"
    step.mkdir(parents=True, exist_ok=True)
    data = b"shard-bytes-abcdef"
    (step / "shard_00000_of_00001.npz").write_bytes(data)
    (step / "manifest.json").write_text(json.dumps({
        "format": 1, "step": 10, "num_processes": 1, "keys": {},
        "shards": [{"file": "shard_00000_of_00001.npz",
                    "sha256": hashlib.sha256(data).hexdigest(),
                    "rows": 1}]}))
    return step


def _db_event_ids(path: Path) -> set[str]:
    ev = SQLiteEvents({"path": str(path)})
    try:
        return {e.event_id for e in ev.find(EventQuery(app_id=1))}
    finally:
        ev.close()


# ---------------------------------------------------------------------------
# backup + restore roundtrip


def test_backup_restore_roundtrip_exactly_once(tmp_path):
    home = tmp_path / "home"
    events = _seed_home(home)
    _seed_router(home)
    _seed_checkpoint(home)
    broot = tmp_path / "bk"

    rep = B.create_backup(home, backup_dir=broot)
    assert rep["seq"] == 1 and rep["files"] >= 6

    target = tmp_path / "restored"
    rr = B.restore(broot, target)
    # the WAL in the backup held all 10 records, 4 overlapping the DB
    # snapshot — id-keyed replay must land exactly-once
    assert rr["replayedRecords"] == len(events)
    assert _db_event_ids(target / "events.db") == \
        {e.event_id for e in events}
    assert (target / "models" / "inst-ok").read_bytes() == \
        (home / "models" / "inst-ok").read_bytes()
    assert (target / "models" / "inst-ok.sha256").read_text() == \
        (home / "models" / "inst-ok.sha256").read_text()
    assert (target / "checkpoints" / "step_10" / "manifest.json").exists()
    assert json.loads((target / "run" / "fleet-router" /
                       "epoch.json").read_text())["epoch"] == 3
    # metadata restored queryable
    meta = MetadataStore(str(target / "metadata.db"))
    try:
        assert meta.engine_instance_get("inst-ok").status == "COMPLETED"
    finally:
        meta.close()
    # status surface
    lines = "\n".join(B.status_lines(home, broot))
    assert "last backup: #1" in lines


def test_backup_consistent_under_live_appends(tmp_path):
    """A writer hammering the WAL while the backup copies must never
    tear the snapshot: every journal record in the backup parses, and
    restore lands a prefix of what was written."""
    home = tmp_path / "home"
    _seed_home(home, n_db=0, n_tail=0)
    broot = tmp_path / "bk"
    stop = threading.Event()
    written = []

    def writer():
        j = EventJournal(home / "journal", fsync="never")
        i = 10
        while not stop.is_set() and i < 500:
            e = Event(event="rate", entity_type="user", entity_id=f"w{i}",
                      event_id=f"live{i:04d}")
            j.append(_wal_payload(e))
            written.append(e.event_id)
            i += 1
        j.close()

    t = threading.Thread(target=writer)
    t.start()
    try:
        rep = B.create_backup(home, backup_dir=broot)
    finally:
        stop.set()
        t.join()
    assert rep["seq"] == 1
    target = tmp_path / "restored"
    rr = B.restore(broot, target)
    got = _db_event_ids(target / "events.db")
    # a consistent cut: some prefix of the live stream, nothing else,
    # nothing torn (a torn record would have been dropped by framing,
    # not produce a wrong event)
    assert got <= set(written)
    assert rr["replayedRecords"] == len(got)


# ---------------------------------------------------------------------------
# manifest discipline: torn writes, bitflips, retention, dedup


def test_manifest_torn_write_and_bitflip_walk(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    B.create_backup(home, backup_dir=broot)
    b2_manifest = broot / "backup-00000002" / B.MANIFEST_NAME
    pristine = b2_manifest.read_bytes()

    # truncation walk: every cut point makes backup 2 not-exist
    for cut in (0, 4, len(pristine) // 2, len(pristine) - 1):
        b2_manifest.write_bytes(pristine[:cut])
        complete, partial = B.list_backups(broot)
        assert [s for s, *_ in complete] == [1], f"cut={cut}"
        assert [s for s, _ in partial] == [2], f"cut={cut}"

    # single bitflip mid-payload: CRC catches it
    flipped = bytearray(pristine)
    flipped[len(flipped) // 2] ^= 0x40
    b2_manifest.write_bytes(bytes(flipped))
    complete, partial = B.list_backups(broot)
    assert [s for s, *_ in complete] == [1]
    assert [s for s, _ in partial] == [2]

    # the corrupted backup is reported, never silently used
    target = tmp_path / "restored"
    rr = B.restore(broot, target)
    assert rr["backup"] == 1
    assert rr["skippedPartial"] == [2]
    with pytest.raises(B.BackupError, match="incomplete or corrupt"):
        B.restore(broot, tmp_path / "r2", backup_id=2)

    # a complete backup with a silently corrupted FILE fails verify
    b2_manifest.write_bytes(pristine)
    blob_copy = broot / "backup-00000002" / "home" / "models" / "inst-ok"
    raw = bytearray(blob_copy.read_bytes())
    raw[0] ^= 0xFF
    blob_copy.write_bytes(bytes(raw))
    with pytest.raises(B.BackupError, match="failed verification"):
        B.restore(broot, tmp_path / "r3", backup_id=2)


def test_incremental_hardlink_dedup(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    broot = tmp_path / "bk"
    rep1 = B.create_backup(home, backup_dir=broot)
    rep2 = B.create_backup(home, backup_dir=broot)
    assert rep1["dedupedFiles"] == 0
    assert rep2["dedupedFiles"] >= 2  # blob + sidecar + sealed segments
    assert rep2["bytes"] < rep1["bytes"]
    rel = Path("home") / "models" / "inst-ok"
    st1 = (broot / "backup-00000001" / rel).stat()
    st2 = (broot / "backup-00000002" / rel).stat()
    assert st1.st_ino == st2.st_ino  # same inode: hardlinked, not copied

    # change the blob: the third backup must re-copy it
    blob = b"retrained-model-bytes!"
    (home / "models" / "inst-ok").write_bytes(blob)
    (home / "models" / "inst-ok.sha256").write_text(
        Model.compute_checksum(blob))
    B.create_backup(home, backup_dir=broot)
    st3 = (broot / "backup-00000003" / rel).stat()
    assert st3.st_ino != st1.st_ino
    target = tmp_path / "restored"
    B.restore(broot, target)
    assert (target / "models" / "inst-ok").read_bytes() == blob


def test_retention_counts_only_complete_backups(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    broot = tmp_path / "bk"
    for _ in range(4):
        B.create_backup(home, backup_dir=broot, keep=2)
    complete, partial = B.list_backups(broot)
    assert [s for s, *_ in complete] == [3, 4]
    assert partial == []
    # a crashed (manifest-less) attempt is swept by the next backup
    debris = broot / "backup-00000007"
    debris.mkdir()
    (debris / "half-copied").write_bytes(b"x")
    rep = B.create_backup(home, backup_dir=broot, keep=2)
    assert rep["seq"] == 8
    assert not debris.exists()
    # the oldest backups were pruned, yet the survivors still restore
    # (hardlinked inodes stay alive across the prune)
    B.restore(broot, tmp_path / "restored")


def test_dr_lock_excludes_concurrent_runs(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    with B._DrLock(home):
        with pytest.raises(B.DrLocked, match="already running"):
            B.create_backup(home, backup_dir=broot)
        with pytest.raises(B.DrLocked):
            B.restore(broot, home, force=True)
    # a stale lock (dead pid) is stolen, not fatal
    (home / "run" / "dr.lock").write_text("999999999")
    B.create_backup(home, backup_dir=broot)


# ---------------------------------------------------------------------------
# restore refusal + chaos site


def test_restore_refuses_nonempty_target_without_force(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    target = tmp_path / "occupied"
    target.mkdir()
    (target / "precious.txt").write_text("do not clobber")
    with pytest.raises(B.RestoreRefused, match="not empty"):
        B.restore(broot, target)
    assert (target / "precious.txt").read_text() == "do not clobber"
    # the CLI maps the refusal to exit code 2
    with pytest.raises(SystemExit) as exc:
        pio(["restore", "--backup-dir", str(broot), "--target", str(target)])
    assert exc.value.code == 2
    # --force proceeds
    assert pio(["restore", "--backup-dir", str(broot), "--target",
                str(target), "--force"]) == 0
    assert (target / "models" / "inst-ok").exists()


def test_restore_apply_fault_leaves_backup_intact(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    target = tmp_path / "restored"
    FAULTS.inject("restore.apply", "error", times=1)
    with pytest.raises(Exception):
        B.restore(broot, target)
    assert FAULTS.fired("restore.apply") == 1
    # the backup is read-only under restore: still complete, and a
    # re-run onto the half-written target completes the job
    complete, _ = B.list_backups(broot)
    assert [s for s, *_ in complete] == [1]
    rr = B.restore(broot, target, force=True)
    assert rr["backup"] == 1
    assert (target / "models" / "inst-ok").exists()


# ---------------------------------------------------------------------------
# point-in-time recovery


def test_pitr_until_ordinal(tmp_path):
    home = tmp_path / "home"
    events = _seed_home(home, n_db=4, n_tail=6)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    target = tmp_path / "restored"
    rr = B.restore(broot, target, until="7")
    assert rr["walTruncated"] is True
    # only the first 7 WAL records (which include the 4 DB-overlap
    # events) survive the cut
    assert _db_event_ids(target / "events.db") == \
        {e.event_id for e in events[:7]}
    # the post-cut tail is DROPPED: no later drainer can resurrect it
    assert list((target / "journal").glob("journal-*.log")) == []


def test_pitr_until_timestamp(tmp_path):
    home = tmp_path / "home"
    events = _seed_home(home, n_db=4, n_tail=6)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    target = tmp_path / "restored"
    cut = "2026-01-01T00:00:05Z"  # events 0..5 have eventTime <= :05
    rr = B.restore(broot, target, until=cut)
    assert rr["walTruncated"] is True
    assert _db_event_ids(target / "events.db") == \
        {e.event_id for e in events[:6]}


# ---------------------------------------------------------------------------
# fsck invariant matrix


def test_fsck_clean_home(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    _seed_router(home)
    _seed_checkpoint(home)
    rep = B.fsck(home)
    assert rep["verdict"] == "clean"
    assert rep["checked"]["blobs"] == 1
    assert rep["checked"]["checkpointSteps"] == 1
    assert rep["checked"]["journalSegments"] >= 1
    assert rep["checked"]["routerEpoch"] is True
    state = json.loads((home / "run" / B.FSCK_STATE).read_text())
    assert state["verdict"] == "clean"
    assert "last fsck: clean" in "\n".join(B.status_lines(home))


def test_fsck_detects_and_repairs_each_corruption_class(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    _seed_router(home)
    step = _seed_checkpoint(home)

    # 1. flipped blob byte
    blob_path = home / "models" / "inst-ok"
    raw = bytearray(blob_path.read_bytes())
    raw[3] ^= 0x01
    blob_path.write_bytes(bytes(raw))
    # 2. deleted checkpoint shard
    (step / "shard_00000_of_00001.npz").unlink()
    # 3. truncated/torn WAL segment: garbage past the last valid frame
    seg = sorted((home / "journal").glob("journal-*.log"))[0]
    good_len = seg.stat().st_size
    with open(seg, "ab") as fh:
        fh.write(b"\xde\xad\xbe\xef-torn-tail")
    # 4. regressed router epoch marker (journal floor is 3)
    (home / "run" / "fleet-router" / "epoch.json").write_text(
        json.dumps({"epoch": 1}))

    rep = B.fsck(home)
    by_inv = {v["invariant"] for v in rep["violations"]}
    assert by_inv == {"blob", "checkpoint", "journal", "router_epoch"}
    assert rep["verdict"] != "clean"
    assert rep["repaired"] == 0

    rep = B.fsck(home, repair=True)
    assert rep["repaired"] == len(rep["violations"]) == 4
    # blob + step quarantined, never deleted
    assert not blob_path.exists()
    assert (home / "quarantine" / "models" / "inst-ok").exists()
    assert not step.exists()
    assert (home / "quarantine" / "checkpoints" / "step_10").exists()
    # torn segment truncated back to its valid prefix
    assert seg.stat().st_size == good_len
    # marker re-seated at the journal floor
    assert json.loads((home / "run" / "fleet-router" /
                       "epoch.json").read_text())["epoch"] == 3
    # re-audit: only the (correctly) missing quarantined blob remains
    rep = B.fsck(home)
    assert {v["invariant"] for v in rep["violations"]} <= {"blob"}
    assert all("no blob" in v["detail"] for v in rep["violations"])


def test_fsck_clamps_cursor_past_tail(tmp_path):
    home = tmp_path / "home"
    _seed_home(home)
    cursor = home / "journal" / "cursor.json"
    cursor.write_text(json.dumps({"seq": 99, "off": 12345, "idx": 7}))
    rep = B.fsck(home)
    assert any(v["invariant"] == "journal" and "past journal tail"
               in v["detail"] for v in rep["violations"])
    B.fsck(home, repair=True)
    cur = json.loads(cursor.read_text())
    assert cur["seq"] == 0  # clamped to the real tail segment
    # and the journal still opens cleanly
    j = EventJournal(home / "journal")
    j.close()
    rep = B.fsck(home)
    assert not any(v["invariant"] == "journal" for v in rep["violations"])


# ---------------------------------------------------------------------------
# orphan-blob gc


def test_gc_blobs_deletes_only_unreferenced(tmp_path, capsys):
    home = tmp_path / "home"
    _seed_home(home)
    meta = MetadataStore(str(home / "metadata.db"))
    meta.engine_instance_insert(EngineInstance(
        id="inst-dead", status="ABANDONED", engine_id="e1"))
    meta.close()
    models = home / "models"
    (models / "inst-dead").write_bytes(b"leaked")
    (models / "inst-dead.sha256").write_text(
        Model.compute_checksum(b"leaked"))
    (models / "inst-stray").write_bytes(b"no instance at all")

    rep = B.fsck(home)
    assert set(rep["orphanBlobs"]) == {"inst-dead", "inst-stray"}

    rep = B.gc_blobs(home, dry_run=True)
    assert set(rep["orphans"]) == {"inst-dead", "inst-stray"}
    assert (models / "inst-dead").exists()  # dry run touches nothing

    rep = B.gc_blobs(home)
    assert rep["deleted"] == 2
    assert not (models / "inst-dead").exists()
    assert not (models / "inst-dead.sha256").exists()
    assert not (models / "inst-stray").exists()
    assert (models / "inst-ok").exists()  # the COMPLETED one survives

    monkey_home = os.environ.get("PIO_HOME")
    try:
        os.environ["PIO_HOME"] = str(home)
        assert pio(["admin", "gc", "--blobs", "--dry-run"]) == 0
        out = capsys.readouterr().out
        assert "No orphaned model blobs" in out
    finally:
        if monkey_home is None:
            os.environ.pop("PIO_HOME", None)
        else:
            os.environ["PIO_HOME"] = monkey_home


# ---------------------------------------------------------------------------
# export/import satellite: idempotent re-import


def test_reimport_is_idempotent(tmp_path, capsys):
    Storage.configure("EVENTDATA", "sqlite",
                      path=str(tmp_path / "events.db"))
    assert pio(["app", "new", "drapp"]) == 0
    app = Storage.get_metadata().app_get_by_name("drapp")
    events_file = tmp_path / "in.jsonl"
    events_file.write_text("\n".join(
        json.dumps(event_to_api_dict(_event(i))) for i in range(8)))

    assert pio(["import", "events", "--appid", str(app.id),
                "--input", str(events_file)]) == 0
    store = Storage.get_events()
    n1 = sum(1 for _ in store.find(EventQuery(app_id=app.id)))
    assert n1 == 8
    # re-import the same file: id-keyed upsert, counts never double
    assert pio(["import", "events", "--appid", str(app.id),
                "--input", str(events_file)]) == 0
    n2 = sum(1 for _ in store.find(EventQuery(app_id=app.id)))
    assert n2 == 8
    # export round-trips the same ids
    out_file = tmp_path / "out.jsonl"
    assert pio(["export", "events", "--appid", str(app.id),
                "--output", str(out_file)]) == 0
    exported = {json.loads(ln)["eventId"]
                for ln in out_file.read_text().splitlines()}
    assert exported == {f"ev{i:04d}" for i in range(8)}


def test_import_rejects_unknown_channel_name(tmp_path, capsys):
    assert pio(["app", "new", "chapp"]) == 0
    app = Storage.get_metadata().app_get_by_name("chapp")
    f = tmp_path / "in.jsonl"
    f.write_text(json.dumps(event_to_api_dict(_event(0))))
    with pytest.raises(SystemExit):
        pio(["import", "events", "--appid", str(app.id),
             "--channel", "nope", "--input", str(f)])


# ---------------------------------------------------------------------------
# bench surface


def test_bench_backup_reports_throughput(capsys):
    assert pio(["bench", "backup", "--files", "4", "--size-kb", "8",
                "--rounds", "2"]) == 0
    out = capsys.readouterr().out
    assert "backup bench" in out
    assert "round 1 (incremental)" in out


# ---------------------------------------------------------------------------
# acceptance drill 1: SIGKILL mid-second-backup


def test_sigkill_mid_second_backup_prior_backup_survives(tmp_path):
    """A host dying mid-backup (hang at the backup.copy chaos site +
    SIGKILL) must leave the PREVIOUS backup manifest-complete and
    restorable; the debris is manifest-less and swept later."""
    home = tmp_path / "home"
    events = _seed_home(home)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)

    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from predictionio_tpu.faults import FAULTS\n"
        "FAULTS.inject('backup.copy', 'hang', times=1, after=2,\n"
        "              max_hang_s=90)\n"
        "from predictionio_tpu.storage.backup import create_backup\n"
        f"create_backup({str(home)!r}, backup_dir={str(broot)!r})\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env)
    try:
        partial_dir = broot / "backup-00000002"
        deadline = time.time() + 90
        while time.time() < deadline:
            if partial_dir.exists() and proc.poll() is None:
                time.sleep(0.3)  # let it reach the armed hang
                break
            time.sleep(0.1)
        assert partial_dir.exists(), "second backup never started"
        assert proc.poll() is None, "backup subprocess died early"
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    # the kill left a manifest-less partial; backup 1 is untouched
    complete, partial = B.list_backups(broot)
    assert [s for s, *_ in complete] == [1]
    assert [s for s, _ in partial] == [2]
    # the lock the dead process held is stale, and the prior backup
    # restores the full dataset
    target = tmp_path / "restored"
    rr = B.restore(broot, target)
    assert rr["backup"] == 1
    assert rr["skippedPartial"] == [2]
    assert _db_event_ids(target / "events.db") == \
        {e.event_id for e in events}
    # the next backup sweeps the debris
    rep = B.create_backup(home, backup_dir=broot)
    assert rep["seq"] == 3
    assert not partial_dir.exists()


# ---------------------------------------------------------------------------
# acceptance drill 2: full train -> backup under ingest -> wipe ->
# restore -> redeploy with bitwise replay parity


def _drill_events_file(path: Path, rng, nu=20, ni=15) -> int:
    u = rng.normal(size=(nu, 3)) + 1
    v = rng.normal(size=(ni, 3)) + 1
    full = u @ v.T
    lines = []
    for uu in range(nu):
        for ii in range(ni):
            if rng.random() < 0.6:
                lines.append(json.dumps({
                    "event": "rate",
                    "entityType": "user", "entityId": f"u{uu}",
                    "targetEntityType": "item", "targetEntityId": f"i{ii}",
                    "properties": {"rating": float(full[uu, ii])},
                    "eventTime": "2020-01-01T00:00:00Z",
                    "eventId": f"drill{uu:03d}x{ii:03d}",
                }))
    path.write_text("\n".join(lines))
    return len(lines)


def test_disaster_drill_restore_serves_bitwise_identical(
        tmp_path, rng, monkeypatch):
    """Train + deploy + capture golden traffic, back up under live WAL
    appends, wipe $PIO_HOME, restore, redeploy — the restored instance
    must answer the captured traffic 100% bitwise-identically (PR-13
    replay harness) and event counts must be exactly-once."""
    from predictionio_tpu.obs.replay import replay_records
    from predictionio_tpu.workflow import resolve_engine_factory
    from predictionio_tpu.workflow.create_server import EngineServer

    home = tmp_path / "pio-home"
    home.mkdir()
    monkeypatch.setenv("PIO_HOME", str(home))

    def durable_storage():
        Storage.reset()
        Storage.configure("METADATA", "sqlite",
                          path=str(home / "metadata.db"))
        Storage.configure("EVENTDATA", "sqlite",
                          path=str(home / "events.db"))
        Storage.configure("MODELDATA", "localfs",
                          path=str(home / "models"))

    durable_storage()
    engine_dir = tmp_path / "myrec"
    shutil.copytree(REPO / "templates" / "recommendation", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "drilltest"
    (engine_dir / "engine.json").write_text(json.dumps(variant))

    assert pio(["app", "new", "drilltest"]) == 0
    app = Storage.get_metadata().app_get_by_name("drilltest")
    events_file = tmp_path / "events.jsonl"
    n_imported = _drill_events_file(events_file, rng)
    assert pio(["import", "--appid", str(app.id),
                "--input", str(events_file)]) == 0
    assert pio(["build", "--engine-dir", str(engine_dir)]) == 0
    assert pio(["train", "--engine-dir", str(engine_dir)]) == 0
    insts = Storage.get_metadata().engine_instance_get_completed(
        "default", "1", "default")
    assert len(insts) == 1
    inst_id = insts[0].id

    # deploy + capture golden traffic
    engine = resolve_engine_factory("engine:engine_factory",
                                    engine_dir=engine_dir)
    server = EngineServer(engine, insts[0])
    records = []
    for i in range(10):
        req = {"user": f"u{i}", "num": 4}
        body = server.serve_query(req)
        records.append({"rid": f"golden{i}", "request": req,
                        "response": body, "status": 200})

    # stream deltas: undrained WAL tail + live appends during the backup
    tail = [Event(event="rate", entity_type="user", entity_id=f"u{i % 5}",
                  target_entity_type="item", target_entity_id=f"i{i % 7}",
                  properties={"rating": 1.0},
                  event_id=f"tail{i:04d}") for i in range(25)]
    j = EventJournal(home / "journal")
    for e in tail[:20]:
        j.append(_wal_payload(e, app_id=app.id))
    stop = threading.Event()

    def live_writer():
        for e in tail[20:]:
            if stop.is_set():
                break
            j.append(_wal_payload(e, app_id=app.id))
            time.sleep(0.002)

    t = threading.Thread(target=live_writer)
    t.start()
    broot = tmp_path / "bk"
    try:
        assert pio(["backup", "--backup-dir", str(broot)]) == 0
    finally:
        stop.set()
        t.join()
    j.close()

    # record what the backup's WAL actually fenced in
    complete, _ = B.list_backups(broot)
    assert [s for s, *_ in complete] == [1]

    # wipe the host
    Storage.reset()
    shutil.rmtree(home)

    # restore + reopen
    assert pio(["restore", "--backup-dir", str(broot),
                "--target", str(home)]) == 0
    durable_storage()

    # exactly-once: every imported event exactly once, plus exactly the
    # journaled tail records that made the fence (no doubles from the
    # DB/WAL overlap, no torn extras)
    got = {e.event_id for e in Storage.get_events().find(
        EventQuery(app_id=app.id))}
    imported = {f"drill{u:03d}x{i:03d}" for u in range(20)
                for i in range(15)}
    tail_ids = {e.event_id for e in tail}
    assert got - tail_ids == got & imported
    assert len(got & imported) == n_imported
    assert 20 <= len(got & tail_ids) <= 25

    # redeploy from the restored stores: same instance, bitwise parity
    insts2 = Storage.get_metadata().engine_instance_get_completed(
        "default", "1", "default")
    assert [i.id for i in insts2] == [inst_id]
    server2 = EngineServer(engine, insts2[0])
    report = replay_records(records, server=server2)
    assert report["total"] == 10
    assert report["tiers"]["bitwise"] == 10, report["mismatches"][:3]


def test_disaster_drill_pitr_mid_stream(tmp_path, monkeypatch):
    """Second drill: restore --until a mid-stream sequence and prove
    only pre-cut events are present in the recovered store."""
    home = tmp_path / "pio-home"
    events = _seed_home(home, n_db=3, n_tail=9)
    broot = tmp_path / "bk"
    B.create_backup(home, backup_dir=broot)
    target = tmp_path / "recovered"
    monkeypatch.setenv("PIO_HOME", str(target))
    assert pio(["restore", "--backup-dir", str(broot), "--until", "8"]) == 0
    got = _db_event_ids(target / "events.db")
    assert got == {e.event_id for e in events[:8]}
    # and nothing post-cut can ever be drained back in
    assert list((target / "journal").glob("journal-*.log")) == []
