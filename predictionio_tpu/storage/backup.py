"""Disaster recovery for every durable store under ``$PIO_HOME``.

Each store in the system is individually crash-safe — the ingest WAL
(journal.py), the sha256-sidecar blob store (registry.py), sharded
training checkpoints (workflow/checkpoint.py), the durable router state
(workflow/fleet.py) — but none of that survives losing the disk.  This
module is the cross-store recovery layer:

* ``create_backup`` takes a consistent, integrity-verified snapshot of
  ALL durable state: sqlite databases are copied through sqlite3's
  online backup API (never torn under a live server), everything else
  is copied behind a size fence recorded AFTER the database cut, so the
  WAL tail in the backup always covers the window between the database
  snapshot and the fence.  A backup EXISTS only when its CRC-framed
  manifest parses — the PR-8 checkpoint discipline applied store-wide.
  Incremental mode hardlinks files whose (path, size, mtime) or content
  hash matches the previous complete backup.
* ``restore`` rebuilds a fresh ``$PIO_HOME`` from any complete backup:
  re-verifies every checksum first, refuses a non-empty target without
  ``force``, and supports point-in-time recovery by replaying the
  backed-up WAL tail through the same id-keyed exactly-once insert path
  the drain loop uses, optionally up to ``--until <ts|seq>``.
* ``fsck`` audits the cross-store invariants standalone: COMPLETED
  instances' blobs exist and match their checksums, checkpoint
  manifests list only present shards, journal cursors sit at or before
  a validly-framed tail, and the router epoch marker is never behind
  its delta journal.  ``repair=True`` quarantines or clamps rather than
  deletes.
* ``gc_blobs`` deletes model blobs unreferenced by any non-retired
  EngineInstance (ABORTED/ABANDONED attempts otherwise leak blobs
  forever).

Backup and restore share one lockfile (``$PIO_HOME/run/dr.lock``) so
they can never run concurrently against the same home.

Chaos sites: ``backup.copy`` fires before each file enters a backup,
``restore.apply`` before each file is materialized into the target —
both registered in faults.py SITES.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import sqlite3
import struct
import time
import zlib
from datetime import datetime, timezone
from hashlib import sha256
from pathlib import Path
from typing import Iterable

from ..obs.metrics import METRICS
from ..faults import FAULTS
from .journal import iter_journal_records

__all__ = [
    "BackupError",
    "DrLocked",
    "RestoreRefused",
    "create_backup",
    "fsck",
    "gc_blobs",
    "list_backups",
    "read_manifest",
    "restore",
    "run_backup_bench",
    "status_lines",
    "verify_backup",
]

# Same on-disk framing as the ingest WAL (journal.py): little-endian
# (payload length, crc32(payload)) ahead of the JSON payload.  A torn or
# bit-flipped manifest fails the CRC and the backup simply does not exist.
_FRAME = struct.Struct("<II")
MANIFEST_NAME = "MANIFEST.bin"
MANIFEST_FORMAT = 1

_BACKUP_RE = re.compile(r"^backup-(\d{8})$")
_SEGMENT_RE = re.compile(r"^journal-(\d{8})\.log$")
_STEP_RE = re.compile(r"^step_(\d+)$")

# Home entries that are rebuildable scratch, not durable state.
_EXCLUDE_TOP = ("backups", "log", "quarantine")
# sqlite scratch siblings: the online backup API folds the WAL into the
# snapshot, so copying these raw would only tear.
_SQLITE_SCRATCH = ("-wal", "-shm", "-journal")

FSCK_STATE = "fsck-last.json"  # under $PIO_HOME/run/, read by `pio status`

_RETIRED_STATUSES = ("ABORTED", "ABANDONED")

_BACKUP_TOTAL = METRICS.counter(
    "pio_backup_total", "Backups attempted, by terminal status.",
    labelnames=("status",))
_BACKUP_BYTES = METRICS.counter(
    "pio_backup_bytes_total",
    "Bytes physically written into backups (dedup hardlinks excluded).")
_BACKUP_DEDUP = METRICS.counter(
    "pio_backup_dedup_files_total",
    "Files satisfied by hardlinking an identical copy from the previous "
    "complete backup instead of rewriting the bytes.")
_BACKUP_LAST_SEQ = METRICS.gauge(
    "pio_backup_last_success_seq",
    "Sequence number of the newest manifest-complete backup.")
_RESTORE_TOTAL = METRICS.counter(
    "pio_backup_restore_total", "Restores attempted, by terminal status.",
    labelnames=("status",))
_RESTORE_REPLAYED = METRICS.counter(
    "pio_backup_restore_replayed_records_total",
    "WAL records replayed through the id-keyed drain path during restore.")
_VERIFY_FAILURES = METRICS.counter(
    "pio_backup_verify_failures_total",
    "Checksum or manifest failures found while verifying backups.")
_FSCK_RUNS = METRICS.counter(
    "pio_fsck_runs_total", "fsck runs, by verdict.", labelnames=("verdict",))
_FSCK_VIOLATIONS = METRICS.counter(
    "pio_fsck_violations_total",
    "Cross-store integrity violations found by fsck, by invariant.",
    labelnames=("invariant",))
_FSCK_ORPHAN_BLOBS = METRICS.gauge(
    "pio_fsck_orphan_blobs",
    "Model blobs unreferenced by any non-retired engine instance, as of "
    "the last fsck or gc run.")
_FSCK_GC_DELETED = METRICS.counter(
    "pio_fsck_gc_deleted_total",
    "Orphaned model blobs deleted by `pio admin gc --blobs`.")

for _s in ("ok", "error"):
    _BACKUP_TOTAL.labels(status=_s)
for _s in ("ok", "error", "refused", "verify_failed"):
    _RESTORE_TOTAL.labels(status=_s)
for _s in ("clean", "violations"):
    _FSCK_RUNS.labels(verdict=_s)
for _s in ("blob", "checkpoint", "journal", "router_epoch"):
    _FSCK_VIOLATIONS.labels(invariant=_s)
del _s


class BackupError(RuntimeError):
    """Backup/restore could not proceed (corrupt input, no backups, ...)."""


class DrLocked(BackupError):
    """Another backup/restore holds the dr.lock for this home."""


class RestoreRefused(BackupError):
    """Target home is non-empty and ``force`` was not given (CLI exit 2)."""


# --------------------------------------------------------------------------
# small file plumbing (same idiom as workflow/checkpoint.py)

def _fsync_file(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: Path) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _sha256_file(path: Path, limit: int | None = None) -> str:
    h = sha256()
    remaining = limit
    with open(path, "rb") as fh:
        while True:
            n = 1 << 20 if remaining is None else min(1 << 20, remaining)
            if n <= 0:
                break
            chunk = fh.read(n)
            if not chunk:
                break
            h.update(chunk)
            if remaining is not None:
                remaining -= len(chunk)
    return h.hexdigest()


def _copy_hashed(src: Path, dst: Path, limit: int | None = None) -> tuple[str, int]:
    """Copy ``src`` (up to ``limit`` bytes — the journal fence) to ``dst``
    via tmp+fsync+rename, hashing the copied bytes in one pass."""
    tmp = dst.with_name(dst.name + ".tmp")
    h = sha256()
    copied = 0
    with open(src, "rb") as rf, open(tmp, "wb") as wf:
        remaining = limit
        while True:
            n = 1 << 20 if remaining is None else min(1 << 20, remaining)
            if n <= 0:
                break
            chunk = rf.read(n)
            if not chunk:
                break
            h.update(chunk)
            wf.write(chunk)
            copied += len(chunk)
            if remaining is not None:
                remaining -= len(chunk)
        wf.flush()
        os.fsync(wf.fileno())
    os.replace(tmp, dst)
    return h.hexdigest(), copied


def _atomic_json(path: Path, obj: dict) -> None:
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(obj, sort_keys=True))
    _fsync_file(tmp)
    os.replace(tmp, path)
    _fsync_dir(path.parent)


def _utcnow_iso() -> str:
    return datetime.now(timezone.utc).isoformat()


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


class _DrLock:
    """``$PIO_HOME/run/dr.lock``: backup and restore are mutually
    exclusive per home.  O_EXCL-create with our pid inside; a lock whose
    pid is dead is stale and stolen."""

    def __init__(self, home: Path):
        self.path = Path(home) / "run" / "dr.lock"

    def __enter__(self) -> "_DrLock":
        self.path.parent.mkdir(parents=True, exist_ok=True)
        for _ in range(3):
            try:
                fd = os.open(self.path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                try:
                    pid = int(self.path.read_text().strip() or "0")
                except (OSError, ValueError):
                    pid = 0
                if pid and _pid_alive(pid):
                    raise DrLocked(
                        f"backup/restore already running (pid {pid} holds "
                        f"{self.path}); retry when it finishes")
                try:  # stale: holder died without cleanup
                    self.path.unlink()
                except FileNotFoundError:
                    pass
                continue
            os.write(fd, str(os.getpid()).encode())
            os.close(fd)
            return self
        raise DrLocked(f"could not acquire {self.path}")

    def __exit__(self, *exc) -> None:
        try:
            self.path.unlink()
        except FileNotFoundError:
            pass


# --------------------------------------------------------------------------
# manifest framing

def _write_manifest(bdir: Path, manifest: dict) -> None:
    payload = json.dumps(manifest, sort_keys=True).encode()
    tmp = bdir / (MANIFEST_NAME + ".tmp")
    with open(tmp, "wb") as fh:
        fh.write(_FRAME.pack(len(payload), zlib.crc32(payload)))
        fh.write(payload)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, bdir / MANIFEST_NAME)
    _fsync_dir(bdir)
    _fsync_dir(bdir.parent)


def read_manifest(bdir: Path) -> dict | None:
    """The backup's manifest, or None if absent/truncated/corrupt — a
    backup without a readable manifest does not exist."""
    try:
        raw = (Path(bdir) / MANIFEST_NAME).read_bytes()
    except OSError:
        return None
    if len(raw) < _FRAME.size:
        return None
    length, crc = _FRAME.unpack(raw[:_FRAME.size])
    payload = raw[_FRAME.size:_FRAME.size + length]
    if len(payload) < length or zlib.crc32(payload) != crc:
        return None
    try:
        m = json.loads(payload)
    except ValueError:
        return None
    if not isinstance(m, dict) or m.get("format") != MANIFEST_FORMAT:
        return None
    return m


def _is_complete(bdir: Path, manifest: dict) -> bool:
    for f in manifest.get("files", ()):
        p = bdir / f["path"]
        try:
            if p.stat().st_size != f["bytes"]:
                return False
        except OSError:
            return False
    return True


def list_backups(root: Path) -> tuple[list[tuple[int, Path, dict]],
                                      list[tuple[int, Path]]]:
    """(complete, partial) backups under ``root``, each oldest-first.
    Complete means the CRC-framed manifest parses AND every listed file
    is present at its recorded size — anything else is a crashed or
    corrupted attempt and is never restored from."""
    root = Path(root)
    complete: list[tuple[int, Path, dict]] = []
    partial: list[tuple[int, Path]] = []
    if not root.is_dir():
        return complete, partial
    for p in sorted(root.iterdir()):
        m = _BACKUP_RE.match(p.name)
        if not m or not p.is_dir():
            continue
        seq = int(m.group(1))
        manifest = read_manifest(p)
        if manifest is not None and _is_complete(p, manifest):
            complete.append((seq, p, manifest))
        else:
            partial.append((seq, p))
    return complete, partial


# --------------------------------------------------------------------------
# backup

def _under(child: Path, parent: Path) -> bool:
    try:
        child.resolve().relative_to(parent.resolve())
        return True
    except ValueError:
        return False


def _scan_home(home: Path, backup_root: Path) -> tuple[list[Path], list[Path]]:
    """(sqlite_dbs, plain_files) of durable state under home.  Scratch
    trees, pidfiles, the dr.lock and the backup root itself are skipped;
    sqlite WAL/SHM siblings are folded by the online backup instead."""
    dbs: list[Path] = []
    plain: list[Path] = []
    if not home.is_dir():
        return dbs, plain
    broot = backup_root.resolve()
    for top in sorted(home.iterdir()):
        if top.name in _EXCLUDE_TOP or top.resolve() == broot:
            continue
        paths = [top] if top.is_file() else sorted(top.rglob("*"))
        for p in paths:
            if not p.is_file() or p.is_symlink():
                continue
            if broot in p.resolve().parents:
                continue  # backups never nest backups
            name = p.name
            if name.endswith(".tmp") or name.endswith(".pid"):
                continue
            if name == "dr.lock" or name == FSCK_STATE:
                continue
            if any(name.endswith(f".db{s}") for s in _SQLITE_SCRATCH):
                continue
            if name.endswith(".db"):
                dbs.append(p)
            else:
                plain.append(p)
    return dbs, plain


def _backup_sqlite(src: Path, dst: Path) -> tuple[str, int]:
    """Snapshot a live sqlite database through the online backup API —
    the copy is transactionally consistent even mid-write."""
    tmp = dst.with_name(dst.name + ".tmp")
    if tmp.exists():
        tmp.unlink()
    try:
        con = sqlite3.connect(str(src))
        try:
            out = sqlite3.connect(str(tmp))
            try:
                con.backup(out)
            finally:
                out.close()
        finally:
            con.close()
    except sqlite3.Error:
        # a .db that is not actually sqlite: plain fenced copy instead
        try:
            tmp.unlink()
        except FileNotFoundError:
            pass
        return _copy_hashed(src, dst, limit=src.stat().st_size)
    _fsync_file(tmp)
    os.replace(tmp, dst)
    digest = _sha256_file(dst)
    return digest, dst.stat().st_size


def create_backup(home: str | os.PathLike | None = None, *,
                  backup_dir: str | os.PathLike | None = None,
                  keep: int = 5, mode: str = "incremental",
                  journal_dir: str | os.PathLike | None = None,
                  checkpoint_dir: str | os.PathLike | None = None) -> dict:
    """Take one manifest-committed snapshot of all durable state.

    Ordering is the consistency argument: sqlite databases are cut
    first (online backup API), then every other file's size is fenced
    at a single pass and copied up to that fence — so the WAL tail in
    the snapshot strictly covers the window after the database cut, and
    replaying it at restore time (id-keyed, idempotent) closes the gap.
    """
    from .registry import Storage
    home = Path(home) if home is not None else Path(Storage.home())
    root = Path(backup_dir) if backup_dir is not None else home / "backups"
    if mode not in ("incremental", "full"):
        raise BackupError(f"unknown backup mode {mode!r}")
    t0 = time.monotonic()
    with _DrLock(home):
        root.mkdir(parents=True, exist_ok=True)
        complete, partial = list_backups(root)
        all_seqs = [s for s, *_ in complete] + [s for s, _ in partial]
        seq = (max(all_seqs) + 1) if all_seqs else 1
        prev_dir: Path | None = None
        prev_files: dict[str, dict] = {}
        if mode == "incremental" and complete:
            _, prev_dir, prev_manifest = complete[-1]
            prev_files = {f["path"]: f for f in prev_manifest["files"]}
        bdir = root / f"backup-{seq:08d}"
        bdir.mkdir()
        try:
            report = _run_backup(
                home, bdir, seq, mode, prev_dir, prev_files,
                journal_dir=Path(journal_dir) if journal_dir else None,
                checkpoint_dir=Path(checkpoint_dir) if checkpoint_dir else None)
        except BaseException:
            _BACKUP_TOTAL.labels(status="error").inc()
            raise
        # retention: count only manifest-complete backups; crashed
        # partials older than this one are swept too.  Hardlinked
        # inodes stay alive in newer backups across the prune.
        complete, partial = list_backups(root)
        for s, p in partial:
            if s < seq:
                shutil.rmtree(p, ignore_errors=True)
        if keep > 0 and len(complete) > keep:
            for s, p, _m in complete[:len(complete) - keep]:
                shutil.rmtree(p, ignore_errors=True)
        _BACKUP_TOTAL.labels(status="ok").inc()
        _BACKUP_LAST_SEQ.set(seq)
        report["durationS"] = round(time.monotonic() - t0, 3)
        report["kept"] = min(len(complete), keep) if keep > 0 else len(complete)
        return report


def _run_backup(home: Path, bdir: Path, seq: int, mode: str,
                prev_dir: Path | None, prev_files: dict[str, dict], *,
                journal_dir: Path | None,
                checkpoint_dir: Path | None) -> dict:
    files: list[dict] = []
    bytes_written = 0
    deduped = 0

    def record(rel: str, digest: str, size: int, mtime_ns: int,
               kind: str, dedup: bool) -> None:
        files.append({"path": rel, "sha256": digest, "bytes": size,
                      "mtimeNs": mtime_ns, "kind": kind, "dedup": dedup})

    db_paths, plain_paths = _scan_home(home, bdir.parent)
    extra: list[tuple[str, Path, Path]] = []  # (prefix, root, file)
    for prefix, d in (("journal", journal_dir), ("checkpoints", checkpoint_dir)):
        if d is None or _under(d, home):
            continue  # under home → already in the home walk
        if d.is_dir():
            for p in sorted(d.rglob("*")):
                if p.is_file() and not p.is_symlink() \
                        and not p.name.endswith(".tmp"):
                    extra.append((prefix, d, p))

    # phase 1: database cut (online backup — never torn)
    for src in db_paths:
        rel = "home/" + src.relative_to(home).as_posix()
        FAULTS.fire("backup.copy")
        dst = bdir / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        digest, size = _backup_sqlite(src, dst)
        bytes_written += size
        record(rel, digest, size, dst.stat().st_mtime_ns, "sqlite", False)

    # phase 2: fence — one stat pass AFTER the database cut.  Append-only
    # files (WAL segments) are copied only up to this size so the
    # snapshot is a consistent cut; everything past it belongs to the
    # next backup.
    fenced: list[tuple[str, Path, int, int]] = []
    for src in plain_paths:
        try:
            st = src.stat()
        except OSError:
            continue  # vanished mid-scan (GC'd segment): not durable state
        fenced.append(("home/" + src.relative_to(home).as_posix(),
                       src, st.st_size, st.st_mtime_ns))
    for prefix, d, src in extra:
        try:
            st = src.stat()
        except OSError:
            continue
        fenced.append((f"{prefix}/" + src.relative_to(d).as_posix(),
                       src, st.st_size, st.st_mtime_ns))

    # phase 3: copy behind the fence, hardlinking unchanged files from
    # the previous complete backup
    for rel, src, size, mtime_ns in fenced:
        FAULTS.fire("backup.copy")
        dst = bdir / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        prev = prev_files.get(rel)
        if prev is not None and prev_dir is not None \
                and prev["bytes"] == size and prev.get("mtimeNs") == mtime_ns:
            try:
                os.link(prev_dir / rel, dst)
                deduped += 1
                record(rel, prev["sha256"], size, mtime_ns, "file", True)
                continue
            except OSError:
                pass  # cross-device or pruned: fall through to a copy
        try:
            digest, copied = _copy_hashed(src, dst, limit=size)
        except FileNotFoundError:
            continue  # vanished between fence and copy
        if prev is not None and prev_dir is not None \
                and prev["sha256"] == digest:
            # content unchanged, only mtime moved (resealed segment):
            # swap the fresh copy for a hardlink so retention dedups it
            try:
                os.link(prev_dir / rel, dst.with_name(dst.name + ".lnk"))
                os.replace(dst.with_name(dst.name + ".lnk"), dst)
                deduped += 1
                record(rel, digest, copied, mtime_ns, "file", True)
                continue
            except OSError:
                pass
        bytes_written += copied
        record(rel, digest, copied, mtime_ns, "file", False)

    manifest = {
        "format": MANIFEST_FORMAT,
        "seq": seq,
        "createdAt": _utcnow_iso(),
        "mode": mode,
        "basedOn": int(_BACKUP_RE.match(prev_dir.name).group(1))
                   if prev_dir is not None else None,
        "roots": {"home": str(home),
                  "journal": str(journal_dir) if journal_dir else None,
                  "checkpoints": str(checkpoint_dir) if checkpoint_dir else None},
        "files": files,
    }
    _write_manifest(bdir, manifest)
    _BACKUP_BYTES.inc(bytes_written)
    if deduped:
        _BACKUP_DEDUP.inc(deduped)
    return {"seq": seq, "dir": str(bdir), "mode": mode,
            "files": len(files), "bytes": bytes_written,
            "dedupedFiles": deduped,
            "basedOn": manifest["basedOn"]}


def verify_backup(bdir: Path, manifest: dict | None = None) -> list[str]:
    """Re-hash every file a backup's manifest lists; the list of
    violations (empty == restorable)."""
    bdir = Path(bdir)
    if manifest is None:
        manifest = read_manifest(bdir)
    if manifest is None:
        _VERIFY_FAILURES.inc()
        return [f"{bdir.name}: no valid manifest (torn or corrupt)"]
    bad: list[str] = []
    for f in manifest.get("files", ()):
        p = bdir / f["path"]
        try:
            if p.stat().st_size != f["bytes"]:
                bad.append(f"{f['path']}: size mismatch")
                continue
        except OSError:
            bad.append(f"{f['path']}: missing")
            continue
        if _sha256_file(p) != f["sha256"]:
            bad.append(f"{f['path']}: sha256 mismatch")
    if bad:
        _VERIFY_FAILURES.inc(len(bad))
    return bad


# --------------------------------------------------------------------------
# restore

def _home_nonempty(target: Path, backup_root: Path) -> bool:
    if not target.is_dir():
        return False
    for p in target.iterdir():
        if p.resolve() == backup_root.resolve():
            continue
        if p.name == "run" and p.is_dir():
            if any(q.name != "dr.lock" for q in p.iterdir()):
                return True
            continue
        return True
    return False


def _journal_roots(target: Path) -> list[Path]:
    """Top-level journal directories under ``target``: the parents of
    ``journal-*.log`` segments, collapsed through ``p<k>/`` partition
    subdirs to the partitioned root."""
    roots: set[Path] = set()
    for seg in target.rglob("journal-*.log"):
        if not _SEGMENT_RE.match(seg.name):
            continue
        d = seg.parent
        if re.fullmatch(r"p\d+", d.name) and (d.parent / "partitions.json").exists():
            d = d.parent
        roots.add(d)
    for pj in target.rglob("partitions.json"):
        roots.add(pj.parent)
    return sorted(roots)


def _iter_journal_dir(root: Path) -> Iterable[bytes]:
    """All records under one journal root, partition subdirs in order."""
    if (root / "partitions.json").exists():
        parts = sorted((d for d in root.iterdir()
                        if d.is_dir() and re.fullmatch(r"p\d+", d.name)),
                       key=lambda d: int(d.name[1:]))
        for d in parts:
            yield from iter_journal_records(d)
    else:
        yield from iter_journal_records(root)


def _parse_until(until) -> tuple[int | None, datetime | None]:
    """``--until`` is either a record ordinal (int: replay the first N
    WAL records) or an ISO-8601 timestamp (replay events with eventTime
    at or before it)."""
    if until is None:
        return None, None
    s = str(until).strip()
    if re.fullmatch(r"\d+", s):
        return int(s), None
    ts = datetime.fromisoformat(s.replace("Z", "+00:00"))
    if ts.tzinfo is None:
        ts = ts.replace(tzinfo=timezone.utc)
    return None, ts


def _replay_wal(target: Path, until) -> tuple[int, bool]:
    """Replay every event-journal record restored under ``target``
    through the id-keyed insert path (INSERT OR REPLACE by event_id —
    the drain loop's exactly-once discipline, so replay is idempotent).
    With a cut, the replayed journals are then removed: everything at
    or before the cut is in the database, everything after it must not
    survive for a later drainer to re-push."""
    from .event import event_from_api_dict
    from .sqlite import SQLiteEvents

    max_seq, max_ts = _parse_until(until)
    roots = [r for r in _journal_roots(target)
             if r.name != "delta-journal"]  # router deltas are not events
    if not roots:
        return 0, False
    backend = SQLiteEvents({"path": str(target / "events.db")})
    replayed = 0
    ordinal = 0
    try:
        groups: dict[tuple[int, int | None], list] = {}
        for root in roots:
            for payload in _iter_journal_dir(root):
                try:
                    obj = json.loads(payload)
                    ev = event_from_api_dict(obj["e"])
                    app_id = int(obj["a"])
                except (ValueError, KeyError, TypeError):
                    continue  # not an event record (foreign journal)
                ordinal += 1
                if max_seq is not None and ordinal > max_seq:
                    continue
                if max_ts is not None and ev.event_time is not None \
                        and ev.event_time > max_ts:
                    continue
                groups.setdefault((app_id, obj.get("c")), []).append(ev)
        for (app_id, channel_id), events in groups.items():
            for i in range(0, len(events), 500):
                backend.insert_batch(events[i:i + 500], app_id, channel_id)
            replayed += len(events)
    finally:
        close = getattr(backend, "close", None)
        if close:
            close()
    truncated = False
    if (max_seq is not None or max_ts is not None) and replayed >= 0:
        # point-in-time cut: drop the replayed WAL so a future drainer
        # cannot re-push post-cut records
        for root in roots:
            shutil.rmtree(root, ignore_errors=True)
            root.mkdir(parents=True, exist_ok=True)
        truncated = True
    _RESTORE_REPLAYED.inc(replayed)
    return replayed, truncated


def restore(backup_dir: str | os.PathLike,
            target_home: str | os.PathLike | None = None, *,
            backup_id: int | None = None, force: bool = False,
            until=None, replay: bool = True) -> dict:
    """Rebuild a home from a manifest-complete backup.

    Every checksum is re-verified before a single byte lands in the
    target; a non-empty target without ``force`` raises
    ``RestoreRefused`` (CLI exit 2).  Incomplete/corrupt backups are
    reported and never silently used.
    """
    from .registry import Storage
    root = Path(backup_dir)
    target = Path(target_home) if target_home is not None else Path(Storage.home())

    if not force and _home_nonempty(target, root):
        _RESTORE_TOTAL.labels(status="refused").inc()
        raise RestoreRefused(
            f"target {target} is not empty — pass --force to overwrite, "
            f"or restore into a fresh --target")

    complete, partial = list_backups(root)
    skipped = [s for s, _ in partial]
    if backup_id is not None:
        chosen = [c for c in complete if c[0] == backup_id]
        if not chosen:
            _RESTORE_TOTAL.labels(status="error").inc()
            if any(s == backup_id for s in skipped):
                raise BackupError(
                    f"backup {backup_id} is incomplete or corrupt "
                    f"(manifest missing/torn) — refusing to restore from it")
            raise BackupError(f"no backup {backup_id} under {root}")
        seq, bdir, manifest = chosen[0]
    elif complete:
        seq, bdir, manifest = complete[-1]
    else:
        _RESTORE_TOTAL.labels(status="error").inc()
        detail = f" ({len(skipped)} incomplete backup(s) ignored: " \
                 f"{skipped})" if skipped else ""
        raise BackupError(f"no complete backup under {root}{detail}")

    bad = verify_backup(bdir, manifest)
    if bad:
        _RESTORE_TOTAL.labels(status="verify_failed").inc()
        raise BackupError(
            f"backup {seq} failed verification, refusing to restore: "
            + "; ".join(bad[:5]))

    target.mkdir(parents=True, exist_ok=True)
    with _DrLock(target):
        applied = 0
        bytes_applied = 0
        try:
            for f in manifest["files"]:
                FAULTS.fire("restore.apply")
                rel = f["path"]
                prefix, _, tail = rel.partition("/")
                if prefix == "home":
                    dst = target / tail
                else:  # external journal/checkpoints roots land inside
                    dst = target / f"restored-{prefix}" / tail
                dst.parent.mkdir(parents=True, exist_ok=True)
                _copy_hashed(bdir / rel, dst)
                applied += 1
                bytes_applied += f["bytes"]
            replayed, truncated = (0, False)
            if replay:
                replayed, truncated = _replay_wal(target, until)
        except BaseException:
            _RESTORE_TOTAL.labels(status="error").inc()
            raise
        _RESTORE_TOTAL.labels(status="ok").inc()
        return {"backup": seq, "dir": str(bdir), "target": str(target),
                "files": applied, "bytes": bytes_applied,
                "replayedRecords": replayed, "walTruncated": truncated,
                "skippedPartial": skipped}


# --------------------------------------------------------------------------
# fsck

def _scan_segment_valid_len(path: Path) -> tuple[int, int]:
    """(valid byte length, whole-frame record count) of one segment."""
    valid = 0
    records = 0
    with open(path, "rb") as fh:
        while True:
            header = fh.read(_FRAME.size)
            if len(header) < _FRAME.size:
                break
            length, crc = _FRAME.unpack(header)
            payload = fh.read(length)
            if len(payload) < length or zlib.crc32(payload) != crc:
                break
            valid += _FRAME.size + length
            records += 1
    return valid, records


def _quarantine(home: Path, path: Path) -> Path:
    """Move a corrupt artifact under ``$PIO_HOME/quarantine/`` keeping
    its relative shape — never deleted by repair, only set aside."""
    qroot = home / "quarantine"
    try:
        rel = path.resolve().relative_to(home.resolve())
    except ValueError:
        rel = Path(path.name)
    dst = qroot / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    if dst.exists():
        dst = dst.with_name(dst.name + f".{int(time.time())}")
    shutil.move(str(path), str(dst))
    return dst


def _find_orphan_blobs(home: Path) -> list[str]:
    """Model blob ids in ``$PIO_HOME/models`` referenced by no
    non-retired EngineInstance (ABORTED/ABANDONED count as retired)."""
    from .metadata import MetadataStore
    models = home / "models"
    meta_path = home / "metadata.db"
    if not models.is_dir() or not meta_path.is_file():
        return []
    store = MetadataStore(str(meta_path))
    try:
        live = {i.id for i in store.engine_instance_get_all()
                if i.status not in _RETIRED_STATUSES}
    finally:
        store.close()
    orphans = []
    for p in sorted(models.iterdir()):
        if not p.is_file() or p.name.endswith(".sha256"):
            continue
        if p.name not in live:
            orphans.append(p.name)
    return orphans


def fsck(home: str | os.PathLike | None = None, *,
         journal_dir: str | os.PathLike | None = None,
         checkpoint_dir: str | os.PathLike | None = None,
         repair: bool = False) -> dict:
    """Audit the cross-store integrity invariants; optionally repair.

    Invariants (one counter label each):
      blob          every COMPLETED instance's model blob exists and
                    matches its .sha256 sidecar
      checkpoint    every checkpoint step manifest lists only present,
                    checksum-matching shards
      journal       segments are validly framed to their tail; cursors
                    point at or before it
      router_epoch  the fleet router epoch marker is >= the max epoch
                    in its delta journal

    ``repair=True`` quarantines corrupt blobs/steps under
    ``$PIO_HOME/quarantine/``, truncates torn segments to their last
    valid frame, clamps cursors, and rewrites a regressed epoch marker.
    Nothing is deleted.
    """
    from .metadata import MetadataStore
    from .registry import Storage
    home = Path(home) if home is not None else Path(Storage.home())
    violations: list[dict] = []
    checked = {"blobs": 0, "checkpointSteps": 0, "journalSegments": 0,
               "routerEpoch": False}

    def flag(invariant: str, path: Path, detail: str,
             repaired: bool = False) -> None:
        violations.append({"invariant": invariant, "path": str(path),
                           "detail": detail, "repaired": repaired})
        _FSCK_VIOLATIONS.labels(invariant=invariant).inc()

    # -- blob invariant
    meta_path = home / "metadata.db"
    models = home / "models"
    if meta_path.is_file():
        store = MetadataStore(str(meta_path))
        try:
            completed = store.engine_instance_get_by_status("COMPLETED")
        finally:
            store.close()
        for inst in completed:
            blob = models / inst.id
            checked["blobs"] += 1
            if not blob.is_file():
                flag("blob", blob, f"COMPLETED instance {inst.id} has no blob")
                continue
            sidecar = models / f"{inst.id}.sha256"
            if not sidecar.is_file():
                continue  # pre-integrity blob: presence is the invariant
            want = sidecar.read_text().strip()
            got = "sha256:" + _sha256_file(blob)
            if want != got:
                repaired = False
                if repair:
                    _quarantine(home, blob)
                    _quarantine(home, sidecar)
                    repaired = True
                flag("blob", blob,
                     f"checksum mismatch (sidecar {want[:23]}..., "
                     f"blob {got[:23]}...)", repaired)

    # -- checkpoint invariant
    ckpt = Path(checkpoint_dir) if checkpoint_dir else home / "checkpoints"
    if ckpt.is_dir():
        for step_dir in sorted(ckpt.iterdir()):
            if not step_dir.is_dir() or not _STEP_RE.match(step_dir.name):
                continue
            checked["checkpointSteps"] += 1
            mf = step_dir / "manifest.json"
            try:
                manifest = json.loads(mf.read_text())
                shards = manifest["shards"]
            except (OSError, ValueError, KeyError):
                repaired = False
                if repair:
                    _quarantine(home, step_dir)
                    repaired = True
                flag("checkpoint", step_dir, "unparseable manifest (torn step)",
                     repaired)
                continue
            broken = None
            for sh in shards:
                p = step_dir / sh["file"]
                if not p.is_file():
                    broken = f"manifest lists missing shard {sh['file']}"
                    break
                if sh.get("sha256") and _sha256_file(p) != sh["sha256"]:
                    broken = f"shard {sh['file']} checksum mismatch"
                    break
            if broken:
                repaired = False
                if repair:
                    _quarantine(home, step_dir)
                    repaired = True
                flag("checkpoint", step_dir, broken, repaired)

    # -- journal invariant
    jroots = _journal_roots(home)
    if journal_dir is not None and Path(journal_dir).is_dir():
        jroots.extend(r for r in _journal_roots(Path(journal_dir))
                      if r not in jroots)
    seen_dirs: list[Path] = []
    for root in jroots:
        dirs = [root]
        if (root / "partitions.json").exists():
            dirs = sorted((d for d in root.iterdir()
                           if d.is_dir() and re.fullmatch(r"p\d+", d.name)),
                          key=lambda d: int(d.name[1:]))
        seen_dirs.extend(dirs)
    for d in seen_dirs:
        segs = sorted(d.glob("journal-*.log"))
        seg_valid: dict[int, int] = {}
        for seg in segs:
            m = _SEGMENT_RE.match(seg.name)
            if not m:
                continue
            checked["journalSegments"] += 1
            valid, _n = _scan_segment_valid_len(seg)
            seg_valid[int(m.group(1))] = valid
            size = seg.stat().st_size
            if valid < size:
                repaired = False
                if repair:
                    with open(seg, "r+b") as fh:
                        fh.truncate(valid)
                        fh.flush()
                        os.fsync(fh.fileno())
                    repaired = True
                flag("journal", seg,
                     f"torn frame: {size - valid} trailing bytes past last "
                     f"valid record (valid prefix {valid}B)", repaired)
        cursor_file = d / "cursor.json"
        if cursor_file.is_file() and seg_valid:
            try:
                cur = json.loads(cursor_file.read_text())
                cseq, coff = int(cur.get("seq", 0)), int(cur.get("off", 0))
            except (ValueError, TypeError):
                flag("journal", cursor_file, "unparseable cursor")
                continue
            max_seq = max(seg_valid)
            bad = None
            if cseq > max_seq:
                bad = f"cursor seq {cseq} past journal tail seq {max_seq}"
                cseq, coff = max_seq, seg_valid[max_seq]
            elif cseq in seg_valid and coff > seg_valid[cseq]:
                bad = (f"cursor offset {coff} past valid bytes "
                       f"{seg_valid[cseq]} of segment {cseq}")
                coff = seg_valid[cseq]
            if bad:
                repaired = False
                if repair:
                    cur["seq"], cur["off"] = cseq, coff
                    _atomic_json(cursor_file, cur)
                    repaired = True
                flag("journal", cursor_file, bad, repaired)

    # -- router epoch invariant
    rdir = home / "run" / "fleet-router"
    if rdir.is_dir():
        checked["routerEpoch"] = True
        floor = 0
        dj = rdir / "delta-journal"
        if dj.is_dir():
            for payload in iter_journal_records(dj):
                if len(payload) >= 8:
                    floor = max(floor,
                                int.from_bytes(payload[:8], "little"))
        marker = rdir / "epoch.json"
        epoch = 0
        doc: dict = {}
        if marker.is_file():
            try:
                doc = json.loads(marker.read_text())
                epoch = int(doc.get("epoch", 0))
            except (ValueError, TypeError):
                doc, epoch = {}, 0
        if floor > epoch:
            repaired = False
            if repair:
                doc["epoch"] = floor
                _atomic_json(marker, doc)
                repaired = True
            flag("router_epoch", marker,
                 f"marker epoch {epoch} behind delta-journal floor {floor}",
                 repaired)

    orphans = _find_orphan_blobs(home)
    _FSCK_ORPHAN_BLOBS.set(len(orphans))

    verdict = "clean" if not violations else f"{len(violations)} violation(s)"
    _FSCK_RUNS.labels(verdict="clean" if not violations else "violations").inc()
    repaired_n = sum(1 for v in violations if v["repaired"])
    report = {"verdict": verdict, "violations": violations,
              "repaired": repaired_n, "orphanBlobs": orphans,
              "checked": checked}
    try:
        (home / "run").mkdir(parents=True, exist_ok=True)
        _atomic_json(home / "run" / FSCK_STATE,
                     {"at": _utcnow_iso(), "verdict": verdict,
                      "violations": len(violations), "repaired": repaired_n,
                      "orphanBlobs": len(orphans)})
    except OSError:
        pass  # status surface only; the audit itself already ran
    return report


def gc_blobs(home: str | os.PathLike | None = None, *,
             dry_run: bool = False) -> dict:
    """Delete model blobs (and their sidecars) referenced by no
    non-retired EngineInstance.  ``dry_run`` only reports."""
    from .registry import Storage
    home = Path(home) if home is not None else Path(Storage.home())
    orphans = _find_orphan_blobs(home)
    deleted = 0
    if not dry_run:
        models = home / "models"
        for name in orphans:
            for p in (models / name, models / f"{name}.sha256"):
                try:
                    p.unlink()
                except FileNotFoundError:
                    pass
            deleted += 1
        if deleted:
            _FSCK_GC_DELETED.inc(deleted)
        _FSCK_ORPHAN_BLOBS.set(0)
    else:
        _FSCK_ORPHAN_BLOBS.set(len(orphans))
    return {"orphans": orphans, "deleted": deleted, "dryRun": dry_run}


# --------------------------------------------------------------------------
# status surface + bench

def status_lines(home: str | os.PathLike | None = None,
                 backup_dir: str | os.PathLike | None = None) -> list[str]:
    """Human lines for `pio status`: last-backup age, last-fsck verdict,
    orphan-blob count."""
    from .registry import Storage
    home = Path(home) if home is not None else Path(Storage.home())
    root = Path(backup_dir) if backup_dir is not None else home / "backups"
    lines: list[str] = []
    complete, partial = list_backups(root)
    if complete:
        seq, _p, manifest = complete[-1]
        age = ""
        try:
            created = datetime.fromisoformat(manifest["createdAt"])
            secs = max(0, int((datetime.now(timezone.utc) - created)
                              .total_seconds()))
            age = f", age {secs}s"
        except (KeyError, ValueError):
            pass
        extra = f" ({len(partial)} incomplete ignored)" if partial else ""
        lines.append(f"last backup: #{seq}{age}, "
                     f"{len(complete)} complete{extra}")
    else:
        lines.append("last backup: none (run `pio backup`)")
    state = home / "run" / FSCK_STATE
    if state.is_file():
        try:
            doc = json.loads(state.read_text())
            lines.append(f"last fsck: {doc.get('verdict', '?')} "
                         f"at {doc.get('at', '?')}, "
                         f"{doc.get('orphanBlobs', 0)} orphan blob(s)")
        except (ValueError, OSError):
            lines.append("last fsck: state unreadable")
    else:
        lines.append("last fsck: never (run `pio admin fsck`)")
    return lines


def run_backup_bench(*, files: int = 64, size_kb: int = 256,
                     rounds: int = 2) -> dict:
    """Synthetic backup throughput: a temp home of ``files`` blobs of
    ``size_kb`` each, one full backup then ``rounds-1`` incrementals
    (all-unchanged → pure dedup).  Prints MB/s and dedup counts."""
    import tempfile

    results = []
    with tempfile.TemporaryDirectory(prefix="pio-bench-backup-") as td:
        home = Path(td) / "home"
        (home / "models").mkdir(parents=True)
        blob = os.urandom(size_kb * 1024)
        for i in range(files):
            (home / "models" / f"bench-{i:04d}").write_bytes(blob[:-(i % 7 + 1)])
        root = Path(td) / "backups"
        for r in range(max(1, rounds)):
            t0 = time.monotonic()
            rep = create_backup(home, backup_dir=root, keep=rounds + 1)
            dt = time.monotonic() - t0
            mb = rep["bytes"] / 1e6
            results.append({"round": r, "seconds": round(dt, 4),
                            "mbWritten": round(mb, 3),
                            "mbPerS": round(mb / dt, 2) if dt > 0 else 0.0,
                            "dedupedFiles": rep["dedupedFiles"]})
    return {"files": files, "sizeKb": size_kb, "rounds": results}
