"""Mid-training checkpoint/resume — beyond the reference's capabilities.

The reference checkpoints at model granularity only: a finished model list
is Kryo-serialized into MODELDATA (reference: core/src/main/scala/io/
prediction/workflow/CoreWorkflow.scala:69-74); an interrupted training
restarts from scratch. SURVEY.md §5 assigns the TPU build step-level
checkpointing: orbax snapshots of the in-progress training state (e.g. the
ALS item-factor matrix + iteration counter) so `pio train` resumed with the
same --checkpoint-dir continues from the latest saved step.

Orbax is the primary backend (async-capable, understands sharded
jax.Arrays); a plain ``.npz`` fallback keeps the feature alive where orbax
is unavailable. Step directories are ``step_<n>``; retention keeps the
newest ``keep`` steps.

Multi-host (elastic) training checkpoints are ``ShardedTrainCheckpointer``:
each process writes only its contiguous row slice of every factor matrix
(``shard_<p>_of_<n>.npz`` + a ``.sha256`` sidecar, the PR-4 integrity
story per shard), all processes rendezvous at a cross-host barrier (a
shared-filesystem ``FileBarrier`` with a timeout, so a dead peer surfaces
as a classified-transient ``BarrierTimeoutError`` instead of a hang), and
process 0 commits ``manifest.json`` atomically. A step exists only once
its manifest does — a torn or missing shard invalidates the step and
resume falls back to the previous complete one. Restore reassembles the
GLOBAL factor matrices from any N-shard manifest, so a relaunch at a
different process count (N→M) just re-slices via ``reshard_state`` /
the model's own layout — elastic topology the way ALX (arXiv:2112.02194)
and Google's ads training infra (arXiv:2501.10546) treat it: preemption
and resharding are the normal case, not failures.
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import time
from pathlib import Path
from typing import Any

import numpy as np

from ..faults import FAULTS
from ..obs.metrics import METRICS

log = logging.getLogger("predictionio_tpu.workflow")

_M_CKPT_SAVE = METRICS.histogram(
    "pio_checkpoint_save_seconds",
    "full durable checkpoint save (backend write + fsync tree + swap)")
_M_SHARD_WRITE = METRICS.histogram(
    "pio_ckpt_shard_write_seconds",
    "one process's factor-shard write (serialize + sha256 + fsync + rename)")
_M_SHARD_BYTES = METRICS.counter(
    "pio_ckpt_shard_bytes_total",
    "bytes of factor-shard data written by this process")
_M_SHARD_VERIFY_FAIL = METRICS.counter(
    "pio_ckpt_shard_verify_failures_total",
    "shards rejected at restore (sha256 mismatch / missing file) — the "
    "step falls back to the previous complete manifest")
_M_MANIFEST_COMMIT = METRICS.histogram(
    "pio_ckpt_manifest_commit_seconds",
    "process-0 manifest commit (shard inventory + atomic rename)")
_M_PARTIAL_DISCARDED = METRICS.counter(
    "pio_ckpt_partial_steps_discarded_total",
    "partial (manifest-less / torn) step directories discarded at resume")
_M_LAST_COMPLETE = METRICS.gauge(
    "pio_ckpt_last_complete_step",
    "newest manifest-complete sharded checkpoint step in the directory")

__all__ = ["TrainCheckpointer", "ShardedTrainCheckpointer", "FileBarrier",
           "ShardIntegrityError", "reshard_state"]

_STEP_RE = re.compile(r"^step_(\d+)$")
_SHARD_RE = re.compile(r"^shard_(\d+)_of_(\d+)\.npz$")


def _fsync_file(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: Path) -> None:
    """Durably record directory entries (new files / renames). Some
    filesystems refuse O_RDONLY fsync on directories — a durability
    best-effort there, same as most databases handle it."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _fsync_tree(root: Path) -> None:
    """fsync every regular file under ``root``, then its directories
    bottom-up, so the whole step's contents are on stable storage before
    the ``_COMPLETE`` marker claims they are."""
    for dirpath, _dirnames, filenames in os.walk(root, topdown=False):
        d = Path(dirpath)
        for fn in filenames:
            _fsync_file(d / fn)
        _fsync_dir(d)


def _to_host(tree: Any) -> Any:
    """jax arrays -> numpy so checkpoints are device-independent."""
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


class _OrbaxBackend:
    def __init__(self):
        import orbax.checkpoint as ocp

        # orbax narrates every save at absl INFO — far too chatty for a
        # CLI that checkpoints every few iterations. Only quiet the absl
        # logger if the application hasn't configured it itself.
        absl_logger = logging.getLogger("absl")
        if absl_logger.level == logging.NOTSET:
            absl_logger.setLevel(logging.WARNING)
        self._ckptr = ocp.PyTreeCheckpointer()

    def save(self, path: Path, state: Any) -> None:
        self._ckptr.save(str(path.resolve()), _to_host(state))

    def restore(self, path: Path) -> Any:
        return self._ckptr.restore(str(path.resolve()))


class _NpzBackend:
    """Flat-pytree .npz fallback (dict-of-arrays/scalars only)."""

    def save(self, path: Path, state: Any) -> None:
        path.mkdir(parents=True, exist_ok=True)
        arrays = {k: np.asarray(v) for k, v in _to_host(state).items()}
        np.savez(path / "state.npz", **arrays)

    def restore(self, path: Path) -> Any:
        with np.load(path / "state.npz", allow_pickle=False) as z:
            return {k: z[k] for k in z.files}


class TrainCheckpointer:
    """Save/restore a training-state pytree per step under ``directory``."""

    def __init__(self, directory: str | Path, *, keep: int = 2,
                 backend: str = "auto"):
        self.directory = Path(directory)
        self.keep = max(1, keep)
        if backend == "npz":
            self._backend: Any = _NpzBackend()
        else:
            try:
                self._backend = _OrbaxBackend()
            except Exception as e:  # orbax missing/incompatible
                if backend == "orbax":
                    raise
                log.warning("orbax unavailable (%s); npz checkpoint fallback", e)
                self._backend = _NpzBackend()

    # -- steps -------------------------------------------------------------
    def _recover(self) -> None:
        """Repair interrupted overwrites. save() swaps via ``step_N.tmp``
        and ``step_N.old`` siblings; a crash can leave any combination of
        them. Rules: a COMPLETE ``.tmp`` is a finished newer save — promote
        it over ``step_N``; an incomplete ``.tmp`` is garbage; ``.old`` is
        the displaced previous checkpoint — restore it only if ``step_N``
        vanished mid-swap, else delete."""
        if not self.directory.is_dir():
            return
        for tmp in self.directory.glob("step_*.tmp"):
            name = tmp.name[: -len(".tmp")]
            if not _STEP_RE.match(name):
                continue
            final = self.directory / name
            if (tmp / "_COMPLETE").exists():
                if final.exists():
                    shutil.rmtree(final)
                tmp.rename(final)
                log.warning("recovered interrupted checkpoint save: %s", name)
            else:
                shutil.rmtree(tmp, ignore_errors=True)
        for old in self.directory.glob("step_*.old"):
            name = old.name[: -len(".old")]
            if not _STEP_RE.match(name):
                continue
            final = self.directory / name
            if not final.exists() and (old / "_COMPLETE").exists():
                old.rename(final)
                log.warning("restored displaced checkpoint: %s", name)
            else:
                shutil.rmtree(old, ignore_errors=True)

    def steps(self) -> list[int]:
        self._recover()
        if not self.directory.is_dir():
            return []
        out = []
        for child in self.directory.iterdir():
            m = _STEP_RE.match(child.name)
            if m and (child / "_COMPLETE").exists():
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def _step_dir(self, step: int) -> Path:
        return self.directory / f"step_{step}"

    # -- save / restore ----------------------------------------------------
    def save(self, step: int, state: Any) -> None:
        """Write atomically AND durably: the step counts only once
        _COMPLETE lands, and _COMPLETE lands only after the step's
        contents are fsynced (file data, then the marker, then the parent
        directory after the rename) — a power cut can surface a missing
        checkpoint, never a "complete" one with torn contents.

        Overwrites are atomic too — the new state is written to a ``.tmp``
        sibling and swapped in, so a crash mid-overwrite never loses the
        previously complete checkpoint of the same step.
        """
        t0 = time.perf_counter()
        try:
            self._save(step, state)
        finally:
            _M_CKPT_SAVE.record(time.perf_counter() - t0)

    def _save(self, step: int, state: Any) -> None:
        self.directory.mkdir(parents=True, exist_ok=True)
        self._recover()  # settle any interrupted swap before starting ours
        path = self._step_dir(step)
        tmp = self.directory / f"step_{step}.tmp"
        if tmp.exists():  # leftover from a crashed save
            shutil.rmtree(tmp)
        self._backend.save(tmp, state)
        _fsync_tree(tmp)  # contents durable BEFORE the marker exists
        marker = tmp / "_COMPLETE"
        marker.write_text(json.dumps({"step": step}))
        _fsync_file(marker)
        _fsync_dir(tmp)
        if path.exists():
            old = self.directory / f"step_{step}.old"
            if old.exists():
                shutil.rmtree(old)
            path.rename(old)
            tmp.rename(path)
            _fsync_dir(self.directory)  # both renames durable together
            shutil.rmtree(old, ignore_errors=True)
        else:
            tmp.rename(path)
            _fsync_dir(self.directory)
        log.info("checkpoint saved: step %d -> %s", step, path)
        # Retention prunes only steps <= the one just saved: steps beyond it
        # can exist legitimately (same run previously trained to a higher
        # iteration target) and must not shadow-delete the fresh save.
        eligible = [s for s in self.steps() if s <= step]
        for old_step in eligible[: -self.keep]:
            shutil.rmtree(self._step_dir(old_step), ignore_errors=True)

    def restore(self, step: int | None = None) -> tuple[int, Any] | None:
        """(step, state) for ``step`` or the latest; None when empty."""
        self._recover()  # an explicit step may need an interrupted-swap repair
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        return step, self._backend.restore(self._step_dir(step))

    def restore_first_valid(self, is_valid) -> tuple[int, Any] | None:
        """Walk steps newest-first and return the first whose state passes
        ``is_valid(state)`` — a stale higher-numbered step from an older
        run must not shadow resumable ones."""
        for step in reversed(self.steps()):
            try:
                state = self._backend.restore(self._step_dir(step))
                ok = bool(is_valid(state))
            except Exception as e:
                # unreadable step OR a foreign state shape the validator
                # chokes on — either way, skip it, don't abort the walk
                log.warning("checkpoint step %d unusable (%s); skipping", step, e)
                continue
            if ok:
                return step, state
            log.info("checkpoint step %d is from a different run; skipping", step)
        return None

    def clear(self) -> None:
        """Drop every step (a fresh run starting over must not leave stale
        steps that retention would preserve over its own)."""
        for step in self.steps():
            shutil.rmtree(self._step_dir(step), ignore_errors=True)


# ---------------------------------------------------------------------------
# sharded (multi-host, elastic) checkpoints
# ---------------------------------------------------------------------------

class ShardIntegrityError(RuntimeError):
    """A shard listed by a manifest is missing or fails its sha256 — the
    step is invalid and resume must fall back to an earlier one."""


def _sha256_file(path: Path) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


class FileBarrier:
    """Cross-host rendezvous over the shared checkpoint filesystem.

    ``jax.multihost_utils.sync_global_devices`` needs a live collective
    runtime (and hangs forever on a dead peer); checkpoint commits only
    need the N writers to agree that all shards are durable, and they
    already share a filesystem — the same one the manifest protocol
    requires. Each process touches ``.barrier/<tag>/proc_<pid>`` and
    waits until all ``num_processes`` marks exist; past ``timeout_s`` it
    raises ``BarrierTimeoutError`` (classified transient), which is how
    a dead worker aborts the step cleanly on the survivors.
    """

    def __init__(self, root: str | Path, num_processes: int, process_id: int,
                 *, timeout_s: float = 120.0, poll_s: float = 0.05):
        self.root = Path(root)
        self.num_processes = int(num_processes)
        self.process_id = int(process_id)
        self.timeout_s = timeout_s
        self.poll_s = poll_s

    def __call__(self, tag: str) -> None:
        from .supervisor import BarrierTimeoutError

        d = self.root / ".barrier" / tag.replace("/", "_")
        d.mkdir(parents=True, exist_ok=True)
        (d / f"proc_{self.process_id}").write_text(
            json.dumps({"pid": self.process_id, "t": time.time()}))
        _fsync_dir(d)
        deadline = time.monotonic() + self.timeout_s
        want = {f"proc_{i}" for i in range(self.num_processes)}
        while True:
            try:
                present = {p.name for p in d.iterdir()} & want
            except OSError:
                present = set()
            if len(present) >= self.num_processes:
                return
            if time.monotonic() >= deadline:
                raise BarrierTimeoutError(
                    f"barrier timeout at {tag!r}: waited {self.timeout_s:.0f}s "
                    f"for {sorted(want - present)} — peer dead or wedged; "
                    "aborting step (relaunch resumes from the last complete "
                    "manifest)")
            time.sleep(self.poll_s)


def reshard_state(state: dict, *, process_id: int, num_processes: int) -> dict:
    """Re-slice a reassembled GLOBAL training state for one process of an
    M-process mesh — the second half of an N→M resume. Row-sharded values
    (ndim >= 2, the factor matrices) take their ``host_row_range`` slice;
    scalars pass through. Pure numpy, so N→M→reassemble is bit-exact."""
    from ..parallel.mesh import host_row_range

    out = {}
    for k, v in state.items():
        arr = np.asarray(v)
        if arr.ndim >= 2:
            lo, hi = host_row_range(arr.shape[0], process_id, num_processes)
            out[k] = arr[lo:hi]
        else:
            out[k] = arr
    return out


class ShardedTrainCheckpointer:
    """Elastic multi-host checkpoints: per-process factor shards + an
    atomically committed manifest. Interface-compatible with
    ``TrainCheckpointer`` (``steps``/``restore_first_valid``/``save``/
    ``clear``), so ``train_als`` takes either.

    Save protocol for step ``n`` across ``num_processes`` writers:

    1. every process writes ``shard_<p>_of_<n>.npz`` — its contiguous
       ``host_row_range`` row slice of each matrix-valued key, plus every
       scalar — via tmp + fsync + atomic rename, with a ``.sha256``
       sidecar (``checkpoint.shard_write`` chaos site fires first);
    2. all processes rendezvous at the cross-host barrier
       (``train.host_lost`` chaos site; a dead peer becomes a
       ``BarrierTimeoutError``, classified transient);
    3. process 0 inventories the shards and commits ``manifest.json``
       via tmp + fsync + atomic rename (``checkpoint.manifest_commit``
       chaos site fires in the torn-manifest window), then prunes
       retention — only manifest-complete steps count toward ``keep``;
    4. a second barrier keeps non-zero processes from racing past an
       uncommitted step.

    A step EXISTS only if its manifest parses and every listed shard is
    present; restore additionally verifies each shard's sha256 and
    reassembles the global matrices, so a resume works from any N-shard
    manifest at any current process count (``reshard_state`` /
    the model layout re-slices). Partial (manifest-less or torn) step
    directories are discarded — and recorded in ``discarded.json`` for
    ``pio status`` — by process 0 at resume time.
    """

    def __init__(self, directory: str | Path, *, keep: int = 2,
                 process_id: int = 0, num_processes: int = 1,
                 barrier=None, barrier_timeout_s: float = 120.0):
        if not (0 <= process_id < num_processes):
            raise ValueError(
                f"process {process_id}/{num_processes} invalid")
        self.directory = Path(directory)
        self.keep = max(1, keep)
        self.process_id = int(process_id)
        self.num_processes = int(num_processes)
        if barrier is None and num_processes > 1:
            barrier = FileBarrier(self.directory, num_processes, process_id,
                                  timeout_s=barrier_timeout_s)
        self._barrier_fn = barrier

    # -- layout ------------------------------------------------------------
    def _step_dir(self, step: int) -> Path:
        return self.directory / f"step_{step}"

    @staticmethod
    def _shard_name(p: int, n: int) -> str:
        return f"shard_{p:05d}_of_{n:05d}.npz"

    def _manifest(self, step_dir: Path) -> dict | None:
        """Parsed manifest when the step is COMPLETE (manifest readable +
        every listed shard present); None otherwise."""
        try:
            man = json.loads((step_dir / "manifest.json").read_text())
            shards = man["shards"]
        except (OSError, ValueError, KeyError, TypeError):
            return None
        for sh in shards:
            if not (step_dir / sh["file"]).is_file():
                return None
        return man

    def _scan(self) -> tuple[list[int], list[int]]:
        """(complete, partial) step numbers, each sorted ascending."""
        complete, partial = [], []
        if not self.directory.is_dir():
            return complete, partial
        for child in self.directory.iterdir():
            m = _STEP_RE.match(child.name)
            if not m or not child.is_dir():
                continue
            (complete if self._manifest(child) is not None
             else partial).append(int(m.group(1)))
        return sorted(complete), sorted(partial)

    def steps(self) -> list[int]:
        return self._scan()[0]

    def partial_steps(self) -> list[int]:
        return self._scan()[1]

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def discarded(self) -> list[dict]:
        """History of partial steps discarded at resume (``pio status``
        reports these so an operator sees what a crash cost)."""
        try:
            return json.loads(
                (self.directory / "discarded.json").read_text())["discarded"]
        except (OSError, ValueError, KeyError, TypeError):
            return []

    # -- barrier -----------------------------------------------------------
    def _sync(self, tag: str) -> None:
        from .supervisor import TransientTrainingError, BarrierTimeoutError

        # chaos site: the sync point where a dead peer surfaces — arming
        # an error here IS losing a host mid-checkpoint
        FAULTS.fire("train.host_lost")
        if self._barrier_fn is None:
            return
        try:
            self._barrier_fn(tag)
        except TransientTrainingError:
            raise  # already classified (BarrierTimeoutError et al.)
        except Exception as e:
            raise BarrierTimeoutError(
                f"checkpoint barrier {tag!r} failed: {e}") from e

    # -- save --------------------------------------------------------------
    def save(self, step: int, state: dict) -> None:
        """Write this process's shard of ``state`` and (on process 0)
        commit the manifest once every shard is durable. ``state`` is the
        full global training state on every process — matrix-valued keys
        (ndim >= 2) are row-sharded by ``host_row_range``, scalars are
        replicated into every shard and read back from shard 0."""
        step_dir = self._step_dir(step)
        step_dir.mkdir(parents=True, exist_ok=True)
        arrays = {k: np.asarray(v) for k, v in state.items()}

        FAULTS.fire("checkpoint.shard_write")
        t0 = time.perf_counter()
        from ..parallel.mesh import host_row_range

        local = {}
        for k, arr in arrays.items():
            if arr.ndim >= 2:
                lo, hi = host_row_range(
                    arr.shape[0], self.process_id, self.num_processes)
                local[k] = arr[lo:hi]
            else:
                local[k] = arr
        name = self._shard_name(self.process_id, self.num_processes)
        tmp = step_dir / (name + ".tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **local)
        _fsync_file(tmp)
        digest = _sha256_file(tmp)
        n_bytes = tmp.stat().st_size
        sidecar = step_dir / (name + ".sha256")
        sidecar.write_text(digest)
        _fsync_file(sidecar)
        tmp.rename(step_dir / name)
        _fsync_dir(step_dir)
        _M_SHARD_WRITE.record(time.perf_counter() - t0)
        _M_SHARD_BYTES.inc(n_bytes)
        log.info("checkpoint shard saved: step %d shard %d/%d (%d bytes)",
                 step, self.process_id, self.num_processes, n_bytes)

        self._sync(f"step{step}.shards.n{self.num_processes}")
        if self.process_id == 0:
            self._commit_manifest(step, step_dir, arrays)
        self._sync(f"step{step}.manifest.n{self.num_processes}")

    def _commit_manifest(self, step: int, step_dir: Path,
                         arrays: dict) -> None:
        from ..parallel.mesh import host_row_range
        from .supervisor import HostLostError

        t0 = time.perf_counter()
        shards = []
        for p in range(self.num_processes):
            name = self._shard_name(p, self.num_processes)
            sidecar = step_dir / (name + ".sha256")
            if not (step_dir / name).is_file() or not sidecar.is_file():
                # barrier passed yet a shard is gone — a peer died after
                # rendezvous or storage lost the write; the step is void
                raise HostLostError(
                    f"host lost: shard {name} missing at manifest commit "
                    f"for step {step}")
            rows = {k: host_row_range(arr.shape[0], p, self.num_processes)
                    for k, arr in arrays.items() if arr.ndim >= 2}
            shards.append({"file": name, "sha256": sidecar.read_text().strip(),
                           "rows": {k: [lo, hi] for k, (lo, hi) in rows.items()}})
        manifest = {
            "format": 1,
            "step": step,
            "num_processes": self.num_processes,
            "keys": {k: {"shape": list(arr.shape), "dtype": str(arr.dtype),
                         "sharded": arr.ndim >= 2}
                     for k, arr in arrays.items()},
            "shards": shards,
        }
        # chaos site: the torn-manifest window — every shard durable, the
        # step one rename away from existing; a kill here must leave a
        # partial step that is never loaded
        FAULTS.fire("checkpoint.manifest_commit")
        tmp = step_dir / "manifest.json.tmp"
        tmp.write_text(json.dumps(manifest, indent=1))
        _fsync_file(tmp)
        tmp.rename(step_dir / "manifest.json")
        _fsync_dir(step_dir)
        _M_MANIFEST_COMMIT.record(time.perf_counter() - t0)
        _M_LAST_COMPLETE.set(step)
        log.info("checkpoint manifest committed: step %d (%d shard(s))",
                 step, self.num_processes)
        # retention: only manifest-COMPLETE steps count toward keep, and
        # only they are pruned — a newer partial directory must never
        # push the newest complete step out of the window
        eligible = [s for s in self.steps() if s <= step]
        for old_step in eligible[: -self.keep]:
            shutil.rmtree(self._step_dir(old_step), ignore_errors=True)
            self._drop_barrier_dirs(old_step)

    def _drop_barrier_dirs(self, step: int) -> None:
        root = self.directory / ".barrier"
        if not root.is_dir():
            return
        for d in root.glob(f"step{step}.*"):
            shutil.rmtree(d, ignore_errors=True)

    # -- restore -----------------------------------------------------------
    def _load_step(self, step: int) -> dict:
        """Verify every shard's sha256 and reassemble the GLOBAL state."""
        step_dir = self._step_dir(step)
        man = self._manifest(step_dir)
        if man is None:
            raise ShardIntegrityError(
                f"step {step} has no complete manifest")
        out: dict = {}
        sharded_keys = {k for k, meta in man["keys"].items() if meta["sharded"]}
        for k in sharded_keys:
            meta = man["keys"][k]
            out[k] = np.empty(tuple(meta["shape"]),
                              dtype=np.dtype(meta["dtype"]))
        for i, sh in enumerate(man["shards"]):
            path = step_dir / sh["file"]
            try:
                actual = _sha256_file(path)
            except OSError as e:
                _M_SHARD_VERIFY_FAIL.inc()
                raise ShardIntegrityError(
                    f"step {step} shard {sh['file']} unreadable: {e}") from e
            if actual != sh["sha256"]:
                _M_SHARD_VERIFY_FAIL.inc()
                raise ShardIntegrityError(
                    f"step {step} shard {sh['file']} corrupt: sha256 "
                    f"{actual} != manifest {sh['sha256']}")
            with np.load(path, allow_pickle=False) as z:
                for k in z.files:
                    if k in sharded_keys:
                        lo, hi = sh["rows"][k]
                        out[k][lo:hi] = z[k]
                    elif i == 0:  # scalars: every shard has them; take p0's
                        out[k] = z[k]
        return out

    def restore(self, step: int | None = None) -> tuple[int, dict] | None:
        """(step, GLOBAL state) for ``step`` or the newest complete one;
        None when no complete step exists. The caller re-slices for its
        own mesh (``reshard_state`` or the model layout) — that is the
        whole N→M story."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        return step, self._load_step(step)

    def restore_first_valid(self, is_valid) -> tuple[int, dict] | None:
        """Walk complete steps newest-first, returning the first whose
        reassembled state passes ``is_valid``. Resume entry point: also
        discards partial (torn) step directories so a crashed save can
        never shadow a complete one, recording what was dropped."""
        self.discard_partial_steps()
        for step in reversed(self.steps()):
            try:
                state = self._load_step(step)
                ok = bool(is_valid(state))
            except Exception as e:
                log.warning("sharded checkpoint step %d unusable (%s); "
                            "skipping", step, e)
                continue
            if ok:
                return step, state
            log.info("sharded checkpoint step %d is from a different run; "
                     "skipping", step)
        return None

    def discard_partial_steps(self) -> list[int]:
        """Process 0 only (single writer of directory-level truth): delete
        manifest-less/torn step directories and append them to
        ``discarded.json``. Returns the discarded step numbers."""
        if self.process_id != 0:
            return []
        partial = self.partial_steps()
        if not partial:
            return []
        for step in partial:
            shutil.rmtree(self._step_dir(step), ignore_errors=True)
            self._drop_barrier_dirs(step)
            _M_PARTIAL_DISCARDED.inc()
        history = self.discarded()
        now = time.strftime("%Y-%m-%dT%H:%M:%S%z")
        history.extend(
            {"step": s, "reason": "no complete manifest", "ts": now}
            for s in partial)
        tmp = self.directory / "discarded.json.tmp"
        tmp.write_text(json.dumps({"discarded": history}, indent=1))
        tmp.rename(self.directory / "discarded.json")
        log.warning("discarded %d partial checkpoint step(s): %s — resuming "
                    "from the previous complete manifest", len(partial), partial)
        return partial

    def shard_status(self) -> dict:
        """Directory truth for ``pio status``: complete/partial steps,
        discard history, and each host's newest on-disk shard."""
        complete, partial = self._scan()
        hosts: dict[int, int] = {}
        for step in sorted(set(complete) | set(partial)):
            d = self._step_dir(step)
            try:
                names = [p.name for p in d.iterdir()]
            except OSError:
                continue
            for name in names:
                m = _SHARD_RE.match(name)
                if m:
                    hosts[int(m.group(1))] = step
        return {"complete": complete, "partial": partial,
                "latest_complete": complete[-1] if complete else None,
                "discarded": self.discarded(), "hosts": hosts}

    def clear(self) -> None:
        """Drop every step (complete AND partial) plus barrier litter —
        a fresh run starting over must leave no stale state behind."""
        complete, partial = self._scan()
        for step in complete + partial:
            shutil.rmtree(self._step_dir(step), ignore_errors=True)
            self._drop_barrier_dirs(step)
