"""Mid-training checkpoint/resume (workflow/checkpoint.py + ALS wiring).

The reference restarts interrupted trainings from scratch (its only
persistence is the finished model, CoreWorkflow.scala:69-74); the TPU
build adds step-level resume per SURVEY.md §5. These tests cover the
checkpointer itself (atomicity, retention, backends) and that a resumed
ALS run reproduces the uninterrupted run.
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

from predictionio_tpu.models.als import ALSConfig, train_als
from predictionio_tpu.storage.bimap import BiMap
from predictionio_tpu.storage.frame import Ratings
from predictionio_tpu.workflow.checkpoint import (
    ShardedTrainCheckpointer,
    ShardIntegrityError,
    TrainCheckpointer,
    reshard_state,
)


@pytest.fixture(params=["auto", "npz"])
def ckptr_factory(request, tmp_path):
    def make(subdir="ck"):
        return TrainCheckpointer(tmp_path / subdir, backend=request.param)
    return make


class TestTrainCheckpointer:
    def test_roundtrip(self, ckptr_factory):
        ck = ckptr_factory()
        state = {"v": np.arange(12, dtype=np.float32).reshape(3, 4),
                 "it": np.int64(3)}
        ck.save(3, state)
        got_step, got = ck.restore()
        assert got_step == 3
        np.testing.assert_array_equal(got["v"], state["v"])
        assert int(got["it"]) == 3

    def test_latest_and_retention(self, ckptr_factory):
        ck = ckptr_factory()
        for s in (1, 2, 3, 4):
            ck.save(s, {"v": np.full((2, 2), float(s)), "it": np.int64(s)})
        assert ck.latest_step() == 4
        assert ck.steps() == [3, 4]  # keep=2 default
        step, st = ck.restore()
        assert step == 4 and float(st["v"][0, 0]) == 4.0

    def test_incomplete_step_ignored(self, ckptr_factory, tmp_path):
        ck = ckptr_factory()
        ck.save(1, {"v": np.zeros((2, 2)), "it": np.int64(1)})
        # simulate a crash mid-save: step dir exists, no _COMPLETE marker
        (ck.directory / "step_2").mkdir()
        assert ck.latest_step() == 1

    def test_empty(self, ckptr_factory):
        assert ckptr_factory().restore() is None


def _ratings(nu=40, ni=30, n=600, seed=0):
    rng = np.random.default_rng(seed)
    return Ratings(
        user_indices=rng.integers(0, nu, n).astype(np.int64),
        item_indices=rng.integers(0, ni, n).astype(np.int64),
        ratings=(rng.random(n).astype(np.float32) * 4 + 1),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
    )


class TestALSResume:
    def test_resume_matches_uninterrupted(self, tmp_path):
        r = _ratings()
        cfg10 = ALSConfig(rank=8, iterations=10, lambda_=0.1, seed=5)
        baseline = train_als(r, cfg10)

        ck = TrainCheckpointer(tmp_path / "als")
        # "crash" after 4 of 10 iterations
        cfg4 = ALSConfig(rank=8, iterations=4, lambda_=0.1, seed=5)
        train_als(r, cfg4, checkpointer=ck, checkpoint_every=2)
        assert ck.latest_step() == 4

        resumed = train_als(r, cfg10, checkpointer=ck, checkpoint_every=2)
        np.testing.assert_allclose(
            resumed.item_factors, baseline.item_factors, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            resumed.user_factors, baseline.user_factors, rtol=1e-5, atol=1e-5)

    def test_resume_at_final_iteration(self, tmp_path):
        r = _ratings()
        ck = TrainCheckpointer(tmp_path / "als")
        cfg = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        m1 = train_als(r, cfg, checkpointer=ck, checkpoint_every=1)
        # rerun with identical iteration count: loop body never executes,
        # u must still be solved from the restored v
        m2 = train_als(r, cfg, checkpointer=ck, checkpoint_every=1)
        np.testing.assert_allclose(m2.user_factors, m1.user_factors,
                                   rtol=1e-5, atol=1e-5)

    def test_shape_mismatch_starts_fresh(self, tmp_path):
        r = _ratings()
        ck = TrainCheckpointer(tmp_path / "als")
        ck.save(2, {"u": np.zeros((5, 3), np.float32),
                    "v": np.zeros((7, 3), np.float32), "it": np.int64(2)})
        cfg = ALSConfig(rank=8, iterations=2, lambda_=0.1, seed=5)
        m = train_als(r, cfg, checkpointer=ck, checkpoint_every=1)
        assert m.item_factors.shape == (30, 8)

    def test_config_change_invalidates_checkpoint(self, tmp_path):
        r = _ratings()
        ck = TrainCheckpointer(tmp_path / "als")
        cfg_a = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        train_als(r, cfg_a, checkpointer=ck, checkpoint_every=1)
        # different lambda: the old run's factors must not be resumed
        cfg_b = ALSConfig(rank=8, iterations=3, lambda_=0.5, seed=5)
        m_b = train_als(r, cfg_b, checkpointer=ck, checkpoint_every=1)
        m_b_fresh = train_als(r, cfg_b)
        np.testing.assert_allclose(m_b.item_factors, m_b_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)

    def test_data_change_invalidates_checkpoint(self, tmp_path):
        ck = TrainCheckpointer(tmp_path / "als")
        cfg = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        train_als(_ratings(seed=0), cfg, checkpointer=ck, checkpoint_every=1)
        r2 = _ratings(seed=9)  # new events arrived
        m = train_als(r2, cfg, checkpointer=ck, checkpoint_every=1)
        m_fresh = train_als(r2, cfg)
        np.testing.assert_allclose(m.item_factors, m_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)

    def test_stale_high_step_does_not_shadow(self, tmp_path):
        # a leftover step_10 from an older (different-data) run must not
        # permanently disable resume: it is skipped, purged, and the new
        # run's own lower-numbered steps take over
        ck = TrainCheckpointer(tmp_path / "als")
        cfg = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        ck.save(10, {"u": np.zeros((40, 8), np.float32),
                     "v": np.zeros((30, 8), np.float32),
                     "it": np.int64(10), "fp": np.uint64(12345)})
        r = _ratings(seed=1)
        m = train_als(r, cfg, checkpointer=ck, checkpoint_every=1)
        m_fresh = train_als(r, cfg)
        np.testing.assert_allclose(m.item_factors, m_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)
        assert 10 not in ck.steps() and ck.latest_step() == 3
        # and a subsequent resume works again
        cfg6 = ALSConfig(rank=8, iterations=6, lambda_=0.1, seed=5)
        m6 = train_als(r, cfg6, checkpointer=ck, checkpoint_every=1)
        m6_fresh = train_als(r, cfg6)
        np.testing.assert_allclose(m6.item_factors, m6_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)

    def test_extend_iterations_resumes(self, tmp_path):
        r = _ratings()
        ck = TrainCheckpointer(tmp_path / "als")
        cfg3 = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        train_als(r, cfg3, checkpointer=ck, checkpoint_every=1)
        # raising the iteration target continues from step 3
        cfg6 = ALSConfig(rank=8, iterations=6, lambda_=0.1, seed=5)
        m = train_als(r, cfg6, checkpointer=ck, checkpoint_every=1)
        m_fresh = train_als(r, cfg6)
        np.testing.assert_allclose(m.item_factors, m_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)

    def test_lower_target_keeps_same_run_checkpoints(self, tmp_path):
        """Re-running with a LOWER iteration target than previously
        checkpointed must not destroy the same run's valid higher-step
        checkpoints — they stay usable for a later higher-target run."""
        r = _ratings()
        ck = TrainCheckpointer(tmp_path / "als")
        cfg6 = ALSConfig(rank=8, iterations=6, lambda_=0.1, seed=5)
        train_als(r, cfg6, checkpointer=ck, checkpoint_every=1)
        assert ck.steps() == [5, 6]
        cfg3 = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        m3 = train_als(r, cfg3, checkpointer=ck, checkpoint_every=1)
        m3_fresh = train_als(r, cfg3)
        np.testing.assert_allclose(m3.item_factors, m3_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)
        # higher-step checkpoints survived; own steps saved alongside
        assert 6 in ck.steps() and 3 in ck.steps()
        # raising the target back to 6 resumes from step 6 exactly
        m6 = train_als(r, cfg6, checkpointer=ck, checkpoint_every=1)
        m6_fresh = train_als(r, cfg6)
        np.testing.assert_allclose(m6.item_factors, m6_fresh.item_factors,
                                   rtol=1e-5, atol=1e-5)


class TestOverwriteAtomicity:
    def test_overwrite_same_step(self, ckptr_factory):
        ck = ckptr_factory()
        ck.save(2, {"v": np.zeros((2, 2)), "it": np.int64(2)})
        ck.save(2, {"v": np.ones((2, 2)), "it": np.int64(2)})
        step, st = ck.restore()
        assert step == 2 and float(st["v"][0, 0]) == 1.0
        assert not (ck.directory / "step_2.tmp").exists()
        assert not (ck.directory / "step_2.old").exists()

    def test_leftover_tmp_ignored_and_cleaned(self, ckptr_factory):
        ck = ckptr_factory()
        ck.save(1, {"v": np.zeros((2, 2)), "it": np.int64(1)})
        # simulate a crash mid-overwrite: tmp dir present, original intact
        (ck.directory / "step_1.tmp").mkdir()
        assert ck.steps() == [1]
        ck.save(1, {"v": np.ones((2, 2)), "it": np.int64(1)})
        _, st = ck.restore()
        assert float(st["v"][0, 0]) == 1.0

    def test_crash_between_swap_renames_recovers(self, ckptr_factory):
        """Crash window: step_N renamed to .old but .tmp not yet promoted —
        the COMPLETE .tmp must be recovered as step_N."""
        ck = ckptr_factory()
        ck.save(3, {"v": np.zeros((2, 2)), "it": np.int64(3)})
        d = ck.directory
        # reconstruct the mid-swap state by hand
        (d / "step_3").rename(d / "step_3.old")
        ck2 = ckptr_factory()
        ck2.save(3, {"v": np.ones((2, 2)), "it": np.int64(3)})
        # ...but first simulate: old present + complete tmp, no final
        (d / "step_3").rename(d / "step_3.tmp")
        assert ck2.steps() == [3]  # recovery promoted the tmp
        _, st = ck2.restore()
        assert float(st["v"][0, 0]) == 1.0
        assert not (d / "step_3.old").exists()
        assert not (d / "step_3.tmp").exists()

    def test_displaced_old_restored_when_final_missing(self, ckptr_factory):
        ck = ckptr_factory()
        ck.save(4, {"v": np.full((2, 2), 7.0), "it": np.int64(4)})
        d = ck.directory
        (d / "step_4").rename(d / "step_4.old")  # crash before tmp landed
        assert ck.steps() == [4]
        _, st = ck.restore()
        assert float(st["v"][0, 0]) == 7.0


class TestDurability:
    """save() must fsync contents BEFORE the _COMPLETE marker, the marker
    itself, and the directories the renames happened in (ISSUE 4
    satellite: a power cut can surface a missing checkpoint, never a
    "complete" one with torn contents)."""

    def test_save_fsyncs_files_marker_and_dirs(self, tmp_path, monkeypatch):
        synced: list[str] = []
        real_fsync = os.fsync

        def recording_fsync(fd):
            synced.append(os.readlink(f"/proc/self/fd/{fd}"))
            real_fsync(fd)

        monkeypatch.setattr(os, "fsync", recording_fsync)
        ck = TrainCheckpointer(tmp_path / "ck", backend="npz")
        ck.save(1, {"v": np.zeros((2, 2)), "it": np.int64(1)})

        def idx(suffix):
            hits = [i for i, p in enumerate(synced) if p.endswith(suffix)]
            assert hits, f"nothing fsynced matching {suffix!r}: {synced}"
            return hits[0]

        # the npz payload, then the marker, then the root dir (post-rename)
        assert idx("state.npz") < idx("_COMPLETE") < idx("/ck")
        # the tmp step dir itself was synced before its rename
        assert any("step_1.tmp" in p and p.endswith(".tmp") for p in synced)

    def test_restore_first_valid_walks_past_corruption(self, tmp_path):
        """ISSUE 4 satellite: the newest-first walk must skip a truncated
        state.npz AND a foreign-shape step, landing on the newest step
        that restores and validates."""
        ck = TrainCheckpointer(tmp_path / "ck", backend="npz", keep=10)
        good = {"u": np.zeros((4, 2), np.float32),
                "v": np.zeros((3, 2), np.float32)}
        ck.save(2, {**good, "it": np.int64(2)})
        ck.save(4, {**good, "it": np.int64(4)})
        # step 6: a foreign run's shapes — restores fine, fails validation
        ck.save(6, {"u": np.zeros((9, 9), np.float32),
                    "v": np.zeros((9, 9), np.float32), "it": np.int64(6)})
        # step 8: torn on disk after the marker claimed completeness
        ck.save(8, {**good, "it": np.int64(8)})
        npz = ck.directory / "step_8" / "state.npz"
        npz.write_bytes(npz.read_bytes()[:20])

        def is_valid(state):
            return state["u"].shape == (4, 2)

        got = ck.restore_first_valid(is_valid)
        assert got is not None
        step, state = got
        assert step == 4
        assert int(state["it"]) == 4

    def test_restore_first_valid_all_bad_returns_none(self, tmp_path):
        ck = TrainCheckpointer(tmp_path / "ck", backend="npz")
        ck.save(1, {"u": np.zeros((9, 9), np.float32), "it": np.int64(1)})
        assert ck.restore_first_valid(lambda s: s["u"].shape == (4, 2)) is None


# ---------------------------------------------------------------------------
# sharded (multi-host, elastic) checkpoints — ISSUE 8


def _state(nu=10, ni=7, rank=3, seed=0):
    rng = np.random.default_rng(seed)
    return {"u": rng.standard_normal((nu, rank)).astype(np.float32),
            "v": rng.standard_normal((ni, rank)).astype(np.float32),
            "it": np.int64(1), "fp": np.uint64(42)}


def _sharded_save(directory, step, state, nproc, *, keep=2):
    """Drive N ShardedTrainCheckpointer writers through one save() —
    threads stand in for the N host processes; the FileBarrier over the
    shared directory is exactly what coordinates real hosts."""
    cks = [ShardedTrainCheckpointer(directory, keep=keep, process_id=p,
                                    num_processes=nproc,
                                    barrier_timeout_s=30.0)
           for p in range(nproc)]
    errs: list[BaseException] = []

    def run(ck):
        try:
            ck.save(step, state)
        except BaseException as e:  # noqa: BLE001 — surfaced via assert
            errs.append(e)

    threads = [threading.Thread(target=run, args=(ck,)) for ck in cks]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs, errs
    return cks


class TestShardedCheckpointer:
    def test_single_process_roundtrip(self, tmp_path):
        ck = ShardedTrainCheckpointer(tmp_path / "ck")
        st = _state()
        ck.save(1, st)
        got_step, got = ck.restore()
        assert got_step == 1
        np.testing.assert_array_equal(got["u"], st["u"])
        np.testing.assert_array_equal(got["v"], st["v"])
        assert int(got["it"]) == 1 and int(got["fp"]) == 42
        assert ck.steps() == [1] and ck.partial_steps() == []

    def test_two_writers_reassemble_bitwise(self, tmp_path):
        st = _state()
        _sharded_save(tmp_path / "ck", 1, st, nproc=2)
        # any-topology reader: a single-process checkpointer reassembles
        # the 2-shard manifest into the exact global matrices (2→1)
        reader = ShardedTrainCheckpointer(tmp_path / "ck")
        step, got = reader.restore()
        assert step == 1
        np.testing.assert_array_equal(got["u"], st["u"])
        np.testing.assert_array_equal(got["v"], st["v"])
        assert int(got["fp"]) == 42
        # each process wrote only its slice
        names = {p.name for p in (tmp_path / "ck" / "step_1").iterdir()}
        assert "shard_00000_of_00002.npz" in names
        assert "shard_00001_of_00002.npz" in names
        assert "manifest.json" in names

    def test_reshard_state_slices_partition_the_rows(self, tmp_path):
        st = _state(nu=11, ni=5)  # 11 rows: uneven 3-way split
        slices = [reshard_state(st, process_id=p, num_processes=3)
                  for p in range(3)]
        np.testing.assert_array_equal(
            np.concatenate([s["u"] for s in slices]), st["u"])
        np.testing.assert_array_equal(
            np.concatenate([s["v"] for s in slices]), st["v"])
        for s in slices:  # scalars replicate
            assert int(s["fp"]) == 42

    def test_retention_counts_only_complete_steps(self, tmp_path):
        """ISSUE 8 satellite: a newer PARTIAL step must not count toward
        `keep` — the newest complete step survives retention even while a
        newer torn directory sits beside it."""
        d = tmp_path / "ck"
        ck = ShardedTrainCheckpointer(d, keep=2)
        ck.save(1, _state())
        ck.save(2, _state())
        # a torn step 3: shard on disk, no manifest (crash mid-commit)
        torn = d / "step_3"
        torn.mkdir()
        (torn / "shard_00000_of_00001.npz").write_bytes(b"x")
        assert ck.steps() == [1, 2] and ck.partial_steps() == [3]
        assert ck.latest_step() == 2  # the torn step never shadows
        # next complete save prunes by COMPLETE steps only: if the torn
        # step 3 counted toward keep=2, step 2 would be deleted here
        ck.save(4, _state())
        assert ck.steps() == [2, 4]

    def test_corrupt_shard_rejected_and_walked_past(self, tmp_path):
        from predictionio_tpu.obs.metrics import METRICS

        d = tmp_path / "ck"
        ck = ShardedTrainCheckpointer(d, keep=4)
        ck.save(1, _state(seed=1))
        ck.save(2, _state(seed=2))
        shard = d / "step_2" / "shard_00000_of_00001.npz"
        shard.write_bytes(b"\x00" * 64)  # bit rot after commit
        with pytest.raises(ShardIntegrityError, match="corrupt"):
            ck.restore()
        assert METRICS.get(
            "pio_ckpt_shard_verify_failures_total").value() >= 1
        got = ck.restore_first_valid(lambda s: True)
        assert got is not None and got[0] == 1
        np.testing.assert_array_equal(got[1]["u"], _state(seed=1)["u"])

    def test_barrier_timeout_is_transient(self, tmp_path):
        from predictionio_tpu.workflow.supervisor import (
            BarrierTimeoutError, classify_error)

        ck = ShardedTrainCheckpointer(tmp_path / "ck", process_id=0,
                                      num_processes=2, barrier_timeout_s=0.3)
        with pytest.raises(BarrierTimeoutError) as ei:
            ck.save(1, _state())  # peer never shows up
        assert classify_error(ei.value) == "transient"
        # the lone shard landed but the step must not exist
        assert ck.steps() == [] and ck.partial_steps() == [1]


class TestShardedChaos:
    """The two torn-save windows, driven through the instrumented fault
    sites (ISSUE 8 satellite: save killed between shard write and
    manifest commit resumes from the previous complete step and reports
    the discarded partial in `pio status`)."""

    @pytest.mark.chaos
    def test_shard_write_fault_leaves_previous_step(self, tmp_path):
        from predictionio_tpu.faults import FAULTS, FaultInjected

        ck = ShardedTrainCheckpointer(tmp_path / "ck")
        ck.save(1, _state())
        FAULTS.inject("checkpoint.shard_write", "error")
        with pytest.raises(FaultInjected):
            ck.save(2, _state())
        assert ck.steps() == [1]
        step, _ = ck.restore()
        assert step == 1

    @pytest.mark.chaos
    def test_kill_between_shard_write_and_manifest_commit(
            self, tmp_path, capsys):
        from predictionio_tpu.obs.metrics import METRICS
        from predictionio_tpu.tools import cli
        from predictionio_tpu.faults import FAULTS, FaultInjected

        d = tmp_path / "ck"
        ck = ShardedTrainCheckpointer(d)
        ck.save(1, _state(seed=1))
        FAULTS.inject("checkpoint.manifest_commit", "error")
        with pytest.raises(FaultInjected):
            ck.save(2, _state(seed=2))
        # the kill window: shard durable, manifest missing
        assert (d / "step_2" / "shard_00000_of_00001.npz").is_file()
        assert not (d / "step_2" / "manifest.json").exists()
        assert ck.partial_steps() == [2]
        FAULTS.clear()

        # reopen (the relaunch): resume lands on step 1, the torn step is
        # discarded and recorded
        ck2 = ShardedTrainCheckpointer(d)
        got = ck2.restore_first_valid(lambda s: True)
        assert got is not None and got[0] == 1
        np.testing.assert_array_equal(got[1]["u"], _state(seed=1)["u"])
        assert not (d / "step_2").exists()
        assert [e["step"] for e in ck2.discarded()] == [2]
        assert METRICS.get(
            "pio_ckpt_partial_steps_discarded_total").value() >= 1

        # ...and the operator sees it in `pio status --checkpoint-dir`
        assert cli.main(["status", "--checkpoint-dir", str(d)]) == 0
        out = capsys.readouterr().out
        assert "discarded partial step 2" in out
        assert "complete steps [1]" in out


class TestShardedALSResume:
    def test_als_resume_through_sharded_checkpointer(self, tmp_path):
        """train_als takes a ShardedTrainCheckpointer transparently: an
        interrupted run resumes from its sharded manifest and matches the
        uninterrupted run."""
        r = _ratings()
        cfg8 = ALSConfig(rank=8, iterations=8, lambda_=0.1, seed=5)
        baseline = train_als(r, cfg8)

        ck = ShardedTrainCheckpointer(tmp_path / "als")
        cfg3 = ALSConfig(rank=8, iterations=3, lambda_=0.1, seed=5)
        train_als(r, cfg3, checkpointer=ck, checkpoint_every=1)
        assert ck.latest_step() == 3

        resumed = train_als(r, cfg8, checkpointer=ck, checkpoint_every=1)
        np.testing.assert_allclose(
            resumed.item_factors, baseline.item_factors, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            resumed.user_factors, baseline.user_factors, rtol=1e-5, atol=1e-5)
