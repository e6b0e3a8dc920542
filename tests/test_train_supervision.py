"""Preemption-tolerant training (ISSUE 4): TrainSupervisor retry/resume/
heartbeat/budget, the orphan reaper, and model-blob integrity with deploy
fallback — all proven via the deterministic fault-injection harness
(predictionio_tpu/faults.py) at the new ``train.step`` /
``train.persist`` sites.

Acceptance scenarios:
- ALS training with a ``train.step`` fault injected mid-run is killed and
  resupervised, resumes from the latest checkpoint (the step counter
  proves no iteration re-ran), and the final model matches an
  uninterrupted run's within tolerance with exactly one COMPLETED
  instance.
- A stale-heartbeat INIT orphan is reaped to ABANDONED, and a corrupted
  newest blob causes /reload to fall back to the previous COMPLETED
  instance while serving stays up.

All train_chaos-marked tests run under conftest's SIGALRM guard and get
every armed fault cleared on teardown.
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import replace
from datetime import datetime, timedelta, timezone
from pathlib import Path

import numpy as np
import pytest
import requests

from predictionio_tpu.controller import Engine, EngineParams
from predictionio_tpu.models import als
from predictionio_tpu.models.als import ALSConfig, train_als
from predictionio_tpu.storage import EngineInstance, Model, Storage
from predictionio_tpu.storage.bimap import BiMap
from predictionio_tpu.storage.frame import Ratings
from predictionio_tpu.testing.sample_engine import (
    SampleAlgoParams,
    SampleAlgorithm,
    SampleDataSource,
    SampleDataSourceParams,
    SamplePreparator,
    SampleQuery,
    SampleServing,
)
from predictionio_tpu.workflow import (
    Context,
    ModelIntegrityError,
    deserialize_models,
    prepare_deploy,
    run_evaluation,
    run_train,
)
from predictionio_tpu.workflow.create_server import (
    EngineServer,
    create_engine_server_app,
)
from predictionio_tpu.faults import FAULTS, FaultInjected
from predictionio_tpu.workflow.supervisor import (
    DEFAULT_PEER_STALE_AFTER_S,
    DEFAULT_STALE_AFTER_S,
    BarrierTimeoutError,
    CoordinatorUnreachableError,
    HostLostError,
    TrainBudgetExceeded,
    TrainSupervisor,
    TransientTrainingError,
    check_peer_liveness,
    classify_error,
    heartbeat_age_s,
    host_heartbeats,
    reap_orphans,
    stale_peers,
)
from tests.helpers import ServerThread

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# harness: a tiny sample engine (fast, storage-free training)


class EchoAlgorithm(SampleAlgorithm):
    query_class = SampleQuery


def make_echo_engine() -> Engine:
    return Engine(
        data_source_classes=SampleDataSource,
        preparator_classes=SamplePreparator,
        algorithm_classes={"echo": EchoAlgorithm},
        serving_classes=SampleServing,
    )


def _echo_params() -> EngineParams:
    return EngineParams(
        data_source_params=("", SampleDataSourceParams(id=0)),
        algorithm_params_list=(("echo", SampleAlgoParams(id=1)),),
    )


def _train_echo(**kw) -> str:
    return run_train(make_echo_engine(), _echo_params(), Context(),
                     engine_factory="tests.test_train_supervision:"
                                    "make_echo_engine",
                     **kw)


def _instances():
    return Storage.get_metadata().engine_instance_get_all()


# ---------------------------------------------------------------------------
# error classifier


def test_classifier_fatal_errors():
    assert classify_error(ValueError("bad params")) == "fatal"
    assert classify_error(KeyError("x")) == "fatal"
    # non-Exception BaseExceptions are NEVER retried: the operator (or
    # the runtime) asked the process to die
    assert classify_error(KeyboardInterrupt()) == "fatal"
    assert classify_error(SystemExit(1)) == "fatal"


def test_classifier_held_chip_and_hbm_overflow_are_fatal():
    """The v5e runtime's own messages (libtpu 0.0.34, taken on the chip
    and from its compiler): a chip another process holds and a program
    that does not fit in HBM stay that way through any back-off, so they
    are reported once instead of retried."""
    assert classify_error(RuntimeError(
        "Unable to initialize backend 'tpu': ABORTED: Internal error when "
        "accessing libtpu multi-process lockfile. Run \"$ sudo rm "
        "/tmp/libtpu_lockfile\". (set JAX_PLATFORMS='' to automatically "
        "choose an available backend)")) == "fatal"
    assert classify_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Allocation (size=25600000000) would exceed "
        "memory (size=17179869184) :: #allocation9 [shape = "
        "'f32[80000,80000]{0,1:T(8,128)}', space=hbm]")) == "fatal"
    assert classify_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 4.00G. That was not possible. There are 3.75G free.; "
        "(0x0x0_HBM0)")) == "fatal"
    # an UNAVAILABLE wrapped around a held chip is still a held chip
    assert classify_error(RuntimeError(
        "UNAVAILABLE: TPU is already in use by pid 7")) == "fatal"


def test_classifier_transient_errors():
    assert classify_error(RuntimeError("TPU device lost")) == "transient"
    assert classify_error(RuntimeError("worker preempted by scheduler")) == "transient"
    assert classify_error(RuntimeError("UNAVAILABLE: socket closed")) == "transient"
    assert classify_error(FaultInjected("train.step")) == "transient"
    assert classify_error(TransientTrainingError("wrapped")) == "transient"
    assert classify_error(MemoryError()) == "transient"
    assert classify_error(ConnectionResetError()) == "transient"


def test_classifier_multihost_failure_modes():
    """A lost peer, a timed-out barrier, or an unreachable coordinator is
    a topology event, not a code bug: the supervisor must retry (the
    relaunch resumes from the last complete sharded manifest)."""
    assert classify_error(HostLostError("host lost: peer heartbeat stale "
                                        "for process(es) [1]")) == "transient"
    assert classify_error(BarrierTimeoutError(
        "barrier timeout at 'step2.shards.n2'")) == "transient"
    assert classify_error(CoordinatorUnreachableError(
        "coordinator unreachable at host0:1234")) == "transient"
    # message patterns alone (e.g. surfaced through a RuntimeError from
    # jax.distributed) classify the same way
    assert classify_error(RuntimeError("barrier timed out waiting for "
                                       "peers")) == "transient"
    assert classify_error(RuntimeError("coordinator unreachable")) == "transient"
    assert classify_error(RuntimeError("peer heartbeat stale")) == "transient"
    assert classify_error(RuntimeError("host lost during all-reduce")) == "transient"


# ---------------------------------------------------------------------------
# multi-host peer liveness (host_heartbeats on the instance record)


def _mh_instance(beats: dict) -> EngineInstance:
    import json

    return EngineInstance(id="mh-1", status="INIT",
                          host_heartbeats=json.dumps(beats))


def test_host_heartbeats_parses_and_tolerates_garbage():
    now = datetime.now(timezone.utc).isoformat()
    inst = _mh_instance({"0": {"ts": now, "attempt": 1},
                         "1": {"ts": now, "attempt": 1}})
    beats = host_heartbeats(inst)
    assert set(beats) == {0, 1}
    assert beats[0]["attempt"] == 1
    # unparseable blob → empty map, never a throw
    assert host_heartbeats(EngineInstance(id="x", host_heartbeats="{oops")) == {}
    assert host_heartbeats(EngineInstance(id="y")) == {}


def test_stale_peers_flags_stale_and_missing_hosts():
    now = datetime.now(timezone.utc)
    fresh = now.isoformat()
    old = (now - timedelta(seconds=DEFAULT_PEER_STALE_AFTER_S * 3)).isoformat()
    inst = _mh_instance({"0": {"ts": fresh, "attempt": 1},
                         "1": {"ts": old, "attempt": 1}})
    # peer 1 is stale; peer 2 never stamped at all
    assert stale_peers(inst, num_processes=3, now=now) == [1, 2]
    # excluding self: process 1 asking about its own staleness is moot
    assert stale_peers(inst, num_processes=3, self_id=1, now=now) == [2]
    # all fresh → no stale peers
    inst2 = _mh_instance({"0": {"ts": fresh}, "1": {"ts": fresh}})
    assert stale_peers(inst2, num_processes=2, now=now) == []


def test_check_peer_liveness_raises_host_lost():
    now = datetime.now(timezone.utc)
    old = (now - timedelta(seconds=500)).isoformat()
    inst = _mh_instance({"0": {"ts": now.isoformat()}, "1": {"ts": old}})
    with pytest.raises(HostLostError, match="peer heartbeat stale"):
        check_peer_liveness(inst, num_processes=2, self_id=0, now=now)
    # and the raise classifies transient end to end
    try:
        check_peer_liveness(inst, num_processes=2, self_id=0, now=now)
    except HostLostError as e:
        assert classify_error(e) == "transient"


# ---------------------------------------------------------------------------
# TrainSupervisor unit behavior


@pytest.mark.train_chaos
def test_supervisor_retries_transient_then_succeeds():
    calls = {"n": 0}

    def body():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientTrainingError(f"preempted #{calls['n']}")
        return "done"

    sup = TrainSupervisor(max_retries=3, retry_backoff_s=0.01)
    assert sup.run(body) == "done"
    assert calls["n"] == 3
    assert sup.attempts == 3
    assert sup.retries_used == 2


@pytest.mark.train_chaos
def test_supervisor_fatal_error_never_retries():
    calls = {"n": 0}

    def body():
        calls["n"] += 1
        raise ValueError("wrong shape")

    sup = TrainSupervisor(max_retries=5, retry_backoff_s=0.01)
    with pytest.raises(ValueError):
        sup.run(body)
    assert calls["n"] == 1


@pytest.mark.train_chaos
def test_supervisor_retries_exhausted_reraises():
    def body():
        raise TransientTrainingError("always preempted")

    sup = TrainSupervisor(max_retries=2, retry_backoff_s=0.01)
    with pytest.raises(TransientTrainingError):
        sup.run(body)
    assert sup.attempts == 3


@pytest.mark.train_chaos
def test_supervisor_budget_aborts_hung_attempt():
    release = threading.Event()

    def body():
        release.wait(30)  # a hung device call

    sup = TrainSupervisor(train_budget_s=0.4)
    t0 = time.monotonic()
    try:
        with pytest.raises(TrainBudgetExceeded):
            sup.run(body)
        assert time.monotonic() - t0 < 10  # aborted, not wedged for 30s
    finally:
        release.set()  # free the abandoned zombie thread


@pytest.mark.train_chaos
def test_supervisor_heartbeat_stamps_attempts():
    beats: list[tuple[str, int]] = []
    calls = {"n": 0}

    def body():
        calls["n"] += 1
        time.sleep(0.12)
        if calls["n"] == 1:
            raise TransientTrainingError("preempted")
        return "ok"

    sup = TrainSupervisor(max_retries=1, retry_backoff_s=0.01,
                          heartbeat_s=0.03,
                          on_heartbeat=lambda iso, at: beats.append((iso, at)))
    sup.run(body)
    assert len(beats) >= 3  # initial + periodic beats across two attempts
    assert beats[0][1] == 0
    assert beats[-1][1] == 1  # the retry's attempt index was stamped
    datetime.fromisoformat(beats[-1][0])  # timestamps are ISO instants


# ---------------------------------------------------------------------------
# run_train under supervision (train.persist site, sample engine)


@pytest.mark.train_chaos
def test_run_train_retries_injected_persist_fault():
    """A transient fault at train.persist kills attempt 1; the supervisor
    re-runs the body, the record shows attempt=1 + a heartbeat, and
    exactly one COMPLETED instance exists with a checksummed blob."""
    FAULTS.inject("train.persist", "error", times=1)
    iid = _train_echo(max_retries=2, retry_backoff_s=0.01, heartbeat_s=0.05)
    assert FAULTS.fired("train.persist") == 1
    insts = _instances()
    assert [i.status for i in insts] == ["COMPLETED"]
    inst = insts[0]
    assert inst.id == iid
    assert inst.attempt == 1  # the retry was recorded
    assert inst.last_heartbeat != ""
    blob = Storage.get_models().get(iid)
    assert blob is not None
    assert blob.checksum == Model.compute_checksum(blob.models)


@pytest.mark.train_chaos
def test_run_train_fatal_fault_aborts_without_retry():
    FAULTS.inject("train.persist", "error", exc=ValueError("bad model"))
    with pytest.raises(ValueError):
        _train_echo(max_retries=3, retry_backoff_s=0.01)
    assert FAULTS.fired("train.persist") == 1  # no retry burned the budget
    assert [i.status for i in _instances()] == ["ABORTED"]


@pytest.mark.train_chaos
def test_run_train_keyboard_interrupt_marks_aborted():
    """Satellite: Ctrl-C used to leave the instance INIT forever because
    only Exception was caught; BaseException must flip it to ABORTED."""
    FAULTS.inject("train.persist", "error", exc=KeyboardInterrupt())
    with pytest.raises(KeyboardInterrupt):
        _train_echo(max_retries=3, retry_backoff_s=0.01)
    assert [i.status for i in _instances()] == ["ABORTED"]


@pytest.mark.train_chaos
def test_run_train_budget_aborts_cleanly():
    FAULTS.inject("train.persist", "slow", delay_s=30.0)
    with pytest.raises(TrainBudgetExceeded):
        _train_echo(train_budget_s=0.4)
    assert [i.status for i in _instances()] == ["ABORTED"]
    FAULTS.clear()  # don't leave the zombie sleeping against a live fault


def test_run_evaluation_keyboard_interrupt_marks_aborted():
    """Satellite: same BaseException contract for run_evaluation."""
    class _KIEngine:
        def batch_eval(self, ctx, params_list):
            raise KeyboardInterrupt

    class _KIEval:
        engine = _KIEngine()
        all_metrics = ()

    with pytest.raises(KeyboardInterrupt):
        run_evaluation(_KIEval(), [EngineParams()])
    evs = Storage.get_metadata().evaluation_instance_get_all()
    assert [e.status for e in evs] == ["ABORTED"]


# ---------------------------------------------------------------------------
# orphan reaper


def _stale_init_instance(age_s: float, **kw) -> str:
    t = datetime.now(timezone.utc) - timedelta(seconds=age_s)
    return Storage.get_metadata().engine_instance_insert(EngineInstance(
        status="INIT", start_time=t, last_heartbeat=t.isoformat(), **kw))


def test_reap_orphans_flips_stale_init_to_abandoned():
    meta = Storage.get_metadata()
    dead = _stale_init_instance(3600)
    live = _stale_init_instance(1)
    reaped = reap_orphans(meta, stale_after_s=600)
    assert [i.id for i in reaped] == [dead]
    assert meta.engine_instance_get(dead).status == "ABANDONED"
    assert meta.engine_instance_get(live).status == "INIT"  # untouched


def test_reap_orphans_dry_run_changes_nothing():
    meta = Storage.get_metadata()
    dead = _stale_init_instance(3600)
    reaped = reap_orphans(meta, stale_after_s=600, dry_run=True)
    assert [i.id for i in reaped] == [dead]
    assert meta.engine_instance_get(dead).status == "INIT"


def test_reap_orphans_uses_start_time_for_pre_supervisor_records():
    """Rows written before the heartbeat column existed have no stamp;
    their start_time stands in."""
    meta = Storage.get_metadata()
    t = datetime.now(timezone.utc) - timedelta(seconds=3600)
    iid = meta.engine_instance_insert(
        EngineInstance(status="INIT", start_time=t))
    assert heartbeat_age_s(meta.engine_instance_get(iid)) > 3000
    assert [i.id for i in reap_orphans(meta, stale_after_s=600)] == [iid]


def test_run_train_sweeps_orphans_automatically():
    dead = _stale_init_instance(2 * DEFAULT_STALE_AFTER_S)
    _train_echo()
    meta = Storage.get_metadata()
    assert meta.engine_instance_get(dead).status == "ABANDONED"


def test_pio_admin_reap_cli():
    from predictionio_tpu.tools import cli

    meta = Storage.get_metadata()
    dead = _stale_init_instance(3600)
    assert cli.main(["admin", "reap", "--stale-after-s", "600",
                     "--dry-run"]) == 0
    assert meta.engine_instance_get(dead).status == "INIT"
    assert cli.main(["admin", "reap", "--stale-after-s", "600"]) == 0
    assert meta.engine_instance_get(dead).status == "ABANDONED"


# ---------------------------------------------------------------------------
# model-blob integrity


def test_model_checksum_roundtrip_and_verify():
    iid = _train_echo()
    meta = Storage.get_metadata()
    inst = meta.engine_instance_get(iid)
    blob = Storage.get_models().get(iid)
    assert blob.checksum.startswith("sha256:")
    # verification passes on the intact blob
    result = prepare_deploy(make_echo_engine(), inst)
    assert result.models


def test_corrupt_blob_fails_integrity_check():
    iid = _train_echo()
    inst = Storage.get_metadata().engine_instance_get(iid)
    good = Storage.get_models().get(iid)
    # bit-rot: bytes change, stored checksum doesn't
    Storage.get_models().insert(Model(
        id=iid, models=good.models[:-1] + b"X", checksum=good.checksum))
    with pytest.raises(ModelIntegrityError):
        prepare_deploy(make_echo_engine(), inst)


def test_legacy_blob_without_checksum_still_deploys():
    iid = _train_echo()
    inst = Storage.get_metadata().engine_instance_get(iid)
    good = Storage.get_models().get(iid)
    Storage.get_models().insert(Model(id=iid, models=good.models, checksum=""))
    result = prepare_deploy(make_echo_engine(), inst)  # no checksum: no check
    assert result.models


def test_localfs_models_checksum_sidecar(tmp_path):
    from predictionio_tpu.storage.registry import LocalFSModels

    store = LocalFSModels(str(tmp_path))
    blob = b"serialized model bytes"
    store.insert(Model(id="ei_1", models=blob,
                       checksum=Model.compute_checksum(blob)))
    assert (tmp_path / "ei_1.sha256").exists()
    m = store.get("ei_1")
    assert m.checksum == Model.compute_checksum(blob)
    assert store.delete("ei_1")
    assert not (tmp_path / "ei_1.sha256").exists()


# ---------------------------------------------------------------------------
# deploy / reload fallback past a corrupt newest blob


def _corrupt_blob(iid: str) -> None:
    good = Storage.get_models().get(iid)
    Storage.get_models().insert(Model(
        id=iid, models=b"rotted" + good.models, checksum=good.checksum))


@pytest.mark.train_chaos
def test_deploy_falls_back_past_corrupt_newest():
    iid1 = _train_echo()
    iid2 = _train_echo()
    _corrupt_blob(iid2)
    meta = Storage.get_metadata()
    inst2 = meta.engine_instance_get(iid2)
    server = EngineServer(make_echo_engine(), inst2, batch_window_ms=0)
    assert server.deployed.instance.id == iid1  # substituted next-newest
    assert [s["engineInstanceId"] for s in server.deploy_skips] == [iid2]


@pytest.mark.train_chaos
def test_pinned_deploy_fails_loud_on_corrupt_blob():
    iid = _train_echo()
    _corrupt_blob(iid)
    inst = Storage.get_metadata().engine_instance_get(iid)
    with pytest.raises(ModelIntegrityError):
        EngineServer(make_echo_engine(), inst, batch_window_ms=0,
                     fallback=False)


@pytest.mark.train_chaos
def test_reload_falls_back_and_serving_stays_up():
    """ISSUE 4 acceptance (part 2): the newest COMPLETED instance's blob
    is corrupt; GET /reload lands on the previous COMPLETED instance, the
    skip is reported in /health.json and /stats.json, and queries keep
    answering throughout."""
    iid1 = _train_echo()
    inst1 = Storage.get_metadata().engine_instance_get(iid1)
    server = EngineServer(make_echo_engine(), inst1, batch_window_ms=0)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        iid2 = _train_echo()  # newer COMPLETED instance...
        _corrupt_blob(iid2)   # ...whose blob rotted on disk

        r = requests.get(st.url + "/reload", timeout=10)
        assert r.status_code == 200
        assert r.json()["engineInstanceId"] == iid1  # fell back
        assert server.deployed.instance.id == iid1

        h = requests.get(st.url + "/health.json", timeout=10).json()
        assert h["model"]["engineInstanceId"] == iid1
        assert h["model"]["fallbackActive"] is True
        assert [s["engineInstanceId"] for s in h["model"]["skipped"]] == [iid2]

        stats = requests.get(st.url + "/stats.json", timeout=10).json()
        assert stats["model"]["fallbackActive"] is True

        # serving never went down
        q = requests.post(st.url + "/queries.json", json={"q": 3}, timeout=10)
        assert q.status_code == 200
        assert q.json()["value"] == 3
    finally:
        st.stop()


# ---------------------------------------------------------------------------
# ALS chaos acceptance: mid-run preemption resumes from the checkpoint


def _ratings(nu=40, ni=30, n=600, seed=0):
    rng = np.random.default_rng(seed)
    return Ratings(
        user_indices=rng.integers(0, nu, n).astype(np.int64),
        item_indices=rng.integers(0, ni, n).astype(np.int64),
        ratings=(rng.random(n).astype(np.float32) * 4 + 1),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
    )


ALS_CFG = ALSConfig(rank=8, iterations=8, lambda_=0.1, seed=5)


class RatingsDataSource:
    def __init__(self, params=None):
        self.params = params

    def read_training(self, ctx):
        return _ratings()

    def read_eval(self, ctx):
        return []


class ALSChaosAlgorithm:
    params_class = None
    persist_model = True

    def __init__(self, params=None):
        self.params = params

    def train(self, ctx, ratings):
        return train_als(ratings, ALS_CFG,
                         checkpointer=ctx.checkpointer("als"),
                         checkpoint_every=ctx.checkpoint_every)

    def predict(self, model, query):
        return None


class PassServing:
    def __init__(self, params=None):
        self.params = params

    def serve(self, query, predictions):
        return predictions[0]


def make_als_chaos_engine() -> Engine:
    from predictionio_tpu.controller import IdentityPreparator

    return Engine(
        data_source_classes=RatingsDataSource,
        preparator_classes=IdentityPreparator,
        algorithm_classes={"als": ALSChaosAlgorithm},
        serving_classes=PassServing,
    )


@pytest.mark.train_chaos
def test_als_midrun_preemption_resumes_and_matches(tmp_path, monkeypatch):
    """ISSUE 4 acceptance (part 1): a train.step fault kills ALS training
    mid-run (after checkpoints exist); the supervisor resumes from the
    latest checkpoint — the device-step counter proves no iteration
    re-ran beyond the checkpoint lag — and the final factors match an
    uninterrupted run's, with exactly one COMPLETED instance."""
    baseline = train_als(_ratings(), ALS_CFG)

    # count actual device training steps across all attempts
    steps = {"n": 0}
    orig_make = als.make_train_step

    def counting_make(*a, **kw):
        step = orig_make(*a, **kw)

        def counted(*sa, **skw):
            steps["n"] += 1
            return step(*sa, **skw)

        return counted

    monkeypatch.setattr(als, "make_train_step", counting_make)

    # checkpoint_every=2 over 8 iterations; the fault skips 4 iteration
    # entries (steps 2 and 4 are durable) then kills the 5th
    FAULTS.inject("train.step", "error", times=1, after=4)
    iid = run_train(
        make_als_chaos_engine(),
        EngineParams(algorithm_params_list=(("als", None),)),
        Context(mode="Train", checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every=2),
        max_retries=2, retry_backoff_s=0.01, heartbeat_s=0.05,
    )
    assert FAULTS.fired("train.step") == 1

    insts = _instances()
    assert [i.status for i in insts] == ["COMPLETED"]  # exactly one, done
    assert insts[0].attempt == 1

    # resume, not restart: attempt 1 ran iterations 0-3, attempt 2 ran
    # 4-7 from the step-4 checkpoint — 8 device steps total. A restart
    # would have run 12.
    assert steps["n"] == ALS_CFG.iterations

    blob = Storage.get_models().get(iid)
    assert blob.checksum == Model.compute_checksum(blob.models)
    (model,) = deserialize_models(blob.models)
    np.testing.assert_allclose(model.item_factors, baseline.item_factors,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(model.user_factors, baseline.user_factors,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# docs guard: every chaos site in faults.py is documented


def test_every_fault_site_documented_in_operations_md():
    """predictionio_tpu/faults.py's docstring is the registry of chaos
    sites; docs/operations.md must document each one."""
    from predictionio_tpu import faults

    sites = re.findall(r"^- ``([a-z_.]+)``", faults.__doc__, re.MULTILINE)
    assert len(sites) >= 12  # the registry keeps growing, never shrinks
    ops = (REPO / "docs" / "operations.md").read_text()
    missing = [s for s in sites if s not in ops]
    assert not missing, f"chaos sites undocumented in operations.md: {missing}"
    for new_site in ("train.step", "train.persist",
                     "admission.decide",
                     "checkpoint.shard_write", "checkpoint.manifest_commit",
                     "train.host_lost",
                     "journal.partition_append", "eventserver.drain_partition"):
        assert new_site in sites
