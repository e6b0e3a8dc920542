"""Test fixtures.

The analog of the reference's ``SharedSparkContext``/``LocalSparkContext``
(reference: core/src/test/scala/io/prediction/workflow/BaseTest.scala):
where the reference stands in a `local[4]` Spark for a cluster, we stand in
an 8-device virtual CPU mesh for a TPU pod slice. Must set XLA_FLAGS before
jax initializes, hence module-level os.environ mutation here.
"""

import os

# Force, don't setdefault: the ambient env may point JAX at a real
# accelerator — tests always run on the virtual CPU mesh.
import re as _re

_flags = _re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", ""),
)
os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from predictionio_tpu.storage import Storage  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multihost: spawns multiple jax.distributed CPU processes")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection resilience tests (CPU-fast, deterministic "
        "via predictionio_tpu.faults; guarded by a per-test "
        "SIGALRM timeout so an injected hang cannot wedge the suite)")
    config.addinivalue_line(
        "markers",
        "ingest: durable event-ingestion tests (the write-ahead journal, "
        "drainer and backpressure surfaces — test_journal.py and "
        "test_ingest_durability.py); select with -m ingest")
    config.addinivalue_line(
        "markers",
        "train_chaos: training-resilience fault-injection tests (the "
        "TrainSupervisor retry/resume/heartbeat/budget surfaces, orphan "
        "reaping and blob-integrity fallback — test_train_supervision.py); "
        "shares the chaos guard's SIGALRM timeout and fault cleanup; "
        "select with -m train_chaos")
    config.addinivalue_line(
        "markers",
        "overload: admission-control / backpressure / brownout tests "
        "(workflow/admission.py, the engine server's overload surfaces "
        "and the event server's 429 path — test_overload.py); chaos-"
        "guarded when also marked chaos; select with -m overload")
    config.addinivalue_line(
        "markers",
        "streaming: streaming online-learning tests (the journal-tailing "
        "fold-in updater, the /reload/delta hot-patch path and the "
        "eval-gated promotion — workflow/streaming.py, "
        "storage/journal.py JournalFollower; test_streaming.py); shares "
        "the chaos guard's SIGALRM timeout and fault cleanup; select "
        "with -m streaming")
    config.addinivalue_line(
        "markers",
        "replay: capture/replay parity tests (the golden-traffic capture "
        "ring, deterministic replay diffing and the provenance envelope "
        "— obs/capture.py, obs/replay.py; test_capture_replay.py); "
        "shares the chaos guard's SIGALRM timeout; select with -m replay")
    config.addinivalue_line(
        "markers",
        "multiengine: multi-variant serving tests (the VariantTable "
        "router, hashed A/B splitting, per-variant admission/SLO/delta "
        "isolation and the variant lifecycle endpoints — "
        "workflow/variants.py; test_variants.py); shares the chaos "
        "guard's SIGALRM timeout; select with -m multiengine")
    config.addinivalue_line(
        "markers",
        "retrieval: ANN / exact retrieval tests (the quantized IVF index, "
        "its exact-fallback and parity contracts, and the adaptive "
        "shard-count cost model — ops/ann.py, ops/retrieval.py; "
        "test_ann.py); select with -m retrieval")
    config.addinivalue_line(
        "markers",
        "tune: hyperparameter-sweep tests (the mesh-packed train_als_grid "
        "program and its bitwise-parity contract, TuneSupervisor trial "
        "isolation, eval-gated winner promotion and the tune.trial chaos "
        "site — workflow/tuning.py, models/als.py train_als_grid; "
        "test_tuning.py); shares the chaos guard's SIGALRM timeout and "
        "fault cleanup; select with -m tune")
    config.addinivalue_line(
        "markers",
        "fleet: serving-fleet tests (the FleetRouter routing tier — "
        "consistent-hash routing, per-replica breakers, hedged retry, "
        "delta fan-out with epoch reconciliation, and the kill-a-"
        "replica acceptance gate — workflow/fleet.py; test_fleet.py); "
        "shares the chaos guard's SIGALRM timeout and fault cleanup; "
        "select with -m fleet")
    config.addinivalue_line(
        "markers",
        "selfheal: fleet self-healing tests (the FleetSupervisor "
        "reap/respawn/quarantine lifecycle, durable router state with "
        "journal-replay recovery, crash-safe fleet.json, and the "
        "supervisor.respawn / router.state_write chaos sites — "
        "workflow/supervise.py, workflow/fleet.py; test_selfheal.py); "
        "shares the chaos guard's SIGALRM timeout and fault cleanup; "
        "select with -m selfheal")
    config.addinivalue_line(
        "markers",
        "obsfleet: fleet observability tests (the router-side "
        "FleetCollector scrape/merge plane — exact cross-replica metric "
        "aggregation, fleet SLO, outlier detection, incident bundles and "
        "cross-process trace assembly — obs/aggregate.py, "
        "workflow/fleet.py; test_fleet_obs.py); shares the chaos guard's "
        "SIGALRM timeout and fault cleanup; select with -m obsfleet")
    config.addinivalue_line(
        "markers",
        "dr: disaster-recovery tests (cross-store backup/restore with "
        "manifest-complete semantics, point-in-time WAL replay, fsck "
        "invariant audits, and the backup.copy / restore.apply chaos "
        "sites — storage/backup.py; test_backup.py); shares the chaos "
        "guard's SIGALRM timeout and fault cleanup; select with -m dr")


#: Hard per-test budget for chaos tests. Injected hangs are capped at
#: FaultSpec.max_hang_s (default 30 s) well below this; the alarm is the
#: backstop that keeps a buggy recovery path from eating the tier-1
#: 870 s budget.
CHAOS_TEST_TIMEOUT_S = 120


@pytest.fixture(autouse=True)
def _chaos_guard(request):
    """For @pytest.mark.chaos / @pytest.mark.train_chaos tests: arm a
    SIGALRM watchdog (pytest-timeout is not in the image) and always
    disarm every injected fault on teardown — a leaked armed fault would
    poison unrelated tests."""
    if (request.node.get_closest_marker("chaos") is None
            and request.node.get_closest_marker("train_chaos") is None
            and request.node.get_closest_marker("streaming") is None
            and request.node.get_closest_marker("replay") is None
            and request.node.get_closest_marker("multiengine") is None
            and request.node.get_closest_marker("tune") is None
            and request.node.get_closest_marker("fleet") is None
            and request.node.get_closest_marker("selfheal") is None
            and request.node.get_closest_marker("obsfleet") is None
            and request.node.get_closest_marker("dr") is None):
        yield
        return

    import signal

    from predictionio_tpu.faults import FAULTS

    def _expired(signum, frame):
        FAULTS.clear()  # release hung threads before failing the test
        raise TimeoutError(
            f"chaos test exceeded {CHAOS_TEST_TIMEOUT_S}s guard")

    old_handler = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, CHAOS_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)
        FAULTS.clear()


#: Hard per-test budget for multihost tests. The subprocess helpers in
#: test_multihost.py already bound each worker's communicate(); this
#: alarm is the outer backstop that keeps a wedged barrier or stuck
#: spawn from eating the tier-1 870 s budget.
MULTIHOST_TEST_TIMEOUT_S = 360


@pytest.fixture(autouse=True)
def _multihost_guard(request):
    """For @pytest.mark.multihost tests: SIGALRM watchdog above the
    per-worker subprocess timeouts (pytest-timeout is not in the image).
    Composes with _chaos_guard by arming only when that guard didn't."""
    if (request.node.get_closest_marker("multihost") is None
            or request.node.get_closest_marker("chaos") is not None
            or request.node.get_closest_marker("train_chaos") is not None
            or request.node.get_closest_marker("streaming") is not None
            or request.node.get_closest_marker("multiengine") is not None
            or request.node.get_closest_marker("tune") is not None):
        yield
        return

    import signal

    def _expired(signum, frame):
        raise TimeoutError(
            f"multihost test exceeded {MULTIHOST_TEST_TIMEOUT_S}s guard")

    old_handler = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, MULTIHOST_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.fixture(autouse=True)
def clean_storage():
    """Fresh in-memory storage per test (the reference drops HBase
    namespaces between specs, StorageTestUtils.scala:16-40)."""
    Storage.reset()
    Storage.configure("METADATA", "memory")
    Storage.configure("EVENTDATA", "memory")
    Storage.configure("MODELDATA", "memory")
    yield
    Storage.reset()


@pytest.fixture(autouse=True)
def _reset_metrics(tmp_path):
    """Zero the process-wide telemetry registry between tests. reset()
    zeroes values IN PLACE, so the metric handles subsystems captured at
    import time stay valid — a test asserting on a counter always starts
    from 0 without re-importing the world.

    The flight recorder (also process-wide) resets too, with its
    incident-dump directory pointed INTO the test's tmp dir — a chaos
    test tripping the watchdog must never write to ~/.pio_tpu."""
    from predictionio_tpu.obs.device import LEDGER
    from predictionio_tpu.obs.flight import FLIGHT
    from predictionio_tpu.obs.metrics import METRICS
    from predictionio_tpu.obs.training import TRAINING

    METRICS.reset()
    FLIGHT.reset()
    LEDGER.reset()
    TRAINING.reset()
    FLIGHT.configure(capacity=256, dump_dir=str(tmp_path / "flight"),
                     cooldown_s=30.0)
    yield
    METRICS.reset()
    FLIGHT.reset()
    LEDGER.reset()
    TRAINING.reset()


@pytest.fixture(scope="session")
def mesh8():
    from predictionio_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh((4, 2), ("data", "model"))


@pytest.fixture
def rng():
    return np.random.default_rng(7)
