"""Fleet self-healing (ISSUE 18): the FleetSupervisor replica
lifecycle — poll-reaping, jittered-exponential-backoff respawn on the
original port, crash-loop quarantine with cooldown release, and the
canary-gated rolling restart wave — plus the router's durable state
(epoch marker + CRC-framed delta journal under ``state_dir``) that
makes a router restart resume at the durable epoch floor and bridge a
lagging replica by journal REPLAY instead of a full reload, and the
crash-safe fleet pidfile (tmp+fsync+rename, PID-staleness detection).

Unit tests drive the supervisor over throwaway ``sys.executable -c``
children (deaths, exit codes and pids are real; readiness is served by
in-process stub replicas); acceptance test A supervises REAL stub
subprocesses under a live router and a concurrent query hammer through
five SIGKILLs; acceptance test B kills a DURABLE router mid-traffic
over real trained engine replicas and proves journal-replay recovery
with 100% bitwise capture-replay parity.
"""

from __future__ import annotations

import json
import logging
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest
import requests

from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.storage.journal import JournalFull
from predictionio_tpu.workflow import fleet as fleet_mod
from predictionio_tpu.faults import FAULTS, FaultInjected
from predictionio_tpu.workflow.fleet import (
    DEADLINE_HEADER,
    FleetRouter,
    RouterStateStore,
    create_fleet_app,
    fleet_state_path,
    read_fleet_state,
    reap_replicas,
    write_fleet_state,
)
from predictionio_tpu.workflow.supervise import FleetSupervisor
from tests.helpers import ServerThread
from tests.test_fleet import _Fleet, _stub_state
from tests.test_resilience import _poll

REPO = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.selfheal


# ---------------------------------------------------------------------------
# throwaway children: real processes, real pids, real exit codes


def _sleeper() -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(300)"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _crasher(rc: int = 7) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", f"import sys; sys.exit({rc})"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _dead_child() -> subprocess.Popen:
    """An already-exited, already-reaped child (rolling-restart tests
    skip the graceful-stop wait for a dead proc)."""
    p = _crasher(0)
    p.wait(timeout=10)
    return p


class _FakeRouter:
    """Records the supervisor's cross-thread contacts."""

    canary_sample = 0
    canary_max_mismatch = 0.25

    def __init__(self):
        self.quarantine_calls: list[tuple[str, bool]] = []
        self.drain_calls: list[tuple[str, bool]] = []

    def set_quarantined(self, name, active):
        self.quarantine_calls.append((name, active))
        return True

    def set_admin_drained(self, name, active):
        self.drain_calls.append((name, active))
        return True


def _sup(spawn, n=1, **kw) -> FleetSupervisor:
    kw.setdefault("backoff_base_s", 0.02)
    kw.setdefault("backoff_cap_s", 0.2)
    kw.setdefault("poll_interval_s", 0.02)
    kw.setdefault("rng", random.Random(7))
    reps = [{"name": f"r{i}", "port": 50000 + i,
             "url": f"http://127.0.0.1:{50000 + i}"} for i in range(n)]
    return FleetSupervisor(spawn, reps, **kw)


# ---------------------------------------------------------------------------
# backoff policy: jittered exponential, strictly increasing, capped


def test_backoff_delay_grows_strictly_and_caps():
    sup = _sup(lambda rep: _sleeper(), backoff_base_s=0.5,
               backoff_cap_s=8.0, rng=random.Random(3))
    delays = [sup._backoff_delay(n) for n in range(1, 7)]
    for n, d in enumerate(delays, start=1):
        raw = min(8.0, 0.5 * 2 ** (n - 1))
        assert 0.8 * raw <= d <= 1.2 * raw, (n, d)
    # the ±20% jitter band is narrower than the doubling, so successive
    # delays grow strictly until the cap flattens them
    for a, b in zip(delays, delays[1:]):
        if b < 8.0 * 0.8:
            assert b > a, delays
    assert delays[-1] <= 8.0 * 1.2


# ---------------------------------------------------------------------------
# reap + respawn lifecycle (single-stepped: tests call poll() directly)


def test_supervisor_reaps_and_logs_exit_code(caplog):
    sup = _sup(lambda rep: _crasher(3))
    rep = sup.replica("r0")
    with caplog.at_level(logging.WARNING,
                         logger="predictionio_tpu.workflow.supervise"):
        sup.poll()                      # pending -> initial spawn
        rep.proc.wait(timeout=10)       # child exits rc=3
        sup.poll()                      # reap: death observed
    assert rep.proc.poll() == 3         # reaped, not a zombie
    assert rep.state == "backoff" and rep.last_exit == 3
    assert METRICS.get("pio_fleet_supervisor_deaths_total").value("r0") == 1
    msg = "\n".join(r.getMessage() for r in caplog.records)
    assert "rc=3" in msg and str(rep.port) in msg


def test_respawn_after_backoff_on_original_port_with_new_pid():
    sup = _sup(lambda rep: _sleeper())
    rep = sup.replica("r0")
    try:
        sup.poll()
        pid0 = rep.proc.pid
        rep.proc.kill()
        rep.proc.wait(timeout=10)
        sup.poll()
        assert rep.state == "backoff" and rep.last_backoff_s > 0
        assert _poll(lambda: (sup.poll() or rep.state == "running"),
                     timeout_s=5, interval_s=0.02)
        assert rep.proc.pid != pid0 and rep.proc.poll() is None
        assert rep.port == 50000        # the ORIGINAL port, always
        assert rep.respawns == 1
        assert METRICS.get(
            "pio_fleet_supervisor_respawns_total").value("r0") == 1
    finally:
        sup.terminate_all()


def test_crash_loop_quarantine_then_cooldown_release():
    """max_respawns deaths inside the window -> quarantined (router
    told, state file rewritten, gauge up); after the cooldown the
    replica is retried and — now healthy — released everywhere."""
    broken = [True]
    router = _FakeRouter()
    writes = []
    sup = _sup(lambda rep: _crasher(9) if broken[0] else _sleeper(),
               router=router, max_respawns=3, crash_window_s=30.0,
               quarantine_s=0.3, state_writer=lambda s: writes.append(
                   [r.state for r in s.replicas]))
    rep = sup.replica("r0")
    try:
        for _ in range(40):
            sup.poll()
            if rep.state == "quarantined":
                break
            if rep.proc is not None:
                try:
                    rep.proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass
            time.sleep(0.03)
        assert rep.state == "quarantined"
        assert len(rep.deaths) == 3
        assert router.quarantine_calls == [("r0", True)]
        assert writes and writes[-1] == ["quarantined"]
        assert METRICS.get(
            "pio_fleet_supervisor_quarantined").value("r0") == 1
        # quarantined replicas are NOT respawned during the cooldown
        sup.poll()
        assert rep.state == "quarantined"

        broken[0] = False               # the bad blob/port got fixed
        assert _poll(lambda: (sup.poll() or rep.state == "running"),
                     timeout_s=5, interval_s=0.05)
        assert rep.proc.poll() is None
        assert router.quarantine_calls[-1] == ("r0", False)
        assert METRICS.get(
            "pio_fleet_supervisor_quarantined").value("r0") == 0
    finally:
        sup.terminate_all()


def test_respawn_fault_counts_as_death_and_backs_off():
    """chaos site supervisor.respawn: a failed exec is a death against
    the crash window — backoff, never a busy loop."""
    FAULTS.inject("supervisor.respawn", "error", times=1)
    sup = _sup(lambda rep: _sleeper())
    rep = sup.replica("r0")
    try:
        sup.poll()                      # initial spawn hits the fault
        assert rep.state == "backoff" and rep.last_exit is None
        assert len(rep.deaths) == 1
        assert METRICS.get("pio_fleet_supervisor_deaths_total").value(
            "r0") == 1
        assert FAULTS.fired("supervisor.respawn") == 1
        assert _poll(lambda: (sup.poll() or rep.state == "running"),
                     timeout_s=5, interval_s=0.02)
        assert rep.proc.poll() is None
    finally:
        sup.terminate_all()


def test_clean_exit_is_operator_stop_not_a_crash():
    """rc == 0 is operator intent (`pio fleet drain --stop`, a direct
    /stop): the replica goes to `stopped` — never respawned, never
    counted toward the crash window, so repeated graceful stops can't
    quarantine a healthy replica."""
    writes = []
    sup = _sup(lambda rep: _crasher(0), max_respawns=2,
               state_writer=lambda s: writes.append(
                   [r.state for r in s.replicas]))
    rep = sup.replica("r0")
    sup.poll()                          # pending -> initial spawn
    rep.proc.wait(timeout=10)           # child exits rc=0
    sup.poll()                          # reap: clean exit observed
    assert rep.state == "stopped" and rep.last_exit == 0
    assert len(rep.deaths) == 0         # nothing toward the crash window
    sup.poll()                          # and it STAYS stopped
    assert rep.state == "stopped" and rep.respawns == 0
    assert writes and writes[-1] == ["stopped"]


def test_context_manager_terminates_the_whole_brood():
    with _sup(lambda rep: _sleeper(), n=2) as sup:
        assert _poll(lambda: all(r.proc is not None and r.proc.poll() is None
                                 for r in sup.replicas), timeout_s=5)
        procs = [r.proc for r in sup.replicas]
    for p in procs:
        assert p.poll() is not None     # terminated AND reaped
    assert all(r.state == "stopped" for r in sup.replicas)
    assert METRICS.get("pio_fleet_supervisor_children").value() == 0


# ---------------------------------------------------------------------------
# spawn_replicas child hygiene (satellite 2)


def test_reap_replicas_logs_nonzero_exit_with_port(caplog):
    good, bad = _sleeper(), _crasher(5)
    good.pio_port = 7001
    bad.pio_port = 7002
    try:
        bad.wait(timeout=10)
        with caplog.at_level(logging.WARNING,
                             logger="predictionio_tpu.workflow.fleet"):
            exited = reap_replicas([good, bad])
        assert exited == [(7002, 5)]
        msg = "\n".join(r.getMessage() for r in caplog.records)
        assert "7002" in msg and "rc=5" in msg
        assert reap_replicas([good, bad]) == [(7002, 5)]  # poll, no wait
    finally:
        good.kill()
        good.wait(timeout=10)


def test_terminate_broods_sweeps_stranded_children():
    p = _sleeper()
    brood = [p]
    fleet_mod._BROODS.append(brood)
    try:
        fleet_mod._terminate_broods()
        assert p.poll() is not None     # terminated and reaped
    finally:
        fleet_mod._BROODS.remove(brood)


def test_prune_broods_drops_exited_children():
    """Every supervisor respawn routes through spawn_replicas; without
    pruning, dead Popen references accumulate in _BROODS forever in a
    long-lived supervised fleet."""
    live, dead = _sleeper(), _dead_child()
    brood = [live, dead]
    all_dead = [_dead_child()]
    fleet_mod._BROODS.extend([brood, all_dead])
    try:
        fleet_mod._prune_broods()
        assert brood == [live]          # pruned IN PLACE (callers keep
        assert brood in fleet_mod._BROODS   # their list identity)
        assert all_dead not in fleet_mod._BROODS
    finally:
        live.kill()
        live.wait(timeout=10)
        if brood in fleet_mod._BROODS:
            fleet_mod._BROODS.remove(brood)


# ---------------------------------------------------------------------------
# crash-safe fleet state file (satellites 1 + 3)


def test_fleet_state_corruption_is_no_fleet_not_a_traceback(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    p = tmp_path / "run" / "fleet.json"
    p.parent.mkdir(parents=True)
    for garbage in (b"\x00\x7f not json", b'{"routerUrl": "http://x', b"[1]",
                    b""):
        p.write_bytes(garbage)
        assert read_fleet_state() is None, garbage
    p.unlink()
    assert read_fleet_state() is None   # missing file: same answer


def test_fleet_state_pid_staleness(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    # live pid (this process) -> not stale
    write_fleet_state("http://127.0.0.1:8000",
                      [{"name": "r0", "url": "http://127.0.0.1:8001",
                        "pid": os.getpid()}], router_pid=os.getpid())
    st = read_fleet_state()
    assert st is not None and st["stale"] is False
    assert st["routerPid"] == os.getpid()
    # every recorded pid dead -> stale
    dead = _dead_child().pid
    write_fleet_state("http://127.0.0.1:8000",
                      [{"name": "r0", "url": "http://127.0.0.1:8001",
                        "pid": dead}], router_pid=dead)
    assert read_fleet_state()["stale"] is True
    # no pids recorded at all (remote replicas) -> never stale
    write_fleet_state("http://127.0.0.1:8000",
                      [{"name": "r0", "url": "http://127.0.0.1:8001",
                        "pid": None}])
    assert read_fleet_state()["stale"] is False


def test_state_write_killed_mid_write_preserves_previous_file(
        tmp_path, monkeypatch):
    """chaos site router.state_write fires in the widest kill window
    (tmp durable, rename pending): the PREVIOUS complete state file
    must survive, with no torn bytes and no leftover tmp."""
    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    p = write_fleet_state("http://127.0.0.1:9001",
                          [{"name": "r0", "url": "u0", "pid": None}])
    FAULTS.inject("router.state_write", "error", times=1)
    with pytest.raises(FaultInjected):
        write_fleet_state("http://127.0.0.1:9002",
                          [{"name": "r1", "url": "u1", "pid": None}])
    st = read_fleet_state()
    assert st is not None and st["routerUrl"] == "http://127.0.0.1:9001"
    assert not list(p.parent.glob("*.tmp"))
    # and the very next write (fault disarmed) goes through atomically
    write_fleet_state("http://127.0.0.1:9002",
                      [{"name": "r1", "url": "u1", "pid": None}])
    assert read_fleet_state()["routerUrl"] == "http://127.0.0.1:9002"


def test_concurrent_state_writes_do_not_collide(tmp_path, monkeypatch):
    """write_fleet_state is called concurrently by the supervisor
    thread (state_writer on respawn/quarantine) and the CLI main
    thread: each write must use its OWN tmp file so interleaved
    writers can't rename each other's tmp out from underneath."""
    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    errs: list[BaseException] = []

    def writer(n: int) -> None:
        try:
            for _ in range(25):
                write_fleet_state(
                    f"http://127.0.0.1:{9000 + n}",
                    [{"name": "r0", "url": "u0", "pid": None}])
        except BaseException as e:  # noqa: BLE001 — the test's assertion
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs, errs[:3]
    st = read_fleet_state()
    assert st is not None and st["routerUrl"].startswith("http://127.0.0.1:900")
    assert not list(fleet_state_path().parent.glob("*.tmp"))


def test_pio_fleet_status_reports_stale_state_file(tmp_path):
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    dead = _dead_child().pid
    (tmp_path / "run").mkdir(parents=True)
    (tmp_path / "run" / "fleet.json").write_text(json.dumps({
        "routerUrl": "http://127.0.0.1:65000", "routerPid": dead,
        "replicas": [{"name": "r0", "url": "http://127.0.0.1:65001",
                      "pid": dead}]}))
    out = subprocess.run([str(REPO / "bin" / "pio"), "fleet", "status"],
                         capture_output=True, text=True, env=env,
                         timeout=60)
    assert out.returncode == 1
    assert "fleet not running (stale state file)" in out.stderr
    out = subprocess.run([str(REPO / "bin" / "pio"), "status"],
                         capture_output=True, text=True, env=env,
                         timeout=60)
    assert out.returncode == 0, out.stderr
    assert "not running (stale state file" in out.stdout


# ---------------------------------------------------------------------------
# RouterStateStore: the durable epoch floor + delta journal


def test_router_state_store_roundtrip_and_marker_crash(tmp_path):
    sd = tmp_path / "router-state"
    store = RouterStateStore(sd)
    store.append(1, b'{"users": {"a": [1.0]}}')
    store.append(2, b'{"users": {"b": [2.0]}}')
    store.close()
    epoch, entries = RouterStateStore(sd).load()
    assert epoch == 2
    assert [e for e, _ in entries] == [1, 2]
    assert json.loads(entries[1][1])["users"] == {"b": [2.0]}
    # marker lost to a crash (written AFTER the journal append): the
    # journal's last record still floors the epoch
    (sd / "epoch.json").unlink()
    epoch, entries = RouterStateStore(sd).load()
    assert epoch == 2 and len(entries) == 2


def test_write_epoch_never_regresses(tmp_path):
    """Marker writes come from concurrent to_thread workers (delta
    appends, amnesia adoptions for several replicas probed at once): a
    late writer carrying a LOWER epoch must not clobber a marker that
    already got further."""
    sd = tmp_path / "rs"
    store = RouterStateStore(sd)
    store.write_epoch(3)
    store.write_epoch(1)                # the slow loser of the race
    assert json.loads((sd / "epoch.json").read_text())["epoch"] == 3
    store.close()
    # and a reopened store seeds its floor from disk via load()
    store2 = RouterStateStore(sd)
    assert store2.load()[0] == 3
    store2.write_epoch(2)
    assert json.loads((sd / "epoch.json").read_text())["epoch"] == 3


def test_router_state_store_append_raises_when_gc_cannot_free(tmp_path):
    """If the drop-oldest GC loop exhausts its retry budget without
    ever appending, append must RAISE (handler 500s, updater retries)
    — never fall through to publishing an epoch marker for a delta
    that was not made durable."""
    store = RouterStateStore(tmp_path / "rs")

    class _StuckJournal:
        """Always full; GC 'frees' a byte per pass, so every retry
        passes the progress check yet the append never fits."""

        size = 1 << 20

        def append(self, payload):
            raise JournalFull("still full")

        def peek_batch(self, n):
            return [b"x"], (0, 0, 0)

        def advance(self, pos):
            _StuckJournal.size -= 1

        def size_bytes(self):
            return _StuckJournal.size

        def close(self):
            pass

    store._journal = _StuckJournal()
    with pytest.raises(JournalFull):
        store.append(1, b'{"users": {"a": [1.0]}}')
    # durability before visibility: no marker for the lost delta
    assert not (tmp_path / "rs" / "epoch.json").exists()


# ---------------------------------------------------------------------------
# durable router over stub replicas: restart without amnesia


def test_router_restart_resumes_durable_epoch_and_replays_journal(tmp_path):
    """Two deltas through a DURABLE router, the second missing one
    replica (armed fan-out fault). A brand-new router process over the
    same state_dir starts AT the durable epoch floor and bridges the
    lagging replica by journal REPLAY — never a full reload."""
    sd = str(tmp_path / "router-state")
    # probe_interval 30 s: after the startup round the first router
    # never probes again, so the lag survives until the restart
    f = _Fleet(2, router_kw={"state_dir": sd, "probe_interval_s": 30.0})
    st2 = None
    try:
        r = requests.post(f.url + "/reload/delta",
                          json={"users": {"d1": [0.1, 0.2]}}, timeout=10)
        assert r.status_code == 200
        assert r.json()["applied"] == ["r0", "r1"]

        FAULTS.inject("fleet.delta_fanout", "error", times=1)
        r = requests.post(f.url + "/reload/delta",
                          json={"users": {"d2": [0.3, 0.4]}}, timeout=10)
        assert r.status_code == 200
        applied = r.json()["applied"]
        assert len(applied) == 1        # exactly one replica lagged
        lagger = ({"r0", "r1"} - set(applied)).pop()
        assert f.router.fleet_epoch == 2
        assert requests.get(f.url + "/fleet.json",
                            timeout=10).json()["durable"] is True

        f.st.stop()                     # the router process "dies"

        router2 = FleetRouter([s.url for s in f.stubs], state_dir=sd,
                              probe_interval_s=0.15, probe_timeout_s=1.0,
                              breaker_reset_s=0.4)
        # resumed BEFORE serving anything: the durable floor, not 0
        assert router2.fleet_epoch == 2
        assert len(router2._journal) == 2
        assert METRICS.get("pio_fleet_epoch_floor").value() == 2

        st2 = ServerThread(lambda: create_fleet_app(router2))
        reconcile = METRICS.get("pio_fleet_reconciliations_total")
        assert _poll(
            lambda: reconcile.value(lagger, "replay") == 1
            and set(router2.status()["eligible"]) == {"r0", "r1"},
            timeout_s=10)
        # the gap was bridged by REPLAY: no replica was fully reloaded
        for name in ("r0", "r1"):
            assert reconcile.value(name, "full_reload") == 0
        for s in f.states:
            assert s["reloads"] == 0
            assert s["epoch"] == 2
        lag_state = f.states[int(lagger[1:])]
        assert len(lag_state["deltas"]) == 2    # delta1 fan-out + replay
    finally:
        if st2 is not None:
            st2.stop()
        for s in f.stubs:
            try:
                s.stop()
            except Exception:  # noqa: BLE001
                pass


def test_concurrent_deltas_get_distinct_epochs(tmp_path):
    """Two /reload/delta POSTs in flight at once: the awaited durable
    append yields to the event loop, and without the epoch lock both
    would read the same fleet_epoch and journal two DIFFERENT deltas
    under ONE epoch — a replica that applied only the first would look
    fully synced and the second delta would never be reconciled."""
    sd = str(tmp_path / "router-state")
    f = _Fleet(2, router_kw={"state_dir": sd})
    orig_append = f.router._store.append

    def slow_append(epoch: int, raw: bytes) -> None:
        time.sleep(0.15)                # widen the allocate->bump window
        orig_append(epoch, raw)

    f.router._store.append = slow_append
    epochs: list[int] = []

    def post(n: int) -> None:
        r = requests.post(f.url + "/reload/delta",
                          json={"users": {f"c{n}": [0.1, 0.2]}},
                          timeout=15)
        assert r.status_code == 200, r.text
        epochs.append(r.json()["epoch"])

    try:
        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        assert sorted(epochs) == [1, 2]     # DISTINCT epochs, no reuse
        assert f.router.fleet_epoch == 2
        assert [e for e, _ in f.router._journal] == [1, 2]
    finally:
        f.close()
    # and the durable journal agrees: one record per epoch
    durable_epochs = [e for e, _ in RouterStateStore(sd).load()[1]]
    assert durable_epochs == [1, 2]


def test_replica_ahead_of_router_is_router_amnesia(tmp_path):
    """A replica reporting a patch epoch AHEAD of a freshly started
    router means the ROUTER lost its durable state — it adopts the
    replica's floor (and re-persists it) instead of reloading the
    healthy replica."""
    sd = tmp_path / "amnesic-state"
    states = [_stub_state("s0", epoch=3), _stub_state("s1", epoch=1)]
    f = _Fleet(2, states=states,
               router_kw={"state_dir": str(sd), "probe_interval_s": 0.15})
    try:
        assert _poll(lambda: f.router.fleet_epoch == 3, timeout_s=10)
        assert METRICS.get("pio_fleet_router_amnesia_total").value() >= 1
        # the AHEAD replica is trusted, never resynced
        assert states[0]["reloads"] == 0
        assert _poll(
            lambda: set(f.router.status()["eligible"]) == {"r0", "r1"},
            timeout_s=10)
        # the adopted floor is persisted durably for the NEXT restart
        assert _poll(
            lambda: (sd / "epoch.json").exists()
            and json.loads((sd / "epoch.json").read_text())["epoch"] == 3,
            timeout_s=10)
    finally:
        f.close()


# ---------------------------------------------------------------------------
# quarantine + restart admin surfaces on the router


def test_fleet_quarantine_endpoint_and_eligibility():
    f = _Fleet(2)
    try:
        r = requests.post(f.url + "/fleet/quarantine",
                          json={"replica": "r0"}, timeout=10)
        assert r.status_code == 200 and r.json()["message"] == "quarantined"
        fj = requests.get(f.url + "/fleet.json", timeout=10).json()
        assert fj["quarantined"] == ["r0"]
        assert fj["eligible"] == ["r1"]
        # traffic keeps flowing, all of it to the survivor
        for i in range(6):
            resp = f.post({"user": f"u{i}", "num": 1})
            assert resp.status_code == 200
            assert f.replica_of(resp) == "r1"
        r = requests.post(f.url + "/fleet/quarantine",
                          json={"replica": "r0", "active": False},
                          timeout=10)
        assert r.status_code == 200 and r.json()["message"] == "released"
        assert _poll(
            lambda: set(f.router.status()["eligible"]) == {"r0", "r1"},
            timeout_s=10)
        r = requests.post(f.url + "/fleet/quarantine",
                          json={"replica": "nope"}, timeout=10)
        assert r.status_code == 404
    finally:
        f.close()


def test_fleet_restart_without_supervisor_is_409():
    f = _Fleet(2)
    try:
        r = requests.post(f.url + "/fleet/restart", timeout=10)
        assert r.status_code == 409
        assert "--supervise" in r.json()["message"]
    finally:
        f.close()


def _attach_supervisor(f: _Fleet, *, dead: bool = True,
                       **kw) -> FleetSupervisor:
    """A supervisor whose children are throwaway procs but whose
    readiness URLs are the fleet's stub replicas (so a 'restarted'
    replica reports ready immediately)."""
    sup = FleetSupervisor(
        lambda rep: _sleeper(),
        [{"name": f"r{i}", "port": 50100 + i, "url": f.stubs[i].url}
         for i in range(len(f.stubs))],
        router=f.router, backoff_base_s=0.02, poll_interval_s=0.02,
        ready_timeout_s=10.0, **kw)
    for i in range(len(f.stubs)):
        sup.adopt(f"r{i}", _dead_child() if dead else _sleeper())
    f.router.supervisor = sup
    return sup


def test_rolling_restart_wave_over_http():
    """`pio fleet restart` end-to-end: drain -> restart -> re-ready one
    replica at a time; every replica gets a fresh pid, nobody stays
    admin-drained, and the wave reports per-replica timings."""
    f = _Fleet(2)
    sup = _attach_supervisor(f)
    pids = [sup.replica(n).proc.pid for n in ("r0", "r1")]
    try:
        r = requests.post(f.url + "/fleet/restart?canary=0", timeout=60)
        assert r.status_code == 200, r.text
        out = r.json()
        assert out["outcome"] == "ok"
        assert out["restarted"] == 2 and out["replicas"] == 2
        assert [w["replica"] for w in out["wave"]] == ["r0", "r1"]
        assert all(w["ok"] and w["restartS"] >= 0 for w in out["wave"])
        for n, old in zip(("r0", "r1"), pids):
            rep = sup.replica(n)
            assert rep.proc.pid != old and rep.proc.poll() is None
            assert rep.state == "running"
        assert set(f.router.status()["eligible"]) == {"r0", "r1"}
        assert METRICS.get(
            "pio_fleet_supervisor_restart_waves_total").value("ok") == 1
    finally:
        sup.terminate_all()
        f.close()


def test_rolling_restart_canary_abort_leaves_rest_of_fleet_untouched():
    """The first restarted replica comes back answering DIFFERENTLY
    (poisoned model): the shadow-diff canary vs a not-yet-restarted
    baseline aborts the wave; the second replica keeps its process."""
    f = _Fleet(2)
    sup = _attach_supervisor(f)
    try:
        for i in range(8):              # fill the router's recent ring
            assert f.post({"user": f"u{i}", "num": 1}).status_code == 200
        f.states[0]["model"] = "poisoned"   # what r0 serves post-restart
        r1_proc = sup.replica("r1").proc
        report = sup.rolling_restart(canary_sample=6, drain_timeout_s=0.2)
        assert report["outcome"] == "canary_abort"
        assert report["restarted"] == 1
        assert report["canary"]["mismatchFraction"] > 0.25
        assert report["canary"]["fresh"] == "r0"
        assert report["canary"]["baseline"] == "r1"
        assert sup.replica("r1").proc is r1_proc    # untouched
        assert METRICS.get(
            "pio_fleet_supervisor_restart_waves_total").value(
                "canary_abort") == 1
        # nobody left admin-drained behind
        assert set(f.router.status()["eligible"]) == {"r0", "r1"}
    finally:
        sup.terminate_all()
        f.close()


# ---------------------------------------------------------------------------
# acceptance A: SIGKILL x5 under load -> backoff respawns, then quarantine


_STUB_REPLICA_SRC = '''
"""Minimal engine-server lookalike for supervisor chaos tests."""
import os, sys
from aiohttp import web

PORT, NAME = int(sys.argv[1]), sys.argv[2]
BOOT = f"{NAME}-{os.getpid()}"
EPOCH = [0]

async def health(request):
    return web.json_response({"status": "ok", "live": True, "ready": True,
                              "startTime": BOOT,
                              "model": {"patchEpoch": EPOCH[0]}})

async def queries(request):
    body = await request.json()
    return web.json_response({"value": body})

async def reload(request):
    return web.json_response({"message": "Reloaded"})

async def reload_delta(request):
    await request.json()
    EPOCH[0] += 1
    return web.json_response({"message": "Patched", "epoch": EPOCH[0]})

async def stop(request):
    import asyncio
    asyncio.get_event_loop().call_later(0.1, os._exit, 0)
    return web.json_response({"message": "Shutting down."})

app = web.Application()
app.router.add_get("/health.json", health)
app.router.add_post("/queries.json", queries)
app.router.add_get("/reload", reload)
app.router.add_post("/reload/delta", reload_delta)
app.router.add_get("/stop", stop)
web.run_app(app, host="127.0.0.1", port=PORT, print=None)
'''


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def test_acceptance_sigkill_x5_backoff_respawns_then_quarantine(tmp_path):
    """ISSUE 18 acceptance (a): two supervised REAL stub subprocesses
    under a live router and a concurrent query hammer. SIGKILL one
    replica 5x: the first four deaths respawn on the original port
    after strictly increasing backoff; the fifth quarantines it (router
    told, traffic redistributed); zero in-deadline requests dropped."""
    stub = tmp_path / "stub_replica.py"
    stub.write_text(_STUB_REPLICA_SRC)
    ports = _free_ports(2)
    urls = [f"http://127.0.0.1:{p}" for p in ports]

    def spawn(rep):
        return subprocess.Popen(
            [sys.executable, str(stub), str(rep.port), rep.name],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    router = FleetRouter(urls, probe_interval_s=0.1, probe_timeout_s=1.0,
                         breaker_reset_s=0.3, dispatch_timeout_s=5.0,
                         max_hedges=1)
    sup = FleetSupervisor(
        spawn,
        [{"name": f"r{i}", "port": ports[i], "url": urls[i]}
         for i in range(2)],
        router=router, max_respawns=5, crash_window_s=60.0,
        quarantine_s=300.0, backoff_base_s=0.05, backoff_cap_s=2.0,
        poll_interval_s=0.05, ready_timeout_s=30.0)
    router.supervisor = sup             # `pio fleet start --supervise`
    st = None
    stop = threading.Event()
    failures: list[str] = []
    n_ok = [0]

    def hammer(seed: int) -> None:
        n = 0
        while not stop.is_set():
            n += 1
            try:
                r = requests.post(
                    st.url + "/queries.json",
                    json={"user": f"u{(seed * 5 + n) % 20}", "num": 1},
                    headers={DEADLINE_HEADER: "8000"}, timeout=10)
            except requests.RequestException as e:
                failures.append(repr(e))
                return
            if r.status_code != 200:
                failures.append(f"{r.status_code}: {r.text[:160]}")
                return
            n_ok[0] += 1

    try:
        sup.start()
        st = ServerThread(lambda: create_fleet_app(router))
        assert _poll(
            lambda: set(router.status()["eligible"]) == {"r0", "r1"},
            timeout_s=30)
        threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        assert _poll(lambda: n_ok[0] >= 10, timeout_s=20)

        rep = sup.replica("r0")
        backoffs = []
        for i in range(1, 5):           # kills 1-4: respawned every time
            os.kill(rep.proc.pid, signal.SIGKILL)
            assert _poll(lambda: rep.respawns >= i, timeout_s=20,
                         interval_s=0.02), f"kill {i} never respawned"
            backoffs.append(rep.last_backoff_s)
            assert _poll(
                lambda: rep.state == "running" and not rep.awaiting_ready
                and "r0" in router.status()["eligible"],
                timeout_s=20), f"kill {i}: r0 never re-readied"
        # jittered exponential: strictly increasing across the window
        assert backoffs == sorted(backoffs) and len(set(backoffs)) == 4, \
            backoffs
        assert backoffs[-1] > backoffs[0] * 2

        os.kill(rep.proc.pid, signal.SIGKILL)       # kill 5: crash loop
        assert _poll(lambda: rep.state == "quarantined", timeout_s=20)
        assert len(rep.deaths) == 5 and rep.respawns == 4
        assert METRICS.get(
            "pio_fleet_supervisor_quarantined").value("r0") == 1
        assert _poll(
            lambda: router.status()["eligible"] == ["r1"], timeout_s=10)
        assert router.status()["quarantined"] == ["r0"]
        assert router.status()["supervisor"]["replicas"][0][
            "state"] == "quarantined"

        # traffic kept flowing through it all
        ok_now = n_ok[0]
        assert _poll(lambda: n_ok[0] > ok_now + 10, timeout_s=20)
        stop.set()
        for t in threads:
            t.join(15)
        assert not failures, failures[:5]   # ZERO dropped in-deadline
    finally:
        stop.set()
        if st is not None:
            st.stop()
        sup.stop()
        sup.terminate_all()


# ---------------------------------------------------------------------------
# acceptance B: kill the DURABLE router mid-traffic; bitwise recovery


def test_acceptance_router_killed_midtraffic_recovers_durably(
        tmp_path, rng):
    """ISSUE 18 acceptance (b): a durable router over two REAL trained
    engine replicas takes two deltas (one replica misses the second via
    an armed fan-out fault) and serves captured traffic. The router is
    then torn down and a NEW router process over the same state_dir
    must (1) resume at the durable fleet epoch, (2) bridge the lagging
    replica by journal REPLAY — not a full reload — and (3) replay the
    pre-kill capture 100% bitwise.

    Durability-before-visibility makes teardown equivalent to SIGKILL
    for this proof: every acked delta was journaled+fsynced BEFORE the
    epoch became visible, so no shutdown hook adds information."""
    from predictionio_tpu.obs.replay import replay_records
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )
    from tests.test_capture_replay import _train_quickstart

    engine, inst = _train_quickstart(tmp_path, rng, "selfhealtest")
    servers = [EngineServer(engine, inst) for _ in range(2)]
    stubs = [ServerThread(lambda s=s: create_engine_server_app(s))
             for s in servers]
    urls = [s.url for s in stubs]
    sd = str(tmp_path / "router-state")
    rank = json.loads((tmp_path / "myrec" / "engine.json").read_text())[
        "algorithms"][0]["params"]["rank"]

    routerA = FleetRouter(urls, state_dir=sd, probe_interval_s=30.0,
                          probe_timeout_s=2.0, dispatch_timeout_s=10.0)
    stA = ServerThread(lambda: create_fleet_app(routerA))
    stA_stopped = False
    stB = None
    try:
        r = requests.post(stA.url + "/reload/delta",
                          json={"users": {"freshA": [0.25] * rank}},
                          timeout=15)
        assert r.status_code == 200
        assert r.json()["applied"] == ["r0", "r1"], r.text

        FAULTS.inject("fleet.delta_fanout", "error", times=1)
        r = requests.post(stA.url + "/reload/delta",
                          json={"users": {"freshB": [0.5] * rank}},
                          timeout=15)
        assert r.status_code == 200
        applied = r.json()["applied"]
        assert len(applied) == 1
        lagger = ({"r0", "r1"} - set(applied)).pop()
        assert routerA.fleet_epoch == 2

        # capture live traffic through the router (trained users only:
        # the replay target must answer from the same factor rows)
        records = []
        for i in range(12):
            q = {"user": f"u{i % 8}", "num": 3}
            resp = requests.post(stA.url + "/queries.json", json=q,
                                 headers={DEADLINE_HEADER: "8000"},
                                 timeout=15)
            assert resp.status_code == 200
            records.append({"request": q, "response": resp.json(),
                            "status": 200})

        stA.stop()                      # the router process dies
        stA_stopped = True

        routerB = FleetRouter(urls, state_dir=sd, probe_interval_s=0.15,
                              probe_timeout_s=2.0, dispatch_timeout_s=10.0)
        assert routerB.fleet_epoch == 2     # durable floor, pre-serving
        stB = ServerThread(lambda: create_fleet_app(routerB))
        reconcile = METRICS.get("pio_fleet_reconciliations_total")
        assert _poll(
            lambda: reconcile.value(lagger, "replay") == 1
            and set(routerB.status()["eligible"]) == {"r0", "r1"},
            timeout_s=20)
        for name in ("r0", "r1"):
            assert reconcile.value(name, "full_reload") == 0
        for s in servers:               # both converged to the live epoch
            assert s.patch_epoch == 2

        report = replay_records(records, target=stB.url)
        assert report["total"] == len(records)
        assert report["tiers"]["bitwise"] == len(records), report["tiers"]
    finally:
        if stB is not None:
            stB.stop()
        if not stA_stopped:
            stA.stop()
        for s in stubs:
            try:
                s.stop()
            except Exception:  # noqa: BLE001
                pass
